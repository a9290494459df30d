"""Mini dry-run: the full lower+compile+roofline path on an 8-device forced
CPU mesh with reduced configs (subprocess so the device-count flag doesn't
leak into other tests).  The production 512-device sweep runs via
``python -m repro.launch.dryrun --all`` (results in results/)."""
import os
import subprocess
import sys
import textwrap

import pytest


def _run(code: str) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env,
                       cwd=__file__.rsplit("/tests/", 1)[0], timeout=600)
    assert r.returncode == 0, (r.stdout[-1500:], r.stderr[-3000:])
    return r.stdout


@pytest.mark.parametrize("arch,shape,strategy", [
    ("qwen3-4b", "train_4k", "split_concurrent"),
    ("dbrx-132b", "decode_32k", "fsdp_tp"),
    ("rwkv6-1.6b", "long_500k", "fsdp_tp"),
])
def test_mini_mesh_lower_compile(arch, shape, strategy):
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, dataclasses
        from repro.configs.base import INPUT_SHAPES, RunConfig, get_smoke_config
        from repro.launch.mesh import make_local_mesh
        from repro.launch.steps import build_step
        from repro.launch.hlo_analysis import roofline_from_compiled

        shape = dataclasses.replace(INPUT_SHAPES["{shape}"], seq_len=256,
                                    global_batch=8)
        cfg = get_smoke_config("{arch}")
        run = RunConfig(strategy="{strategy}")
        mesh = make_local_mesh(data=2, model=4)
        bundle = build_step(cfg, run, shape, mesh)
        lowered = bundle.lower()
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        roof = roofline_from_compiled(compiled, 8, model_flops=1e6)
        assert roof.flops > 0
        assert mem.temp_size_in_bytes >= 0
        print("MINI_DRYRUN_OK", roof.dominant,
              compiled.cost_analysis().get("flops", 0))
    """)
    out = _run(code)
    assert "MINI_DRYRUN_OK" in out


def test_collective_parse_on_real_hlo():
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_local_mesh
        from repro.launch.hlo_analysis import parse_collectives

        mesh = make_local_mesh(data=2, model=4)
        x = jax.ShapeDtypeStruct((8, 512), jnp.float32)
        w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        f = jax.jit(lambda x, w: (x @ w).sum(),
                    in_shardings=(NamedSharding(mesh, P("data", None)),
                                  NamedSharding(mesh, P(None, "model"))))
        comp = f.lower(x, w).compile()
        stats = parse_collectives(comp.as_text())
        # summing a (data,model)-sharded product requires an all-reduce
        assert stats.total_bytes > 0, comp.as_text()[:800]
        print("PARSE_OK", stats.bytes_by_kind)
    """)
    out = _run(code)
    assert "PARSE_OK" in out
