"""CPU rehearsal of ``chip_smoke.py``: its round loop, at ``FABRIC_CNN``
size with the server-step kernel in interpret mode, must pass every
check the script makes on the chip."""
import importlib.util
import os
import subprocess
import sys
from pathlib import Path

import jax

from repro.configs.paper_cnn import FABRIC_CNN
from repro.launch.compile_cache import use_compile_cache

_ROOT = Path(__file__).resolve().parents[1]
_PATH = _ROOT / "chip_smoke.py"
_spec = importlib.util.spec_from_file_location("chip_smoke", _PATH)
chip_smoke = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(chip_smoke)


def test_chip_smoke_rounds_rehearse_on_cpu():
    lines = []
    result = chip_smoke.run_rounds(FABRIC_CNN, clients=4, rounds=2,
                                   mode="interpret", log=lines.append)
    assert chip_smoke.failures(result, rounds=2, mode="interpret") == []
    assert result["mode"] == "interpret"
    assert result["error_reports"] == 0 and result["stale_executions"] == 0
    assert len(result["losses"]) == 3 and all(result["complete"])
    assert result["losses"][-1] < result["losses"][0]
    assert all(d <= chip_smoke.REL_BOUND * s for d, s in result["diffs"])
    assert len([ln for ln in lines if ln.startswith("round ")]) == 3


def test_chip_smoke_failures_name_each_broken_check():
    good = {"mode": "pallas", "error_reports": 0, "stale_executions": 0,
            "losses": [2.3, 2.1], "complete": [True, True],
            "diffs": [(0.0, 1.0), (1e-7, 1.0)]}
    assert chip_smoke.failures(good, rounds=1, mode="pallas") == []
    bad = dict(good, mode="xla", error_reports=1, stale_executions=2,
               losses=[2.0, 2.4], diffs=[(0.0, 1.0), (1e-3, 1.0)])
    msgs = chip_smoke.failures(bad, rounds=1, mode="pallas")
    assert len(msgs) == 5, msgs
    missing = chip_smoke.failures(dict(good, losses=[2.3]), rounds=1,
                                  mode="pallas")
    assert any("rounds missing" in m for m in missing)


def test_chip_smoke_refuses_to_run_without_a_tpu(capsys):
    assert chip_smoke.main() != 0
    out, err = capsys.readouterr()
    assert "no TPU" in err and '"ok"' not in out


def test_compile_cache_lands_in_the_env_dir(tmp_path):
    code = ("import jax\n"
            "from repro.launch.compile_cache import use_compile_cache\n"
            "use_compile_cache()\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs', 0)\n"
            "jax.jit(lambda x: x + 1)(1.0)\n")
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu", PYTHONPATH=str(_ROOT / "src"))
    subprocess.run([sys.executable, "-c", code], env=env, check=True,
                   timeout=120)
    assert any(tmp_path.iterdir())


def test_compile_cache_defaults_to_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert use_compile_cache() == str(_ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(_ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
