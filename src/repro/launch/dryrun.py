"""Multi-pod dry-run driver: see the usage block below (module docstring
kept minimal because the XLA device-count flag must be set before any
other import)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import: jax locks the device
# count at first initialisation, and the production-mesh dry-run needs 512
# placeholder devices on this CPU-only host.

# Multi-pod dry-run: lower + compile every (architecture x input-shape)
# combination against the production meshes, prove memory/sharding coherence,
# and emit the roofline terms consumed by EXPERIMENTS.md §Dry-run/§Roofline.
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] \
#       --out results/dryrun.jsonl

import argparse
import dataclasses
import gc
import json
import time
import traceback

import jax

from repro.configs.base import (ARCH_IDS, INPUT_SHAPES, RunConfig,
                                get_arch_config)
from repro.launch.hlo_analysis import (Roofline, parse_collectives,
                                       roofline_from_compiled)
from repro.launch.mesh import make_production_mesh
from repro.launch.steps import build_step
from repro.models import flags


def model_flops_for(cfg, shape) -> float:
    """Analytic useful FLOPs (6ND train / 2ND inference) for a shape."""
    from repro.models.model import count_params_analytic

    n = count_params_analytic(cfg, active_only=True)
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: one token per sequence


def _accounting_depths(cfg):
    if cfg.family == "hybrid":
        p = cfg.attn_layer_period
        return p, 2 * p
    return 2, 4


def _reduced_depth(cfg, depth: int):
    kw = {"num_layers": depth}
    if cfg.family == "encdec":
        kw["encoder_layers"] = depth
    return dataclasses.replace(cfg, **kw)


def accounting_costs(cfg, run, shape, mesh) -> dict:
    """XLA's HLO cost analysis counts a while-loop body ONCE regardless of
    trip count (verified empirically; see EXPERIMENTS.md §Dry-run), so
    scanned-layer models under-report FLOPs/bytes.  We therefore compile
    reduced-depth UNROLLED variants at two depths and extrapolate the
    per-layer slope to the full depth.  Memory analysis still comes from
    the full-depth scanned compile (loop buffers are reused, so that one
    is correct as-is)."""
    d1, d2 = _accounting_depths(cfg)
    samples = []
    for d in (d1, d2):
        bundle = build_step(_reduced_depth(cfg, d), run, shape, mesh)
        with flags.unrolled_for_accounting():
            compiled = bundle.lower().compile()
        cost = compiled.cost_analysis()
        coll = parse_collectives(compiled.as_text())
        samples.append({
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": float(coll.total_bytes),
            "coll_by_kind": dict(coll.bytes_by_kind),
        })
        del compiled, bundle
        gc.collect()
    L = cfg.num_layers

    def extrap(key):
        v1, v2 = samples[0][key], samples[1][key]
        slope = (v2 - v1) / (d2 - d1)
        return max(v1 + slope * (L - d1), 0.0)

    kinds = set(samples[0]["coll_by_kind"]) | set(samples[1]["coll_by_kind"])
    coll_by_kind = {}
    for k in kinds:
        v1 = samples[0]["coll_by_kind"].get(k, 0)
        v2 = samples[1]["coll_by_kind"].get(k, 0)
        coll_by_kind[k] = int(max(v1 + (v2 - v1) / (d2 - d1) * (L - d1), 0))
    return {
        "flops_per_device": extrap("flops"),
        "bytes_per_device": extrap("bytes"),
        "collective_bytes_per_device": extrap("coll"),
        "collectives_by_kind": coll_by_kind,
        "accounting_depths": [d1, d2],
    }


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            strategy: str | None = None, verbose: bool = True,
            accounting: bool = True) -> dict:
    """Lower+compile one (arch, shape) combo on the production mesh and
    return its memory/roofline record."""
    shape = INPUT_SHAPES[shape_name]
    cfg = get_arch_config(arch)
    strategy = strategy or ("split_concurrent" if shape.kind == "train"
                            else "fsdp_tp")
    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.launch.steps import resolve_decode_layout
    layout = (resolve_decode_layout(cfg, mesh, "auto")
              if shape.kind == "decode" else "batch_sharded")
    run = RunConfig(arch=arch, shape=shape_name, strategy=strategy,
                    param_dtype="float32" if shape.kind == "train"
                    else "bfloat16", decode_layout=layout,
                    multi_pod=multi_pod)
    chips = mesh.size
    t0 = time.time()
    bundle = build_step(cfg, run, shape, mesh)
    with mesh:
        lowered = bundle.lower()
        compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    stats = parse_collectives(compiled.as_text())
    if accounting:
        acct = accounting_costs(cfg, run, shape, mesh)
        roof = Roofline(
            flops=acct["flops_per_device"] * chips,
            hbm_bytes=acct["bytes_per_device"] * chips,
            collective_bytes=acct["collective_bytes_per_device"] * chips,
            chips=chips,
            model_flops=model_flops_for(bundle.cfg, shape))
        stats.bytes_by_kind = acct["collectives_by_kind"]
    else:
        roof = roofline_from_compiled(
            compiled, chips, model_flops=model_flops_for(bundle.cfg, shape))
    rec = {
        "arch": arch, "shape": shape_name, "strategy": strategy,
        "mesh": "x".join(str(mesh.shape[a]) for a in mesh.axis_names),
        "chips": chips,
        "compile_s": round(t1 - t0, 1),
        "arg_bytes_per_device": mem.argument_size_in_bytes,
        "out_bytes_per_device": mem.output_size_in_bytes,
        "temp_bytes_per_device": mem.temp_size_in_bytes,
        "peak_bytes_per_device": (mem.argument_size_in_bytes
                                  + mem.temp_size_in_bytes),
        "collectives": {k: int(v) for k, v in stats.bytes_by_kind.items()},
        "collective_counts": dict(stats.count_by_kind),
        **roof.as_dict(),
    }
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} ({rec['mesh']}, {strategy}): "
              f"OK compile={rec['compile_s']}s "
              f"args/dev={rec['arg_bytes_per_device']/2**30:.2f}GiB "
              f"temp/dev={rec['temp_bytes_per_device']/2**30:.2f}GiB "
              f"dominant={rec['dominant']} "
              f"t=({roof.t_compute:.4f},{roof.t_memory:.4f},"
              f"{roof.t_collective:.4f})s", flush=True)
    del compiled, lowered, bundle
    gc.collect()
    return rec


def main() -> None:
    """CLI: ``--arch/--shape`` for one combo or ``--all`` for the sweep."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None, help="write JSONL records here")
    args = ap.parse_args()

    combos = []
    if args.all:
        combos = [(a, s) for a in ARCH_IDS for s in INPUT_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        combos = [(args.arch, args.shape)]

    records, failures = [], []
    for arch, shape in combos:
        try:
            records.append(run_one(arch, shape, multi_pod=args.multi_pod,
                                   strategy=args.strategy))
        except Exception as e:  # a failure here is a sharding bug
            failures.append((arch, shape, repr(e)))
            print(f"[dryrun] {arch} x {shape} FAILED: {e}", flush=True)
            traceback.print_exc()
        if args.out:
            with open(args.out, "w") as f:
                for r in records:
                    f.write(json.dumps(r) + "\n")
    print(f"[dryrun] {len(records)} OK, {len(failures)} failed")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
