#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run_cell.py --workload fig4.paper16 --seed 7 \
        --seconds 50 --trace 0

Run from the root of a checkout.  Loads, warms up and checks the cell,
measures whole federated rounds for ``--seconds``, and prints the
result as the last line of standard output: ``correct``, ``attempted``,
``failed``, the cell's end-to-end metrics (``--trace 0``) or its
per-layer metrics (``--trace 1``), and the device.  Exits non-zero, with
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]


def parse(argv):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import harness
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, args.workload, ROOT)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"run_cell: {args.workload} needs {cell.chips} TPU chip(s); "
              f"JAX found {len(devices)} {devices[0].platform!r} "
              f"device(s) and does not fall back", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    line = harness.run(cell, seed=args.seed, seconds=args.seconds,
                       trace=bool(args.trace), t_start=T_START)
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
