#!/usr/bin/env python3
"""Read the two ends from which a cell's limits are set.

On the chip, for each seed, in one process:

    python3 bench/limits.py --workload fig4.paper16 --seeds 14 \
        --out chiprun_out/limits.fig4.paper16.json

* the lower end: a sound run of the program through the harness with a
  one-second window (the compared numbers come from the set-up's checked
  rounds and the reference);
* the control: the reference computed one precision step below the
  configuration's (``precision="fp8"``);
* the fault "half of the batch left out, the mean taken over the rest",
  planted in the reference put in the program's place;
* the fault "a state left unchanged": the reference's losses at the
  seed's weights, and no gradient and no change.

Each seed's numbers go to ``--out``, and the summary can be made again
from them without a chip:

    python3 bench/limits.py --dump chiprun_out/limits.fig4.paper16.json

The summary gives, for each number, the largest sound reading, the
smallest control and fault readings, and the limit the rule gives: a
third of the way from the lower end to the upper one on a log scale,
more room above the lower end than below the upper one.

The control and each fault have to fail one of the cell's numbers on
every seed.  A fault that reads under 10x the lower end sets no upper
end, and with many rows a round the half-batch fault reads only a few
times the sound runs: where no number's limit lies under a side's
smallest reading, each number that can catch it has its limit lowered
to the log-middle between that reading and the geometric mean of its
two ends, so that the limit keeps more room above the lower end than
below the upper one.  A side that no number can catch is listed under
``uncaught``, and the cell cannot be run as it stands.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import compare  # noqa: E402

# a control has to read 3x the sound runs to set the upper end, a fault
# 10x, a state left unchanged 3x
FACTOR = {"control": 3, "half_batch": 10, "state_unchanged": 3}


def read(workload: str, seeds: list[int]) -> list[dict]:
    """For each seed, every compared number of each side."""
    import harness
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, workload, ROOT)
    harness.use_compile_cache()
    ref = cell.reference()
    frozen = dict(cell.config, optimizer=dict(cell.config["optimizer"],
                                              lr=0.0))
    out = []
    for seed in seeds:
        line = harness.run(cell, seed=seed, seconds=1.0, trace=False,
                           t_start=time.perf_counter(), say=lambda _: None)
        params0 = ref.as_host(ref.init_params(cell.config, seed))
        reference = harness.reference_readings(cell, seed, params0)
        rows = cell.program.round_rows(cell.config, cell.traffic, seed,
                                       harness.CHECKED_ROUNDS)
        unchanged = {
            "losses": ref.train_rounds(frozen, params0, rows)[0],
            "grad": {k: 0 * v for k, v in reference["grad"].items()},
            "delta": {k: 0 * v for k, v in reference["delta"].items()}}
        sides = {
            "control": harness.reference_readings(cell, seed, params0,
                                                  precision="fp8"),
            "half_batch": harness.reference_readings(cell, seed, params0,
                                                     half=True),
            "state_unchanged": unchanged}
        row = {"seed": seed,
               "program": {k: c["value"] for k, c in line["checks"].items()},
               **{k: compare.numbers(v, reference, 0)
                  for k, v in sides.items()}}
        out.append(row)
        print(json.dumps(row), flush=True)
    return out


def limit(lower: float, upper: float) -> float:
    return math.exp(math.log(lower) / 3 + 2 * math.log(upper) / 3)


def lower_to_catch(number: dict, side: str) -> bool:
    """Lower ``number``'s limit under ``side``'s smallest reading where
    that leaves it above the geometric mean of its two ends; True if
    it did."""
    if number["upper"] is None:
        return False
    floor = math.sqrt(number["lower"] * number["upper"])
    end = number["ends"][side]
    if end <= floor:
        return False
    number["limit"] = math.sqrt(floor * end)
    number["lowered_for"] = side
    return True


def summary(dump: list[dict]) -> dict:
    out = {"seeds": [d["seed"] for d in dump], "numbers": {}}
    for k in compare.NUMBERS:
        if k == "stale":
            out["stale"] = max(d["program"][k] for d in dump)
            continue
        lower = max(d["program"][k] for d in dump)
        ends = {side: min(d[side][k] for d in dump) for side in FACTOR}
        upper = min((v for side, v in ends.items()
                     if v >= FACTOR[side] * lower), default=None)
        out["numbers"][k] = {
            "lower": lower, "ends": ends, "upper": upper,
            "limit": limit(lower, upper) if upper else None,
            "program": sorted(d["program"][k] for d in dump)}
    numbers = out["numbers"].values()
    out["uncaught"] = []
    for side in FACTOR:
        if any(n["limit"] is not None and n["ends"][side] > n["limit"]
               for n in numbers):
            continue
        if not [n for n in numbers if lower_to_catch(n, side)]:
            out["uncaught"].append(side)
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload")
    p.add_argument("--seeds", type=int, default=14)
    p.add_argument("--first-seed", type=int, default=2**31 + 101)
    p.add_argument("--out", type=Path)
    p.add_argument("--dump", type=Path)
    args = p.parse_args(argv)
    if args.dump:
        dump = json.loads(args.dump.read_text())
    else:
        dump = read(args.workload, [args.first_seed + 7919 * i
                                    for i in range(args.seeds)])
        if args.out:
            args.out.parent.mkdir(parents=True, exist_ok=True)
            args.out.write_text(json.dumps(dump))
    print(json.dumps(summary(dump), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
