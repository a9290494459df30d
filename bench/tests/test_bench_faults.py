"""The comparison that decides ``correct`` must catch the faults a
training cell can have.

Each test drives a whole run of a tiny cell past the look for a chip,
with the program's server step broken underneath, and sees ``correct``
come out false: a step that returns its state unchanged, and a step
that leaves half of the batch's gradients out and takes the mean over
the rest.  A single chip exchanges nothing between chips, so that fault
does not exist here.  The control, the reference itself computed one
precision step below what each configuration states, must fail too, on
each real configuration at a size a test run can hold.  Each real cell's
limits must lie under the smallest reading, over the seeds they were
set from on the chip, of the control and of each fault, for one number
at least; ``limits.py`` lowers a limit where none does.
"""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import json  # noqa: E402

import compare  # noqa: E402
import harness  # noqa: E402
import limits  # noqa: E402
from bench_tiny_cell import TINY, add_tiny_cell  # noqa: E402


def _unchanged(step):
    def broken(self, grads, works, params, opt_state):
        return params, opt_state
    return broken


def _half_batch(step):
    def broken(self, grads, works, params, opt_state):
        k = max(1, len(grads) // 2)
        return step(self, grads[:k], works[:k], params, opt_state)
    return broken


@pytest.mark.parametrize("fault", [_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_a_broken_server_step_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.train_fabric import FusedServerStep
    cell = harness.load_cell(add_tiny_cell(tmp_path), TINY, tmp_path,
                             tmp_path)
    monkeypatch.setattr(FusedServerStep, "step",
                        fault(FusedServerStep.step))
    line = harness.run(cell, seed=12345, seconds=0.3, trace=False,
                       t_start=time.perf_counter())
    assert line["correct"] is False
    failing = [k for k, c in line["checks"].items()
               if c["value"] > c["limit"]]
    assert failing, line["checks"]


@pytest.mark.parametrize("workload", ["fig4.paper16", "fig2.paper16",
                                      "fig4.cohort256"])
def test_the_control_is_not_correct(workload):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, workload, ROOT)
    mix = dict(cell.traffic, shards_per_round=2)
    ref = cell.reference()
    seed = 2**31 + 5
    params0 = ref.as_host(ref.init_params(cell.config, seed))
    rows = cell.program.round_rows(cell.config, mix, seed, 3)

    def readings(precision):
        losses, g1, p3 = ref.train_rounds(cell.config, params0, rows,
                                          precision=precision)
        return compare.readings(losses, g1, params0, p3)

    reference = readings("highest")
    assert compare.verdict(compare.numbers(reference, reference, 0),
                           cell.limits)
    control = compare.numbers(readings("fp8"), reference, 0)
    assert not compare.verdict(control, cell.limits), control


CELLS = [w["name"] for w in json.loads(
    (ROOT / "BENCHMARK.json").read_text())["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("side", sorted(limits.FACTOR))
def test_every_side_fails_a_limit_on_every_seed_read(workload, side):
    checks = json.loads((BENCH / "checks" / f"{workload}.json").read_text())
    readings = checks["set_from"]["readings"]
    caught = [k for k, r in readings.items()
              if r["ends"][side] > checks["limits"][k]]
    assert caught, (side, {k: (r["ends"][side], checks["limits"][k])
                           for k, r in readings.items()})
    for k, r in readings.items():
        assert r["lower"] < checks["limits"][k]


def _dump(sound, control, half):
    """A limits dump of two seeds in which every number but ``grad_diff``
    reads alike on every side, and ``grad_diff`` reads as given."""
    def sides(i):
        flat = {k: 0.01 for k in compare.NUMBERS}
        return {"program": dict(flat, grad_diff=sound[i]),
                "control": dict(flat, grad_diff=control[i]),
                "half_batch": dict(flat, grad_diff=half[i]),
                "state_unchanged": {k: 1.0 for k in compare.NUMBERS}}
    return [{"seed": i, **sides(i)} for i in range(2)]


def test_a_fault_no_limit_catches_lowers_a_limit_under_it():
    # the control sets the upper end; the half-batch fault, at 4x the
    # sound runs, sets none and reads under the limit that rule gives
    out = limits.summary(_dump([0.01, 0.009], [0.11, 0.1], [0.05, 0.04]))
    n = out["numbers"]["grad_diff"]
    assert n["upper"] == 0.1
    assert n["lowered_for"] == "half_batch"
    assert 0.01 < (0.01 * 0.1) ** 0.5 < n["limit"] < 0.04
    assert out["uncaught"] == []


def test_a_fault_that_no_number_can_catch_is_listed():
    out = limits.summary(_dump([0.01, 0.009], [0.11, 0.1], [0.02, 0.03]))
    assert "lowered_for" not in out["numbers"]["grad_diff"]
    assert out["uncaught"] == ["half_batch"]


def test_a_caught_fault_leaves_the_limits_as_the_rule_gives_them():
    out = limits.summary(_dump([0.01, 0.009], [0.11, 0.1], [0.5, 0.4]))
    n = out["numbers"]["grad_diff"]
    assert "lowered_for" not in n
    assert n["limit"] == pytest.approx(0.01 ** (1 / 3) * 0.1 ** (2 / 3))
