"""The comparison that decides ``correct`` must catch the faults a
training cell can have.

Each test drives a whole run of a tiny cell past the look for a chip,
with the program's server step broken underneath, and sees ``correct``
come out false: a step that returns its state unchanged, and a step
that leaves half of the batch's gradients out and takes the mean over
the rest.  A single chip exchanges nothing between chips, so that fault
does not exist here.  The control, the reference itself computed one
precision step below what each configuration states, must fail too, on
each real configuration at a size a test run can hold.
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
import traffic  # noqa: E402
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


@pytest.mark.parametrize("workload", ["fig4.paper16", "fig2.paper16"])
def test_the_control_is_not_correct(workload):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, workload, ROOT)
    mix = dict(cell.traffic, shards_per_round=2)
    ref = cell.reference()
    seed = 2**31 + 5
    params0 = ref.as_host(ref.init_params(cell.config, seed))
    rows = traffic.round_rows(cell.config, mix, seed, 3)

    def readings(precision):
        losses, g1, p3 = ref.train_rounds(cell.config, params0, rows,
                                          precision=precision)
        return compare.readings(losses, g1, params0, p3)

    reference = readings("highest")
    assert compare.verdict(compare.numbers(reference, reference, 0),
                           cell.limits)
    control = compare.numbers(readings("fp8"), reference, 0)
    assert not compare.verdict(control, cell.limits), control
