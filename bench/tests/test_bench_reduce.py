"""The reduction from a profiler trace to per-layer metrics, on a trace
recorded on the chip.

``data/fig2_paper16.json.gz`` is the compact extract
(``python3 bench/trace_reduce.py RUN.xplane.pb OUT.json.gz``) of a traced
run of ``fig2.paper16`` with a 1.2 s window on one TPU v5 lite; its
result line is beside it.
"""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import trace_reduce  # noqa: E402

DATA = BENCH / "tests" / "data"
TRACE = DATA / "fig2_paper16.json.gz"
LINE = json.loads((DATA / "fig2_paper16.line.json").read_text())


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.summarize(TRACE, chips=1)


@pytest.fixture(scope="module")
def run(summary):
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, "fig2.paper16", ROOT)
    shards = LINE["attempted"]
    rounds = shards // cell.traffic["shards_per_round"]
    return harness.Run(
        cell=cell, device=LINE["device"], setup_s=1.0,
        window_s=summary.window_s, round_walls=[summary.window_s / rounds]
        * rounds, samples=shards * cell.config["batch_size"],
        shards=shards, failed=0, spans={}, wire_bytes=0, trace=summary)


def _raw_intervals(lines, lo, hi):
    planes = trace_reduce._planes(TRACE)
    device = [p for p in planes if p.name == "/device:TPU:0"][0]
    out = []
    for line in device.lines:
        if line.name in lines:
            for e in line.events:
                s, t = e.start_ns, e.start_ns + e.duration_ns
                if s >= lo and t <= hi:
                    out.append((s, t))
    return out


def test_busy_is_the_union_of_the_device_operations(summary):
    lo, hi = summary.window
    iv = np.array(sorted(_raw_intervals(("XLA Ops", "Async XLA Ops"), lo,
                                        hi)))
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.r_[True, iv[1:, 0] > reach[:-1]]     # each merged block
    starts = iv[first, 0]
    ends = reach[np.r_[np.flatnonzero(first)[1:] - 1, len(iv) - 1]]
    assert summary.busy_s == pytest.approx((ends - starts).sum() * 1e-9)
    assert 0 < summary.busy_s < summary.window_s
    assert summary.window_s == pytest.approx(LINE["device"]["window_s"])
    assert summary.busy_s == pytest.approx(LINE["device"]["busy_s"])


def test_idle_share_reads_the_trace(run, summary):
    idle = harness.read_metric(run, "device.idle")
    assert idle == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))
    assert 90 < idle < 100


def test_programs_go_to_the_spans_they_run_in(run, summary):
    grad = summary.programs("grad")
    spans = [s for s in summary.spans if s.name == "bench.grad"]
    assert len(grad) == len(spans) == run.shards
    assert len({m.name for m in grad}) == 1
    step = summary.programs("server_step")
    names = {m.name for m in step}
    assert any(n.startswith("jit_flat") for n in names)
    rounds = len(run.round_walls)
    assert all(sum(m.name == n for m in step) == rounds for n in names)


def test_device_time_per_shard_sums_the_gradient_program(run, summary):
    got = harness.read_metric(run, "grad.device_ms_per_shard")
    want = 1e3 * sum(m.seconds for m in summary.programs("grad")) / run.shards
    assert got == pytest.approx(want)
    assert 0.05 < got < 5


def test_server_step_roofline_is_a_share_of_the_least_time(run, summary):
    got = harness.read_metric(run, "server_step_roofline")
    step_s = sum(m.seconds for m in summary.programs("server_step"))
    least = 20 * 22_466 * 4 / 819e9          # (M + 4) f32 buffers, by hand
    rounds = len(run.round_walls)
    assert got == pytest.approx(100 * rounds * least / step_s)
    assert 0 < got <= 100


def test_breakdown_names_the_top_ops_and_idle_gaps(summary):
    b = summary.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    secs = [s for _, s in b["device_ops"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
    gaps = [s for _, s in b["idle_gaps"]]
    assert gaps == sorted(gaps, reverse=True)
    assert all(name.startswith("bench.") or name == "outside bench spans"
               for name, _ in b["idle_gaps"])


def test_union_counts_overlaps_once_and_clips_to_the_window():
    assert trace_reduce.union_ns([(0, 10), (5, 15), (20, 30)], 0, 100) == 25
    assert trace_reduce.union_ns([(0, 10), (5, 15)], 8, 12) == 4
    assert trace_reduce.union_ns([], 0, 10) == 0


def _plane(name, lines):
    return SimpleNamespace(name=name, lines=[
        SimpleNamespace(name=ln, events=[
            SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


def test_a_trace_without_one_window_or_the_chips_is_refused():
    host = _plane("/host:CPU", {"main": [("bench.round", 0, 10)]})
    dev = _plane("/device:TPU:0", {"XLA Ops": [("%a = f32[1] add()", 1, 2)]})
    with pytest.raises(ValueError, match="window"):
        trace_reduce.reduce_planes([host, dev], chips=1)
    host = _plane("/host:CPU", {"main": [("bench.window", 0, 10)]})
    with pytest.raises(ValueError, match="TPU devices"):
        trace_reduce.reduce_planes([host, dev], chips=4)
    s = trace_reduce.reduce_planes([host, dev], chips=1)
    assert s.busy_s == pytest.approx(2e-9) and s.window_s == pytest.approx(
        1e-8)


def test_op_labels_are_short_and_name_the_kernel():
    name = ('%impl.1 = (f32[24,1024]{1,0}, f32[24,1024]{1,0}) custom-call('
            'f32[16]{0} %c), custom_call_target="tpu_custom_call"')
    assert trace_reduce.op_label(name).startswith("tpu_custom_call (f32[24")
    assert trace_reduce.op_label(
        "%fusion.12 = f32[50,16,16,32]{0,3,2,1} fusion(x)") == \
        "fusion f32[50,16,16,32]"
