"""The program's spans on the profiler's clock (``program_trace.py``):
the two-anchor clock map, idle attribution to block spans, the seven
numbers, a CPU run of a tiny cell with the program's tracer installed,
and the recorded chip extracts."""
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import harness  # noqa: E402
import program_trace as pt_mod  # noqa: E402
import trace_reduce  # noqa: E402

from bench_tiny_cell import TINY, add_tiny_cell  # noqa: E402

DATA = BENCH / "tests" / "data"


def _summary(window, modules=(), spans=()):
    device = trace_reduce.Device(
        modules=[trace_reduce.Event(*m) for m in modules], ops=[],
        busy_ns=0.0)
    return trace_reduce.TraceSummary(
        window=window, devices=[device],
        spans=[trace_reduce.Event(*s) for s in spans])


def test_the_two_anchor_clock_map_places_a_synthetic_span():
    # the tracer's clock reads 100.0 s and 150.0 s at the window's ends,
    # which the profiler puts at 7e9 and 57.005e9 ns: an offset and a
    # drift of 100 parts per million
    anchors, window = (100.0, 150.0), (7e9, 57.005e9)
    f = pt_mod.clock_map(anchors, window)
    assert f(100.0) == 7e9 and f(150.0) == pytest.approx(57.005e9)
    rows = [["wire.encode", "block", 120.0, 120.5, {"bytes": 3}],
            ["before", "block", 90.0, 99.0, {}],
            ["across", "lane", 149.0, 151.0, {}]]
    spans = pt_mod.map_spans(rows, anchors, window)
    assert [s.name for s in spans] == ["wire.encode", "across"]
    enc = spans[0]
    assert enc.start == pytest.approx(7e9 + 20.0 * 1.0001e9)
    assert enc.seconds == pytest.approx(0.5 * 1.0001)
    assert enc.kind == "block" and enc.args == {"bytes": 3}
    # one anchor alone (an offset) would misplace it by the drift
    assert abs(enc.start - (7e9 + 20e9)) > 1e6


def test_compact_events_join_async_pairs_and_mark_blocks():
    from repro.obs import Tracer

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    tr = Tracer(clock=clock)
    a = tr.begin("ticket", args={"ticket": 4})
    with tr.span("grad.h2d") as args:
        clock.t = 1.0
        args["bytes"] = 9
    x = tr.begin("client.execute", lane=True)
    clock.t = 2.0
    tr.end(x)
    tr.instant("round.barrier_open")
    tr.end(a)
    assert pt_mod.compact_events(tr.events()) == [
        ["grad.h2d", "block", 0.0, 1.0, {"bytes": 9}],
        ["client.execute", "lane", 1.0, 2.0, {}],
        ["ticket", "async", 0.0, 2.0, {"ticket": 4}]]


def test_idle_time_goes_to_the_innermost_block_span():
    # device busy over [0, 10] and [60, 100] of a [0, 100] window: idle
    # [10, 60]; block spans: publish [5, 20], encode [30, 50] holding a
    # nested decode [35, 40]; a lane span [0, 100] names nothing
    summary = _summary((0.0, 100.0), modules=[("jit_a(1)", 0.0, 10.0),
                                              ("jit_a(1)", 60.0, 100.0)],
                       spans=[("bench.window", 0.0, 100.0)])
    spans = [pt_mod.Span("round.publish", 5.0, 20.0, "block"),
             pt_mod.Span("wire.encode", 30.0, 50.0, "block"),
             pt_mod.Span("wire.decode", 35.0, 40.0, "block"),
             pt_mod.Span("client.execute", 0.0, 100.0, "lane")]
    p = pt_mod.ProgramTrace(summary, spans)
    got = p.idle_by_span()
    want = {"round.publish": 10e-9, "wire.encode": 15e-9,
            "wire.decode": 5e-9, pt_mod.OUTSIDE: 20e-9}
    assert got == pytest.approx(want)
    assert sum(got.values()) == pytest.approx(50e-9)
    # the gap's midpoint, 35, lies in the decode nested in the encode
    assert p.idle_gaps() == [["wire.decode", pytest.approx(50e-9)]]
    assert [m.start for m in p.programs_named("a")] == [0.0, 60.0]
    assert p.programs_named("b") == []


def test_the_seven_numbers_from_synthetic_spans():
    summary = _summary((0.0, 100e6))
    ms = 1e6
    spans = [pt_mod.Span("round.publish", 1 * ms, 3 * ms, "block"),
             pt_mod.Span("round.publish", 51 * ms, 55 * ms, "block"),
             pt_mod.Span("round.publish", 99 * ms, 101 * ms, "block"),
             pt_mod.Span("wire.encode", 4 * ms, 5 * ms, "block"),
             pt_mod.Span("wire.decode", 6 * ms, 9 * ms, "block"),
             pt_mod.Span("grad.h2d", 10 * ms, 12 * ms, "block"),
             pt_mod.Span("grad.h2d", 20 * ms, 24 * ms, "block"),
             pt_mod.Span("grad.d2h", 13 * ms, 14 * ms, "block"),
             pt_mod.Span("server_step.h2d", 30 * ms, 36 * ms, "block"),
             pt_mod.Span("ticket", 2 * ms, 16 * ms, "async",
                         {"ticket": 1}),
             pt_mod.Span("ticket", 3 * ms, 26 * ms, "async",
                         {"ticket": 2}),
             pt_mod.Span("ticket", -5 * ms, 26 * ms, "async",
                         {"ticket": 0}),
             pt_mod.Span("lease", 7 * ms, 16 * ms, "async",
                         {"ticket_ids": [1, 0]}),
             pt_mod.Span("lease", 8 * ms, 26 * ms, "async",
                         {"ticket_ids": [2]}),
             pt_mod.Span("lease", 9 * ms, 26 * ms, "async",
                         {"ticket_ids": [2]})]
    got = pt_mod.numbers(pt_mod.ProgramTrace(summary, spans), rounds=2,
                         shards=4)
    assert got == pytest.approx({
        "round.publish_ms": (2 + 4) / 2,           # the third is cut off
        "ticket.wait_ms": ((7 - 2) + (8 - 3)) / 2,  # first lease, in window
        "wire.encode_ms_per_round": 1 / 2,
        "wire.decode_ms_per_round": 3 / 2,
        "grad.h2d_ms_per_shard": (2 + 4) / 4,
        "grad.d2h_ms_per_shard": 1 / 4,
        "server_step.h2d_ms_per_round": 6 / 2})


def test_an_extract_without_program_spans_reads_as_before():
    line = json.loads((DATA / "fig2_paper16.line.json").read_text())
    p = pt_mod.load(DATA / "fig2_paper16.json.gz")
    assert p.spans == []
    summary = trace_reduce.summarize(DATA / "fig2_paper16.json.gz", chips=1)
    assert p.summary.breakdown() == summary.breakdown()
    assert p.idle_gaps() == summary.breakdown()["idle_gaps"]
    assert p.summary.owners == summary.owners
    assert p.summary.busy_s == pytest.approx(line["device"]["busy_s"])
    assert all(v is None for v in pt_mod.numbers(p, 8, 112).values())
    idle = p.idle_by_span()
    assert list(idle) == [pt_mod.OUTSIDE]
    assert idle[pt_mod.OUTSIDE] == pytest.approx(
        summary.window_s - summary.busy_s, rel=0.01)


def test_a_tiny_cell_driven_with_the_program_tracer(tmp_path, monkeypatch):
    # a loaded CPU can stretch a lease past the watchdog's grace x ETA,
    # and its tickets then run twice; this test counts one run a shard
    monkeypatch.setattr(harness, "GRACE_S", 50.0)
    cell = harness.load_cell(add_tiny_cell(tmp_path), TINY, tmp_path,
                             tmp_path)
    out, log, tracer = pt_mod.drive(cell, seed=2**31 + 5, seconds=0.5,
                                    say=lambda m: None)
    assert out["failed"] == 0 and out["compiles"] == 0
    c0, c1 = log.anchors
    assert c0 < c1
    rows = pt_mod.compact_events(tracer.events())
    inside = lambda name: [r for r in rows if r[0] == name
                           and c0 <= r[2] and r[3] <= c1]
    rounds, shards = len(out["walls"]), out["shards"]
    assert len(inside("round.publish")) == rounds
    for name in ("grad.h2d", "grad.compute", "grad.d2h"):
        assert len(inside(name)) == shards
    for name in ("server_step.coeffs", "server_step.h2d",
                 "server_step.compute"):
        assert len(inside(name)) == rounds
    # a lease may carry several tickets: one weights fetch and one submit
    # each, so the codec runs at least twice a round on each side
    for name in ("wire.encode", "wire.decode"):
        assert 2 * rounds <= len(inside(name)) <= 2 * shards
    # the tracer and the harness's spans both run on CLOCK_MONOTONIC
    # here, so the window's anchors bracket the harness's own window
    assert c0 >= out["t_w0"] - 0.01 and c1 <= out["t_w1"] + 0.01
    # program spans take the identity map in a synthetic summary
    summary = _summary((c0 * 1e9, c1 * 1e9))
    p = pt_mod.ProgramTrace(summary, pt_mod.map_spans(
        rows, (c0, c1), summary.window))
    got = pt_mod.numbers(p, rounds, shards)
    assert all(v is not None and v > 0 for v in got.values()), got
    # every grad span lies within the harness's grad span around the task
    grads = log.spans["grad"]
    for r in inside("grad.h2d") + inside("grad.d2h"):
        assert any(s <= r[2] and r[3] <= e for s, e in grads)


# -- a traced chip run with the program's spans --------------------------------
#
# ``data/fig4_paper16_program.json.gz`` is the ``--extract`` of
# ``program_trace.py`` on ``fig4.paper16`` with a 3 s window on one TPU
# v5 lite; its printed line is beside it.

EXTRACT = DATA / "fig4_paper16_program.json.gz"
LINE4 = json.loads((DATA / "fig4_paper16_program.line.json").read_text())


@pytest.fixture(scope="module")
def chip():
    return pt_mod.load(EXTRACT)


def _bench(p, name):
    lo, hi = p.summary.window
    return [s for s in p.summary.spans if s.name == f"bench.{name}"
            and s.start >= lo and s.end <= hi]


@pytest.fixture(scope="module")
def chip_run(chip):
    """The harness's Run of the extract, its spans from the trace."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, "fig4.paper16", ROOT)
    rounds = _bench(chip, "round")
    return harness.Run(
        cell=cell, device=LINE4["device"], setup_s=1.0,
        window_s=chip.summary.window_s,
        round_walls=[s.seconds for s in rounds],
        samples=LINE4["attempted"] * cell.config["batch_size"],
        shards=LINE4["attempted"], failed=0,
        spans={name: [s.seconds for s in _bench(chip, name)]
               for name in ("grad", "server_step", "round")},
        wire_bytes=0, trace=chip.summary)


def test_the_extract_reads_what_the_chip_run_printed(chip):
    assert len(_bench(chip, "round")) == LINE4["rounds"]
    got = pt_mod.numbers(chip, LINE4["rounds"], LINE4["attempted"])
    assert got == pytest.approx(LINE4["program"])
    assert all(v > 0 for v in got.values())
    assert chip.idle_by_span() == pytest.approx(LINE4["idle_by_span"])
    assert chip.idle_gaps() == LINE4["breakdown"]["idle_gaps"]
    assert sum(chip.idle_by_span().values()) == pytest.approx(
        chip.summary.window_s - chip.summary.busy_s, rel=0.01)


def test_each_grad_span_lies_within_its_bench_grad_span(chip):
    grads = _bench(chip, "grad")
    lo, hi = chip.summary.window
    spans = [s for s in chip.blocks() if s.name.startswith("grad.")
             and s.start >= lo and s.end <= hi]
    assert len(spans) == 3 * len(grads)
    slack = trace_reduce.SLACK_NS
    for s in spans:
        assert any(g.start - slack <= s.start and s.end <= g.end + slack
                   for g in grads), s


def test_program_spans_fit_inside_the_benchmarks_outside_timers(chip,
                                                                chip_run):
    n = pt_mod.numbers(chip, len(chip_run.round_walls), chip_run.shards)
    read = lambda name: harness.read_metric(chip_run, name)
    assert n["grad.h2d_ms_per_shard"] + n["grad.d2h_ms_per_shard"] <= read(
        "grad.host_ms_per_shard")
    assert n["server_step.h2d_ms_per_round"] <= read(
        "server_step.ms_per_round")
    # the client's codec runs outside the task, so in the residual
    assert (n["round.publish_ms"] + n["wire.encode_ms_per_round"]
            + n["wire.decode_ms_per_round"]) <= read("round.residual_ms")


@pytest.mark.parametrize("function, span, owner", [
    ("cnn_loss_and_grads", "grad.compute", "grad"),
    ("member_coeffs", "server_step.coeffs", "server_step"),
    ("fused_server_step", "server_step.compute", "server_step"),
])
def test_each_program_is_found_by_its_stable_name(chip, function, span,
                                                  owner):
    execs = chip.programs_named(function)
    spans = chip.within(span)
    assert execs and len(execs) == len(spans)
    slack = trace_reduce.SLACK_NS
    for m in execs:
        assert any(s.start - slack <= m.start and m.end <= s.end + slack
                   for s in spans), m
    assert {chip.summary.owners[m.name] for m in execs} == {owner}
