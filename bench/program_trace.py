#!/usr/bin/env python3
"""The program's own spans on the profiler's clock.

A traced run of a cell yields the profiler's trace (``trace_reduce.py``)
with the benchmark's spans around calls into the program.  This module
adds the program's own ``repro.obs.trace.Tracer``: the harness's spans
say *that* the host was inside a round or a gradient, the program's
spans say *what* it was doing there (weight publish, wire encode and
decode, host<->device copies, the ticket queue).

One clock.  The tracer runs on the ticket queue's clock; the profiler on
its own.  Both clocks are read at the open and at the close of the
``bench.window`` span, and the tracer's times are mapped linearly onto
the profiler's nanoseconds through those two anchors, so that a drift
between the clocks over the window cancels (:func:`clock_map`).

Idle attribution.  A span recorded by ``Tracer.span`` (``block``) wraps
synchronous work on the one thread that runs the event loop, the clients
and the server step, so the innermost block span open at an instant says
what that thread was doing; idle device time under no block span is
"outside program spans" (:func:`idle_by_span`).  Async spans (``ticket``,
``lease``) and the lane spans that hold an ``await`` (``round``,
``wire.lease``, ``client.execute``) overlap across clients and name
nothing.

The harness builds no program tracer yet, so the seven numbers here
(:func:`numbers`) are no per-layer metrics of ``BENCHMARK.json``:
:func:`record` drives a cell with the tracer installed, and

    python3 bench/program_trace.py --workload fig4.paper16 --seed 7 \\
        --seconds 50 [--extract OUT.json.gz]

prints them, beside the cell's per-layer metrics, as its last line.
``--extract`` also writes the compact extract of ``trace_reduce.py``
with the program's spans added under ``"program"``.
"""
from __future__ import annotations

import asyncio
import bisect
import functools
import gzip
import json
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from pathlib import Path
from unittest import mock

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if __name__ == "__main__":          # run as a script from the checkout
    sys.path[:0] = [str(BENCH), str(ROOT / "src")]

import harness  # noqa: E402
import trace_reduce  # noqa: E402

OUTSIDE = "outside program spans"


@dataclass(frozen=True)
class Span:
    """A program span on the profiler's clock (ns)."""

    name: str
    start: float
    end: float
    kind: str                 # "block", "lane" or "async"
    args: dict = field(default_factory=dict, compare=False)

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


# -- recording --------------------------------------------------------------


def compact_events(events) -> list:
    """The tracer's decoded events (``Tracer.events()``) as
    ``[name, kind, t0, t1, args]`` rows on the tracer's clock: lane spans
    (``block`` where ``Tracer.span`` recorded them) and async spans, whose
    begin and end are joined; instants are left out."""
    out, begins = [], {}
    for e in events:
        if e["ph"] == "X":
            out.append([e["name"], "block" if e.get("block") else "lane",
                        e["ts"], e["ts"] + e["dur"], e["args"]])
        elif e["ph"] == "b":
            begins[e["id"]] = e
        elif e["ph"] == "e" and e["id"] in begins:
            b = begins.pop(e["id"])
            out.append([b["name"], "async", b["ts"], e["ts"], b["args"]])
    return out


def clock_map(anchors, window):
    """The linear map from the tracer's clock onto the profiler's ns that
    sends the tracer's readings at the window's open and close,
    ``anchors = (c0, c1)``, to the window's ends ``(lo, hi)``."""
    (c0, c1), (lo, hi) = anchors, window
    scale = (hi - lo) / (c1 - c0)
    return lambda t: lo + (t - c0) * scale


def map_spans(rows, anchors, window) -> list[Span]:
    """:func:`compact_events` rows as Spans on the profiler's clock,
    those that overlap the window."""
    f = clock_map(anchors, window)
    lo, hi = window
    spans = [Span(name, f(t0), f(t1), kind, args or {})
             for name, kind, t0, t1, args in rows]
    return sorted((s for s in spans if s.end > lo and s.start < hi),
                  key=lambda s: s.start)


class AnchoredSpanLog(harness.SpanLog):
    """The harness's span log that also reads ``clock`` (the program
    tracer's) inside the ``bench.window`` span, at its open and close."""

    def __init__(self, clock):
        super().__init__()
        self.clock = clock
        self.anchors = None

    @contextmanager
    def span(self, name: str):
        with super().span(name):
            c0 = self.clock()
            try:
                yield
            finally:
                if name == "window":
                    self.anchors = (c0, self.clock())


def drive(cell, *, seed: int, seconds: float, profiler=None, say=print):
    """Drive ``cell`` as the harness does (``harness._drive``), with one
    program ``Tracer`` on the queue's clock handed to the distributor and
    to the remote clients.  Returns ``(out, log, tracer)``: the harness's
    readings, its span log with the window's anchors, and the tracer."""
    from repro.core import federation, transport
    from repro.obs import Tracer
    tracer = Tracer(clock=time.monotonic)       # the queue's default clock
    log = AnchoredSpanLog(tracer.clock)
    compiles = harness._CompileCounter()
    # the harness builds the distributor and the clients with no tracer;
    # hand them this one for the run (PERF.md §7: the harness edit that
    # replaces this)
    with mock.patch.object(federation, "FederatedDistributor",
                           functools.partial(federation.FederatedDistributor,
                                             tracer=tracer)), \
            mock.patch.object(transport, "spawn_remote_clients",
                              functools.partial(
                                  transport.spawn_remote_clients,
                                  tracer=tracer)):
        out = asyncio.run(harness._drive(cell, seed, seconds, log, profiler,
                                         compiles, say))
    out["compiles"] = compiles.count
    return out, log, tracer


# -- reading ----------------------------------------------------------------


@dataclass
class ProgramTrace:
    """A reduced profiler trace with the program's spans beside it."""

    summary: trace_reduce.TraceSummary
    spans: list                    # Span, sorted by start

    def within(self, name: str) -> list[Span]:
        """The ``name`` spans that lie within the window."""
        lo, hi = self.summary.window
        return [s for s in self.spans
                if s.name == name and s.start >= lo and s.end <= hi]

    def blocks(self) -> list[Span]:
        return [s for s in self.spans if s.kind == "block"]

    def programs_named(self, function: str) -> list:
        """Every execution, in the window, of the program jitted from the
        function ``function`` (module ``jit_<function>(<fingerprint>)``)."""
        prefix = f"jit_{function}("
        return [m for d in self.summary.devices for m in d.modules
                if m.name.startswith(prefix)]

    def idle_gaps(self) -> list:
        """The longest idle gaps, each named by the innermost span open at
        its midpoint: a benchmark span or a program block span."""
        named = self.summary.spans + [
            trace_reduce.Event(s.name, s.start, s.end) for s in self.blocks()]
        return [[trace_reduce.host_activity(named, (lo + hi) / 2),
                 (hi - lo) * 1e-9] for lo, hi in trace_reduce.idle_gaps(
                     self.summary.devices[0],
                     self.summary.window)[:trace_reduce.TOP]]

    def idle_by_span(self) -> dict[str, float]:
        """Seconds of the first chip's idle time under each program block
        span, the innermost where they nest, and under none
        (:data:`OUTSIDE`); they add up to the idle time."""
        gaps = sorted(trace_reduce.idle_gaps(self.summary.devices[0],
                                             self.summary.window))
        out: dict[str, float] = {}
        for lo, hi, name in _innermost(self.blocks()):
            i = bisect.bisect_right(gaps, (lo, float("inf"))) - 1
            for g0, g1 in gaps[max(i, 0):]:
                if g0 >= hi:
                    break
                ov = min(hi, g1) - max(lo, g0)
                if ov > 0:
                    out[name] = out.get(name, 0.0) + ov * 1e-9
        idle = sum(g1 - g0 for g0, g1 in gaps) * 1e-9
        out[OUTSIDE] = idle - sum(out.values())
        return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def _innermost(spans) -> list[tuple[float, float, str]]:
    """Disjoint ``(start, end, name)`` pieces of the time the spans cover,
    each under the innermost span: the latest-started one still open."""
    points = sorted({p for s in spans for p in (s.start, s.end)})
    by_start = sorted(spans, key=lambda s: s.start)
    out, open_, j = [], [], 0
    for a, b in zip(points, points[1:]):
        while j < len(by_start) and by_start[j].start <= a:
            open_.append(by_start[j])
            j += 1
        open_ = [s for s in open_ if s.end > a]
        if open_:
            out.append((a, b, open_[-1].name))
    return out


def load(path: Path, *, chips: int = 1) -> ProgramTrace:
    """The extract (``--extract``) or plain ``trace_reduce`` extract at
    ``path``; one without program spans gives none."""
    summary = trace_reduce.summarize(path, chips=chips)
    with gzip.open(path, "rt") as f:
        program = json.load(f).get("program")
    spans = ([] if program is None else
             map_spans(program["events"], program["anchors"],
                       summary.window))
    return ProgramTrace(summary, spans)


def write_extract(xplane: Path, out: Path, rows, anchors):
    """``trace_reduce.extract`` of ``xplane`` into ``out``, with the
    program's spans and the window's anchors under ``"program"``."""
    trace_reduce.extract(xplane, out)
    with gzip.open(out, "rt") as f:
        data = json.load(f)
    data["program"] = {"anchors": list(anchors), "events": rows}
    with gzip.open(out, "wt") as f:
        json.dump(data, f)


# -- the numbers --------------------------------------------------------------


def _ms_per(pt: ProgramTrace, name: str, n: int):
    spans = pt.within(name)
    if not spans or n <= 0:
        return None
    return 1e3 * sum(s.seconds for s in spans) / n


def ticket_wait_ms(pt: ProgramTrace):
    """Mean, over the tickets enqueued in the window, of the time from a
    ticket's enqueue to the first lease that carries it."""
    lo, hi = pt.summary.window
    leases = {}
    for s in pt.spans:
        if s.name == "lease" and s.kind == "async":
            for tid in s.args.get("ticket_ids", ()):
                leases.setdefault(tid, []).append(s.start)
    waits = []
    for s in pt.spans:
        if s.name == "ticket" and s.kind == "async" and lo <= s.start <= hi:
            later = [t for t in leases.get(s.args.get("ticket"), ())
                     if t >= s.start]
            if later:
                waits.append(min(later) - s.start)
    return 1e3 * 1e-9 * sum(waits) / len(waits) if waits else None


#: per-layer number -> (the block span summed over the window, and
#: whether the sum is taken per round or per shard)
SUMS = {
    "round.publish_ms": ("round.publish", "round"),
    "wire.encode_ms_per_round": ("wire.encode", "round"),
    "wire.decode_ms_per_round": ("wire.decode", "round"),
    "grad.h2d_ms_per_shard": ("grad.h2d", "shard"),
    "grad.d2h_ms_per_shard": ("grad.d2h", "shard"),
    "server_step.h2d_ms_per_round": ("server_step.h2d", "round"),
}


def numbers(pt: ProgramTrace, rounds: int, shards: int) -> dict:
    """The seven numbers; each None where it finds nothing to read."""
    per = {"round": rounds, "shard": shards}
    out = {name: _ms_per(pt, span, per[by])
           for name, (span, by) in SUMS.items()}
    out["ticket.wait_ms"] = ticket_wait_ms(pt)
    return out


# -- one recorded run ---------------------------------------------------------


def record(cell, *, seed: int, seconds: float, t_start: float,
           extract: Path | None = None, say=print) -> dict:
    """A traced run of ``cell`` with the program's tracer: the cell's
    per-layer metrics as the harness reads them, the seven program-span
    numbers, the breakdown and the idle time by program span.  The
    reference is not run: this line decides no ``correct``."""
    import jax
    with tempfile.TemporaryDirectory(prefix="bench-program-") as tmp:
        profiler = harness._Tracer(Path(tmp))
        out, log, tracer = drive(cell, seed=seed, seconds=seconds,
                                 profiler=profiler, say=say)
        summary = trace_reduce.summarize(profiler.path(), chips=cell.chips)
        rows = compact_events(tracer.events())
        if extract is not None:
            write_extract(profiler.path(), Path(extract), rows, log.anchors)
    pt = ProgramTrace(summary, map_spans(rows, log.anchors, summary.window))
    lo, hi = out["t_w0"], out["t_w1"]
    dev = jax.devices()[0]
    run = harness.Run(
        cell=cell, device={"platform": dev.platform, "kind": dev.device_kind,
                           "count": len(jax.devices()),
                           "memory_peak_bytes": out["memory_peak_bytes"]},
        setup_s=lo - t_start, window_s=hi - lo, round_walls=out["walls"],
        samples=out["samples"], shards=out["shards"], failed=out["failed"],
        spans={name: log.durations(name, lo, hi)
               for name in ("grad", "server_step", "round")},
        wire_bytes=out["wire_bytes"], trace=summary)
    rounds = len(out["walls"])
    return {
        "workload": cell.name, "seed": seed, "rounds": rounds,
        "attempted": out["shards"], "failed": out["failed"],
        "compiles_in_window": out["compiles"],
        "tracer_balanced": tracer.balanced(),
        "metrics": {m["name"]: harness.read_metric(run, m["name"])
                    for m in cell.per_layer},
        "program": numbers(pt, rounds, out["shards"]),
        # each program by its stable name: executions in the window, and
        # the benchmark span the reduction gave it to
        "programs": {f: [len(pt.programs_named(f)), sorted(
            {str(summary.owners.get(m.name)) for m in pt.programs_named(f)})]
            for f in (cell.program.GRAD_PROGRAM, "member_coeffs",
                      "fused_server_step")},
        "device": run.device | {"busy_s": summary.busy_s,
                                "window_s": summary.window_s},
        "breakdown": summary.breakdown() | {"idle_gaps": pt.idle_gaps()},
        "idle_by_span": pt.idle_by_span(),
    }


def main(argv=None) -> int:
    import argparse
    t_start = time.perf_counter()
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--extract", type=Path)
    args = p.parse_args(argv)
    import jax
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = harness.load_cell(spec, args.workload, ROOT)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"program_trace: {args.workload} needs {cell.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 2
    harness.use_compile_cache()
    line = record(cell, seed=args.seed, seconds=args.seconds,
                  t_start=t_start, extract=args.extract,
                  say=lambda m: print(m, file=sys.stderr, flush=True))
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
