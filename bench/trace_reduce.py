"""The reduction from a profiler trace to the numbers the per-layer
readers take.

A traced run writes one ``.xplane.pb``.  Read with
``jax.profiler.ProfileData``, a TPU's plane ``/device:TPU:<n>`` holds the
line ``XLA Modules`` (one event per program execution, named
``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per
operation) and ``Async XLA Ops`` (copies and slices that run beside the
operations, from their start to the wait that ends them).  The host
plane ``/host:CPU`` holds the benchmark's own spans, the
``TraceAnnotation`` events named ``bench.<span>``.

Everything is clipped to the window, the ``bench.window`` span.  The
host's and the device's clocks in one trace disagree by up to about a
millisecond (a program dispatched inside a span can show as starting
before it), so a program is given to the span that most of its
executions overlap, never by one execution alone.

    python3 bench/trace_reduce.py RUN.xplane.pb OUT.json.gz

writes the compact extract that the tests read: the device planes and
the benchmark's spans, without the rest of the host's events.
"""
from __future__ import annotations

import bisect
import gzip
import json
import re
import sys
from collections import Counter
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
SPAN_PREFIX = "bench."
TOP = 10
# the host's and the device's clocks in one trace disagree by up to about
# a millisecond
SLACK_NS = 2e6


@dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns, on the trace's clock
    end: float

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class Device:
    """One chip's events within the window."""

    modules: list            # Event per program execution
    ops: list                # Event per operation
    busy_ns: float           # union of operations and async copies


@dataclass
class TraceSummary:
    window: tuple            # (start, end) ns
    devices: list            # Device per chip used
    spans: list              # host Event per bench span in the window
    owners: dict = field(default_factory=dict)   # module name -> span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return sum(d.busy_ns for d in self.devices) / len(self.devices) * 1e-9

    def programs(self, span: str) -> list:
        """Every execution, in the window, of the programs that run within
        the host's ``bench.<span>`` spans."""
        return [m for d in self.devices for m in d.modules
                if self.owners.get(m.name) == span]

    def breakdown(self) -> dict:
        """The device operations that took most time, and the longest
        idle gaps named by the innermost bench span open on the host."""
        totals: Counter = Counter()
        for d in self.devices:
            for e in d.ops:
                totals[op_label(e.name)] += e.seconds
        gaps = [[host_activity(self.spans, (lo + hi) / 2), (hi - lo) * 1e-9]
                for lo, hi in idle_gaps(self.devices[0], self.window)[:TOP]]
        return {"device_ops": [[k, v] for k, v in totals.most_common(TOP)],
                "idle_gaps": gaps}


def op_label(name: str) -> str:
    """A short label of an HLO op event: the instruction's name without
    its number, and its result's type, e.g. ``fusion f32[50,32,32,32]``;
    the server step's Pallas kernel reads ``tpu_custom_call``."""
    lhs, sep, rhs = name.partition(" = ")
    if not sep:
        return name[:80]
    op = lhs.lstrip("%").split(".")[0]
    if 'custom_call_target="tpu_custom_call"' in rhs:
        op = "tpu_custom_call"
    result = re.sub(r"\{[^{}]*\}", "", rhs)          # drop the layouts
    result = (result[:result.index(")") + 1] if result.startswith("(")
              else result.split(" ", 1)[0])
    return f"{op} {result}"[:80]


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end)`` intervals within [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(device: Device, window) -> list[tuple[float, float]]:
    """The gaps between the device's programs within the window, longest
    first."""
    lo, hi = window
    gaps, last = [], lo
    for s, e in sorted((m.start, m.end) for m in device.modules):
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    if hi > last:
        gaps.append((last, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def host_activity(spans, t: float) -> str:
    """The innermost (shortest) bench span that holds time ``t``."""
    best = None
    for s in spans:
        if s.start <= t <= s.end and (best is None or
                                      s.end - s.start < best.end - best.start):
            best = s
    return best.name if best is not None else "outside bench spans"


def _events(line):
    for e in line.events:
        yield Event(e.name, float(e.start_ns),
                    float(e.start_ns) + float(e.duration_ns))


def _planes(path: Path):
    """The planes of a trace: an ``.xplane.pb``, or a compact extract."""
    path = Path(path)
    if path.name.endswith(".json.gz"):
        with gzip.open(path, "rt") as f:
            data = json.load(f)
        return [SimpleNamespace(name=p["name"], lines=[
            SimpleNamespace(name=ln["name"], events=[
                SimpleNamespace(name=n, start_ns=s, duration_ns=d)
                for n, s, d in ln["events"]])
            for ln in p["lines"]]) for p in data["planes"]]
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(path)).planes


def summarize(path: Path, *, chips: int, window: str = "bench.window"
              ) -> TraceSummary:
    """Read the trace at ``path`` and reduce it to a TraceSummary of the
    first ``chips`` TPU devices within the host span ``window``."""
    return reduce_planes(_planes(path), chips=chips, window=window)


def reduce_planes(planes, *, chips: int, window: str = "bench.window"
                  ) -> TraceSummary:
    spans, devices = [], {}
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                spans.extend(Event(e.name, float(e.start_ns),
                                   float(e.start_ns) + float(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
        elif plane.name.startswith(DEVICE_PREFIX):
            devices[plane.name] = {line.name: list(_events(line))
                                   for line in plane.lines}
    windows = [s for s in spans if s.name == window]
    if len(windows) != 1:
        raise ValueError(f"trace holds {len(windows)} {window!r} spans, "
                         f"not one")
    lo, hi = windows[0].start, windows[0].end
    names = sorted(devices, key=lambda n: int(n[len(DEVICE_PREFIX):]))
    if len(names) < chips:
        raise ValueError(f"trace holds {len(names)} TPU devices, the cell "
                         f"uses {chips}")

    def inside(events):
        return [e for e in events if e.start >= lo and e.end <= hi]

    out = []
    for name in names[:chips]:
        lines = devices[name]
        ops = inside(lines.get(OP_LINE, ()))
        copies = inside(lines.get(ASYNC_LINE, ()))
        busy = union_ns([(e.start, e.end) for e in ops + copies], lo, hi)
        out.append(Device(modules=inside(lines.get(MODULE_LINE, ())),
                          ops=ops, busy_ns=busy))
    spans = [s for s in spans if s.end > lo and s.start < hi]
    return TraceSummary(window=(lo, hi), devices=out, spans=spans,
                        owners=owners(out, spans))


def owners(devices, spans, slack_ns: float = SLACK_NS) -> dict[str, str]:
    """{program name: span}: the bench span (without its ``bench.``
    prefix) that the program's executions overlap most often, each
    execution widened by ``slack_ns`` on both sides for the clocks'
    disagreement; among spans overlapped as often, the one that covers
    the least time, the most specific."""
    by_name: dict[str, list] = {}
    for s in spans:
        by_name.setdefault(s.name.removeprefix(SPAN_PREFIX), []).append(s)
    index = {}
    for name, lst in by_name.items():
        lst.sort(key=lambda e: e.start)
        index[name] = ([e.start for e in lst], lst,
                       sum(e.end - e.start for e in lst))
    votes: dict[str, Counter] = {}
    for d in devices:
        for m in d.modules:
            lo, hi = m.start - slack_ns, m.end + slack_ns
            c = votes.setdefault(m.name, Counter())
            for name, (starts, lst, _) in index.items():
                i = bisect.bisect_right(starts, hi) - 1
                # spans of one name never overlap each other, so only the
                # last one to start before ``hi`` can reach ``lo``
                if i >= 0 and lst[i].end >= lo:
                    c[name] += 1
    return {prog: max(c, key=lambda n: (c[n], -index[n][2]))
            for prog, c in votes.items() if c}


def extract(path: Path, out: Path, *, names_cap: int = 400):
    """Write the compact extract of the trace at ``path`` to ``out``
    (``.json.gz``): the device planes, with each event's name cut to
    ``names_cap`` letters, and the host's bench spans."""
    planes = []
    for plane in _planes(path):
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [{"name": ln.name, "events": [
                [e.name[:names_cap], e.start_ns, e.duration_ns]
                for e in ln.events]} for ln in plane.lines]
        elif plane.name.startswith("/host"):
            lines = [{"name": "bench spans", "events": [
                [e.name, e.start_ns, e.duration_ns]
                for ln in plane.lines for e in ln.events
                if e.name.startswith(SPAN_PREFIX)]}]
        else:
            continue
        planes.append({"name": plane.name, "lines": lines})
    with gzip.open(out, "wt") as f:
        json.dump({"planes": planes}, f)


if __name__ == "__main__":
    extract(Path(sys.argv[1]), Path(sys.argv[2]))
