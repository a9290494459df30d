"""Observability-layer tests: tracer span balance (property-tested over
random queue op sequences), Chrome/Perfetto export format, same-seed
trace determinism, the metrics registry and its naming convention, the
metrics-vs-legacy differential checks, trace-context propagation over
the v2 wire, and the run_until_done stall warning."""
import asyncio
import json

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.distributor import (AdaptiveSizer, AsyncDistributor,
                                    ClientProfile, FixedSizer, TaskDef)
from repro.core.federation import FederatedDistributor
from repro.core.tickets import TicketQueue
from repro.core.transport import (TransportServer, spawn_remote_clients)
from repro.core.wire import make_trace_context, parse_trace_context
from repro.obs import (MetricsRegistry, Tracer, collect_fabric,
                       valid_metric_name)
from repro.train_fabric import FederatedTrainer


def _run(coro):
    return asyncio.run(coro)


class SimClock:
    """Settable virtual clock (docs/ARCHITECTURE.md §Injectable clock)."""

    def __init__(self):
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


# module-level so it pickles across the wire
def _square(x, static):
    return x * x


def make_fed(n_members=2, **kw):
    kw.setdefault("timeout", 5.0)
    kw.setdefault("redistribute_min", 0.02)
    kw.setdefault("sizer", AdaptiveSizer(target_lease_time=0.02, max_size=8))
    kw.setdefault("watchdog_interval", 0.005)
    kw.setdefault("grace", 2.0)
    return FederatedDistributor(n_members, **kw)


def _grad_task():
    def run(args, static):
        return {"grad": {"w": np.full(2, float(args), np.float32)},
                "loss": float(args),
                "round": static["weights"]["round"]}
    return TaskDef("backbone_shard", run, static_files=("weights",))


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


def test_tracer_span_schemas_async_lane_instant():
    clock = SimClock()
    tr = Tracer(clock=clock)
    a = tr.begin("lease", track="queue", cat="lease", args={"lease": 1})
    clock.t = 0.25
    x = tr.begin("client.execute", track="client:c0", cat="client",
                 lane=True)
    clock.t = 1.0
    tr.end(x, args={"executed": 2})
    tr.instant("ticket.route", track="queue", cat="ticket",
               args={"shard": 3})
    tr.end(a, args={"status": "drained"})
    assert tr.balanced()
    evs = tr.events()
    assert tr.event_count() == len(evs) == 4     # async pair counts twice
    lane = next(e for e in evs if e["ph"] == "X")
    assert lane["ts"] == 0.25 and lane["dur"] == 0.75
    assert lane["args"] == {"executed": 2}
    b = next(e for e in evs if e["ph"] == "b")
    e = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == e["id"]
    # end-args merge over begin-args on the async begin event
    assert b["args"] == {"lease": 1, "status": "drained"}
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["args"] == {"shard": 3} and inst["ts"] == 1.0


def test_tracer_end_is_exactly_once_and_none_tolerant():
    tr = Tracer(clock=SimClock())
    tr.end(None)                                 # pop(key, None) idiom
    assert tr.balanced()                         # vacuously
    s = tr.begin("ticket")
    assert not tr.balanced() and tr.open_spans()[0]["name"] == "ticket"
    tr.end(s)
    assert tr.balanced()
    tr.end(s)                                    # double close
    assert tr.end_errors == 1 and not tr.balanced()


def test_tracer_begin_many_bulk_matches_begin():
    clock = SimClock()
    tr = Tracer(clock=clock)
    sids = tr.begin_many("ticket", [{"ticket": i} for i in range(5)],
                         track="queue", cat="ticket")
    assert len(set(sids)) == 5 and tr.spans_opened == 5
    # bulk ids interleave safely with singles
    s = tr.begin("lease")
    assert s not in sids
    clock.t = 1.0
    for sid in sids:
        tr.end(sid)
    tr.end(s)
    assert tr.balanced()
    begins = [e for e in tr.events() if e["ph"] == "b"
              and e["name"] == "ticket"]
    assert [e["args"]["ticket"] for e in begins] == list(range(5))


def test_chrome_trace_format_is_perfetto_loadable():
    clock = SimClock()
    tr = Tracer(clock=clock)
    s = tr.begin("lease", track="queue", cat="lease")
    clock.t = 0.5
    x = tr.begin("client.execute", track="client:c0", cat="client",
                 lane=True)
    clock.t = 2.0
    tr.end(x)
    tr.instant("federation.steal", track="member0", cat="federation")
    tr.end(s)
    trace = tr.chrome_trace()
    assert trace["displayTimeUnit"] == "ms"
    evs = trace["traceEvents"]
    json.dumps(trace)                            # JSON-safe throughout
    # one thread_name + thread_sort_index metadata pair per track
    meta = [e for e in evs if e["ph"] == "M"]
    named = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert named == {"queue", "client:c0", "member0"}
    assert any(e["name"] == "process_name" for e in meta)
    # timestamps are microseconds; instants carry thread scope
    lane = next(e for e in evs if e["ph"] == "X")
    assert lane["ts"] == 500000.0 and lane["dur"] == 1500000.0
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t"
    # every event lands on a declared track's tid of the single process
    tids = {e["args"]["name"]: e["tid"] for e in meta
            if e["name"] == "thread_name"}
    for e in evs:
        assert e.get("pid", 1) == 1
        if e["ph"] != "M":
            assert e["tid"] in set(tids.values())


def test_same_ops_same_virtual_clock_serialize_identically():
    def run_once() -> str:
        clock = SimClock()
        tr = Tracer(clock=clock)
        q = TicketQueue(timeout=30.0, redistribute_min=0.5, clock=clock,
                        tracer=tr)
        tids = q.add_many("t", list(range(8)))
        b1 = q.lease("a", 3)
        clock.t = 1.0
        q.submit_batch(b1.lease_id, {t: t for t in b1.ticket_ids}, "a")
        b2 = q.lease("b", 4)
        clock.t = 2.5
        q.release(b2.lease_id, client_failed=True)
        clock.t = 3.1
        b3 = q.lease("a", 8)
        q.submit_batch(b3.lease_id, {t: -t for t in b3.ticket_ids}, "a")
        q.cancel(tids)
        assert q.all_done() and tr.balanced()
        return tr.to_json()

    assert run_once() == run_once()


# ---------------------------------------------------------------------------
# Property: every queue-lifecycle span closes exactly once
# ---------------------------------------------------------------------------


@settings(max_examples=30)
@given(st.lists(st.tuples(
    st.sampled_from(["add", "lease", "submit", "release", "cancel",
                     "tick"]),
    st.integers(min_value=0, max_value=5)), min_size=1, max_size=40))
def test_property_spans_balance_over_random_op_sequences(ops):
    """Random interleavings of add/lease/submit/release/cancel (with
    redistribute_min=0, so one ticket can sit in several overlapping
    leases) must leave the trace balanced once the queue drains: every
    ticket and lease span closed exactly once, no end on a dead id."""
    clock = SimClock()
    tr = Tracer(clock=clock)
    q = TicketQueue(timeout=30.0, redistribute_min=0.0, clock=clock,
                    tracer=tr)
    leases = []
    for op, k in ops:
        if op == "add":
            q.add_many("t", list(range(k + 1)))
        elif op == "lease":
            b = q.lease(f"c{k % 3}", k + 1)
            if b is not None:
                leases.append(b)
        elif op == "submit" and leases:
            b = leases[k % len(leases)]
            q.submit_batch(b.lease_id,
                           {t: t for t in b.ticket_ids[:k + 1]}, b.client)
        elif op == "release" and leases:
            q.release(leases[k % len(leases)].lease_id,
                      client_failed=bool(k % 2))
        elif op == "cancel":
            q.cancel(list(q._tickets)[:k + 1])
        elif op == "tick":
            clock.t += 0.5 * (k + 1)
    # drain whatever the random walk left behind, as a fold would
    q.cancel([tid for tid, t in q._tickets.items() if not t.completed])
    for b in leases:
        q.release(b.lease_id)
    assert q.all_done()
    assert tr.balanced(), (tr.open_spans(), tr.end_errors)
    assert tr.spans_opened == tr.spans_closed
    if any(op == "add" for op, _ in ops):
        assert tr.spans_closed > 0


# ---------------------------------------------------------------------------
# Round engine: traced reticket / fold rounds stay balanced
# ---------------------------------------------------------------------------


async def _traced_round(policy, barrier_k, profiles, metrics=None):
    tr = Tracer()
    fed = make_fed(2, n_shards=4, sizer=FixedSizer(1), tracer=tr)
    tr.clock = fed.queue.clock
    fed.register_task(_grad_task())
    fed.spawn_clients(profiles)
    async with FederatedTrainer(fed, barrier_k=barrier_k,
                                straggler_policy=policy,
                                timeout=20.0, metrics=metrics) as t:
        res = await t.run_round(
            list(range(6)), shard_work=[1.0] * 6,
            statics={"weights": {"round": 0}})
    await fed.shutdown()
    return res, tr, fed


def _names(tr):
    return {e["name"] for e in tr.events()}


def test_traced_reticket_round_balances_and_records_policy_instants():
    res, tr, _ = _run(_traced_round(
        "reticket", 5,
        [ClientProfile(name="fast0", speed=500.0),
         ClientProfile(name="fast1", speed=500.0),
         ClientProfile(name="dead-slow", speed=0.5)]))
    assert res.complete
    assert tr.balanced(), tr.open_spans()
    names = _names(tr)
    assert {"ticket", "lease", "client.execute", "round",
            "ticket.route", "round.barrier_open",
            "round.reticket"} <= names
    # the round lane span closed ok and covers the whole round
    round_ev = next(e for e in tr.events()
                    if e["name"] == "round" and e["ph"] == "X")
    assert round_ev["args"]["status"] == "ok"
    assert round_ev["dur"] >= res.barrier_wait >= 0.0


def test_traced_fold_round_balances_and_cancel_closes_ticket_spans():
    res, tr, _ = _run(_traced_round(
        "fold", 5,
        [ClientProfile(name="fast0", speed=500.0),
         ClientProfile(name="fast1", speed=500.0),
         ClientProfile(name="dead-slow", speed=0.5)]))
    assert len(res.arrived) >= 5
    assert tr.balanced(), tr.open_spans()
    if res.stragglers:                  # straggler lost the race: folded
        assert "round.fold" in _names(tr)
        cancelled = [e for e in tr.events()
                     if e["name"] == "ticket" and e["ph"] == "b"
                     and e["args"].get("status") == "cancelled"]
        assert len(cancelled) == len(res.stragglers)


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_enforces_naming_and_idempotent_registration():
    reg = MetricsRegistry()
    for bad in ("no_subsystem_total", "cache.hits", "cache.hits_pct",
                "Cache.hits_total", "cache.", "queue.Rate_total"):
        assert not valid_metric_name(bad)
        with pytest.raises(ValueError):
            reg.counter(bad)
    c = reg.counter("cache.hits_total", labels=("cache",))
    assert reg.counter("cache.hits_total", labels=("cache",)) is c
    with pytest.raises(ValueError):                 # kind clash
        reg.gauge("cache.hits_total", labels=("cache",))
    with pytest.raises(ValueError):                 # label-set clash
        reg.counter("cache.hits_total", labels=("other",))
    with pytest.raises(ValueError):                 # wrong labels at use
        c.inc(other="x")
    c.inc(cache="edge0")
    c.inc(2.0, cache="edge0")
    assert c.value(cache="edge0") == 3.0
    c.set_total(7, cache="edge1")
    c.set_total(7, cache="edge1")                   # collector idempotence
    assert c.total() == 10.0


def test_histogram_buckets_snapshot_and_export():
    reg = MetricsRegistry()
    h = reg.histogram("round.duration_seconds", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(99.0)
    assert h.count() == 3 and h.sum() == pytest.approx(99.55)
    row = reg.snapshot()["round.duration_seconds"]["values"][0]
    assert row["buckets"] == {"0.1": 1, "1.0": 2, "inf": 3}
    assert row["count"] == 3
    rows = reg.export()
    assert [r["name"] for r in rows] == ["round.duration_seconds"]
    json.dumps(rows)                                 # BENCH-json safe


def test_metrics_registry_values_match_legacy_counters():
    """Differential check: after a real federated round, the registry's
    view (via collect_fabric) equals every legacy counter it absorbs —
    origin download ledger, per-member steals, edge-cache hits, queue
    lifecycle counts — and re-collection doesn't double-count."""
    async def go():
        reg = MetricsRegistry()
        fed = make_fed(2, n_shards=4)
        fed.register_task(_grad_task())
        fed.spawn_clients([ClientProfile(name=f"c{i}", speed=500.0)
                           for i in range(3)])
        async with FederatedTrainer(fed, metrics=reg, timeout=20.0) as t:
            res = await t.run_round(
                list(range(6)), shard_work=[1.0] * 6,
                statics={"weights": {"round": 0}})
        await fed.shutdown()
        collect_fabric(reg, distributor=fed)
        return reg, fed, res

    reg, fed, res = _run(go())
    assert res.complete
    # trainer-owned histograms landed in the RoundResult snapshot
    assert res.metrics["round.duration_seconds"]["values"][0]["count"] == 1
    # the trainer prunes the round's tickets, so the queue counters are
    # small — the differential contract is equality, whatever the value
    snap = fed.queue.snapshot()
    assert reg.get("queue.executed_total").value() == snap["executed"]
    assert (reg.get("queue.redistributions_total").value()
            == snap["redistributions"])
    rate = reg.get("queue.client_rate")
    assert snap["clients"], "no client ever reported"
    for client, cs in snap["clients"].items():
        assert rate.value(client=client) == (cs["rate"] or 0.0) > 0
    dl = reg.get("origin.downloads_total")
    assert fed.download_count, "origin ledger unexpectedly empty"
    for key, n in fed.download_count.items():
        assert dl.value(key=key) == n
    steals = reg.get("federation.steals_total")
    hits = reg.get("cache.hits_total")
    for m in fed.members:
        assert steals.value(member=m.index) == m.steals
        s = m.edge.stats()
        assert hits.value(cache=s["name"]) == s["hits"]
    assert reg.get("federation.alive_count").value() == 2
    # collectors are re-runnable views: same values, not doubled
    before = reg.snapshot()
    collect_fabric(reg, distributor=fed)
    assert reg.snapshot() == before


# ---------------------------------------------------------------------------
# Trace context on the v2 wire
# ---------------------------------------------------------------------------


def test_trace_context_builder_strict_parser_tolerant():
    assert make_trace_context(lease=3, client="c", round=None) == \
        {"lease": 3, "client": "c"}
    with pytest.raises(ValueError):
        make_trace_context(bogus=1)                  # builder is strict
    # parser never raises on junk from an untrusted peer
    assert parse_trace_context(None) is None
    assert parse_trace_context([1, 2]) is None
    assert parse_trace_context("x") is None
    assert parse_trace_context({"lease": True, "client": 7,
                                "exec_s": "fast", "extra": ()}) == {}
    assert parse_trace_context(
        {"lease": 3, "client": "c", "exec_s": 0.25, "round": 2,
         "junk": 1}) == \
        {"lease": 3, "client": "c", "exec_s": 0.25, "round": 2}


def test_wire_trace_context_rides_v2_and_closes_server_spans():
    async def go():
        tr = Tracer()
        d = AsyncDistributor(
            timeout=10.0, redistribute_min=0.02,
            sizer=AdaptiveSizer(target_lease_time=0.05, max_size=8),
            watchdog_interval=0.01, tracer=tr)
        tr.clock = d.queue.clock
        d.register_task(TaskDef("sq", _square))
        d.add_work("sq", list(range(12)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)])
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, tr, clients[0]

    ok, tr, c = _run(go())
    assert ok
    # every grant carried trace context; the submit echo closed the
    # server's wire span with the client-measured execute time
    assert c.trace_contexts == c.leases_taken > 0
    assert tr.balanced(), tr.open_spans()
    wire = [e for e in tr.events()
            if e["name"] == "wire.lease" and e["ph"] == "X"]
    assert wire
    assert all(e["args"]["status"] == "submitted" for e in wire)
    assert all(e["args"]["exec_s"] >= 0 for e in wire)


def test_wire_untraced_grants_carry_no_trace_context():
    async def go():
        d = AsyncDistributor(
            timeout=10.0, redistribute_min=0.02,
            sizer=AdaptiveSizer(target_lease_time=0.05, max_size=8),
            watchdog_interval=0.01)
        d.register_task(TaskDef("sq", _square))
        d.add_work("sq", list(range(8)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)])
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, clients[0]

    ok, c = _run(go())
    assert ok
    assert c.trace_contexts == 0 and c.leases_taken > 0


# ---------------------------------------------------------------------------
# run_until_done stall diagnostics (the silent wall-cap fix)
# ---------------------------------------------------------------------------


def test_run_until_done_wall_cap_warns_with_stall_report():
    clock = SimClock()                    # a wedged virtual clock

    async def go():
        tr = Tracer(clock=clock)
        d = AsyncDistributor(timeout=5.0, redistribute_min=0.02,
                             clock=clock, tracer=tr)
        d.register_task(TaskDef("sq", _square))
        d.add_work("sq", [1, 2, 3])
        d.queue.lease("ghost", 2)         # an in-flight lease to report
        with pytest.warns(RuntimeWarning,
                          match="run_until_done gave up"):
            ok = await d.run_until_done(timeout=100.0, wall_cap=0.2)
        return ok, d.last_stall_report, tr

    ok, report, tr = _run(go())
    assert ok is False
    assert report["reason"] == "wall_cap"
    assert report["snapshot"]["tickets"] == 3
    assert report["snapshot"]["executed"] == 0
    assert [ls["client"] for ls in report["outstanding_leases"]] == ["ghost"]
    assert "ghost" in report["client_rates"]
    json.dumps(report)                    # structured, log-shippable
    # the give-up is also on the trace, where the timeline shows context
    stall = [e for e in tr.events() if e["name"] == "distributor.stall"]
    assert len(stall) == 1 and stall[0]["args"]["reason"] == "wall_cap"


def test_run_until_done_virtual_timeout_warns_with_timeout_reason():
    class SteppingClock:
        def __init__(self):
            self.t = 0.0

        def __call__(self):
            self.t += 1.0
            return self.t

    async def go():
        d = AsyncDistributor(timeout=5.0, redistribute_min=0.02,
                             clock=SteppingClock())
        d.register_task(TaskDef("sq", _square))
        d.add_work("sq", [1])
        with pytest.warns(RuntimeWarning, match="timeout expired"):
            ok = await d.run_until_done(timeout=5.0)
        return ok, d.last_stall_report

    ok, report = _run(go())
    assert ok is False and report["reason"] == "timeout"
    assert report["virtual_clock"] > 5.0


# ---------------------------------------------------------------------------
# Spans around synchronous host work, and the current tracer
# ---------------------------------------------------------------------------


def test_tracer_span_nests_and_stays_balanced():
    clock = SimClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", cat="round", args={"round": 3}) as outer:
        clock.t = 1.0
        with tr.span("inner", cat="wire") as inner:
            clock.t = 1.5
            inner["bytes"] = 42
        clock.t = 2.0
        outer["leaves"] = 7
    with pytest.raises(RuntimeError):
        with tr.span("raises"):
            clock.t = 3.0
            raise RuntimeError("boom")
    assert tr.balanced()
    evs = tr.events()
    # completion order: the inner block ends first
    assert [e["name"] for e in evs] == ["inner", "outer", "raises"]
    inner_ev, outer_ev, raised = evs
    assert all(e["ph"] == "X" and e["block"] is True for e in evs)
    assert all(e["track"] == "host" for e in evs)
    assert (inner_ev["ts"], inner_ev["dur"]) == (1.0, 0.5)
    assert (outer_ev["ts"], outer_ev["dur"]) == (0.0, 2.0)
    assert outer_ev["ts"] <= inner_ev["ts"] and (
        inner_ev["ts"] + inner_ev["dur"] <= outer_ev["ts"] + outer_ev["dur"])
    assert inner_ev["args"] == {"bytes": 42}
    assert outer_ev["args"] == {"round": 3, "leaves": 7}
    assert (raised["ts"], raised["dur"]) == (2.0, 1.0)
    # lane spans of begin/end carry no block mark; Chrome export drops it
    x = tr.begin("client.execute", lane=True)
    tr.end(x)
    assert "block" not in tr.events()[-1]
    assert all("block" not in e for e in tr.chrome_trace()["traceEvents"])


def test_trace_span_is_a_noop_without_a_current_tracer():
    from contextlib import nullcontext

    from repro.obs import trace
    assert trace.current() is None
    ctx = trace.span("grad.h2d")
    assert isinstance(ctx, nullcontext)
    with ctx as args:
        assert args is None
    with trace.use(None):                       # keeps "none current"
        assert trace.current() is None
    tr = Tracer(clock=SimClock())
    with trace.use(tr):
        assert trace.current() is tr
        with trace.use(None):                   # keeps the outer one
            assert trace.current() is tr
        with trace.span("grad.h2d", cat="grad") as args:
            args["bytes"] = 1
    assert trace.current() is None
    assert [(e["name"], e["args"]) for e in tr.events()] == [
        ("grad.h2d", {"bytes": 1})]


@pytest.mark.parametrize("sharded", [False, True])
def test_lease_span_carries_its_ticket_ids(sharded):
    from repro.core.shards import ShardedTicketQueue
    clock = SimClock()
    tr = Tracer(clock=clock)
    q = (ShardedTicketQueue(3, timeout=30.0, redistribute_min=1.0,
                            clock=clock, tracer=tr) if sharded
         else TicketQueue(timeout=30.0, redistribute_min=1.0, clock=clock,
                          tracer=tr))
    q.add_many("t", list(range(7)))
    batches = [q.lease("a", 3), q.lease("b", 4)]
    for b in batches:
        q.submit_batch(b.lease_id, {t: t for t in b.ticket_ids}, b.client)
    assert tr.balanced()
    leases = {e["args"]["lease"]: e["args"] for e in tr.events()
              if e["name"] == "lease" and e["ph"] == "b"}
    assert {lid: a["ticket_ids"] for lid, a in leases.items()} == {
        b.lease_id: list(b.ticket_ids) for b in batches}
    assert all(a["tickets"] == len(a["ticket_ids"]) for a in leases.values())


# the paper CNN at fabric size, through remote clients over loopback


def _fabric_cnn():
    from repro.configs.paper_cnn import FABRIC_CNN
    return FABRIC_CNN


def _cnn_params(ccfg, seed=0):
    import jax

    from repro.models.cnn import init_cnn
    from repro.sharding.spec import values_tree
    return jax.device_get(values_tree(init_cnn(jax.random.PRNGKey(seed),
                                               ccfg)))


async def _traced_cnn_rounds(m: int, rounds: int, tracer):
    from repro.core.split_parallel import TrainState
    from repro.models.cnn import CnnGradShard
    from repro.optim import adagrad
    from repro.train_fabric import FederatedTrainingLoop, FusedServerStep

    ccfg = _fabric_cnn()
    rows = ccfg.batch_size
    task = CnnGradShard(ccfg, n_rows=m * rows, seed=1)
    fed = make_fed(2, n_shards=4, redistribute_min=10.0,
                   sizer=FixedSizer(1), tracer=tracer)
    fed.register_task(TaskDef("cnn", task, static_files=("weights",)))
    server = TransportServer(fed)
    addr = await server.start()
    clients, tasks = spawn_remote_clients(
        addr, [ClientProfile(name=f"c{i}", speed=0.0) for i in range(m)],
        reconnect_delay=0.02, tracer=tracer)
    opt = adagrad(0.02)
    params = _cnn_params(ccfg)
    state = TrainState(params=params, head={}, head_stale={},
                       opt_state=opt.init(params), head_opt_state={},
                       prev_features=(), prev_labels=(), prev_mask=(),
                       step=np.zeros((), np.int32))
    trainer = FederatedTrainer(fed, task_name="cnn", timeout=60.0)
    loop = FederatedTrainingLoop(
        trainer, opt, state,
        server_step=FusedServerStep(opt, lr=0.02, mode="xla"))
    args = [(i * rows, (i + 1) * rows) for i in range(m)]
    results = []
    try:
        # every client parked on a lease before the first round, so that
        # each of them takes one of the round's M tickets
        for _ in range(3000):
            frames = server.stats()["by_type"]["frames_in"]
            if frames.get("lease_request", 0) >= m:
                break
            await asyncio.sleep(0.01)
        async with trainer:
            for _ in range(rounds):
                results.append(await loop.run_round(args, [float(rows)] * m))
    finally:
        for c in clients:
            await c.stop()
        await asyncio.gather(*tasks, return_exceptions=True)
        await server.stop()
        await fed.shutdown()
    return results, clients


def test_traced_cnn_round_records_each_host_span_once_per_piece_of_work():
    import jax
    m, rounds = 4, 2
    tr = Tracer()
    results, clients = _run(_traced_cnn_rounds(m, rounds, tr))
    assert all(r.complete for r in results)
    assert tr.balanced(), tr.open_spans()
    blocks = [e for e in tr.events() if e.get("block")]
    count = {}
    for e in blocks:
        count[e["name"]] = count.get(e["name"], 0) + 1
    # per round: one publish; each of the M shards has its weights
    # encoded by the server and decoded by its client, and its gradient
    # encoded by the client and decoded by the server
    assert count == {"round.publish": rounds,
                     "wire.encode": 2 * m * rounds,
                     "wire.decode": 2 * m * rounds,
                     "grad.h2d": m * rounds, "grad.compute": m * rounds,
                     "grad.d2h": m * rounds,
                     "server_step.coeffs": rounds,
                     "server_step.h2d": rounds,
                     "server_step.compute": rounds}
    by = lambda name, **kv: [e["args"] for e in blocks if e["name"] == name
                             and all(e["args"].get(k) == v
                                     for k, v in kv.items())]
    assert len(by("wire.encode", side="server", kind="static_data")) == \
        len(by("wire.encode", side="client", kind="submit")) == m * rounds
    assert len(by("wire.decode", side="client", kind="static_data")) == \
        len(by("wire.decode", side="server", kind="submit")) == m * rounds
    # a client decodes what the server encoded, and the other way round
    assert sorted(a["bytes"] for a in by("wire.encode", side="server")) \
        == sorted(a["bytes"] for a in by("wire.decode", side="client"))
    assert sorted(a["bytes"] for a in by("wire.encode", side="client")) \
        == sorted(a["bytes"] for a in by("wire.decode", side="server"))
    ccfg = _fabric_cnn()
    p_bytes = sum(x.nbytes for x in
                  jax.tree_util.tree_leaves(_cnn_params(ccfg)))
    rows = ccfg.batch_size
    x_bytes = rows * ccfg.image_size ** 2 * ccfg.in_channels * 4 + rows * 4
    assert all(a["bytes"] == p_bytes + x_bytes for a in by("grad.h2d"))
    assert all(a["bytes"] == p_bytes for a in by("grad.d2h"))
    # a remote client's task moves its operands in one packed buffer
    # and its gradients back in one
    assert all(a["arrays"] == 1 for a in by("grad.h2d") + by("grad.d2h"))
    assert all(a["bytes"] == m * p_bytes for a in by("server_step.h2d"))
    assert all(a["M"] == m for a in by("server_step.coeffs"))
    # the publish is the round tag and every param leaf, all changed
    n_leaves = len(jax.tree_util.tree_leaves(_cnn_params(ccfg)))
    assert [(a["leaves"], a["changed"]) for a in by("round.publish")] == [
        (n_leaves + 1, n_leaves + 1)] * rounds
    # each leased ticket's id is on its lease span
    leased = sorted(t for e in tr.events() if e["name"] == "lease"
                    and e["ph"] == "b" for t in e["args"]["ticket_ids"])
    assert leased == sorted(t for r in results for t in r.ticket_ids)


def _grad_and_step(tracer, mode):
    import jax

    from repro.models.cnn import CnnGradShard
    from repro.obs import trace
    from repro.optim import adagrad
    from repro.train_fabric import FusedServerStep

    ccfg = _fabric_cnn()
    rows = ccfg.batch_size
    params = _cnn_params(ccfg, seed=3)
    task = CnnGradShard(ccfg, n_rows=3 * rows, seed=5)
    opt = adagrad(0.02)
    step = FusedServerStep(opt, lr=0.02, mode=mode)
    with trace.use(tracer):
        outs = [task(((i * rows), (i + 1) * rows),
                     {"weights": {"round": 0, "params": params}})
                for i in range(3)]
        new = step.step([o["grad"] for o in outs], [1.0, 2.0, 3.0], params,
                        opt.init(params))
    return jax.device_get((outs, new))


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_grad_shard_and_fused_step_are_bit_identical_when_traced(mode):
    import jax
    tr = Tracer()
    plain = _grad_and_step(None, mode)
    traced = _grad_and_step(tr, mode)
    a, b = jax.tree_util.tree_leaves(plain), jax.tree_util.tree_leaves(traced)
    assert len(a) == len(b)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    names = [e["name"] for e in tr.events()]
    assert names == ["grad.h2d", "grad.compute", "grad.d2h"] * 3 + [
        "server_step.coeffs", "server_step.h2d", "server_step.compute"]


def test_device_programs_carry_stable_names():
    """A trace reduction finds each program by its module name,
    ``jit_<name>``, whatever the code around it is called."""
    from repro.models.cnn import loss_and_grads
    from repro.optim import adagrad
    from repro.train_fabric import FusedServerStep
    from repro.train_fabric.server_step import _coeffs_jit
    ccfg = _fabric_cnn()
    params = _cnn_params(ccfg)
    x = np.zeros((2, ccfg.image_size, ccfg.image_size, ccfg.in_channels),
                 np.float32)
    module = lambda lowered: lowered.as_text().split("\n", 1)[0]
    assert "@jit_cnn_loss_and_grads" in module(
        loss_and_grads(ccfg).lower(params, x, np.zeros(2, np.int32)))
    coeffs = np.ones(2, np.float32)
    assert "@jit_member_coeffs" in module(
        _coeffs_jit(None).lower((params, params), coeffs))
    for mode in ("xla", "interpret"):
        step = FusedServerStep(adagrad(0.02), lr=0.02, mode=mode)
        assert "@jit_fused_server_step" in module(
            step._jit.lower((params, params), coeffs, params, params))
