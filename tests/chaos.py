"""Chaos harness: churning remote fleets against the live transport.

The reusable half of the browser-scale story (the 10k-client version
runs on the virtual clock in ``benchmarks/churn_scale.py``; this module
is real sockets).  :class:`ChurningFleet` manages a population of
``RemoteBrowserClient``\\ s whose device parameters come from
``core/profiles.py`` and can **abruptly kill** any fraction of them —
task cancelled, socket dropped, no release frame, exactly a closed tab —
then backfill with fresh devices.  The tests drive ``FederatedTrainer``
rounds where *every* client is remote, under per-round churn, and assert
the fabric's churn contract:

  * no round stalls (``FederatedTrainer.stalls == 0`` with a stall
    detector armed far below the round timeout);
  * no ticket is lost (every round closes complete) and none
    double-completes (first result wins; eviction cannot re-run a
    finished ticket into a second accept);
  * admission refusals are retryable — refused clients back off and the
    work still finishes.

Run in tier-1 via pytest; everything uses loopback sockets, tiny
workloads, and generous wall deadlines.
"""
import asyncio

from hypothesis import given, settings, strategies as st

from repro.core.distributor import (AdaptiveSizer, AsyncDistributor,
                                    ClientProfile, FixedSizer, TaskDef)
from repro.core.federation import FederatedDistributor
from repro.core.profiles import draw_fleet, scale_hazard
from repro.core.transport import (PROTOCOL_VERSION, RemoteBrowserClient,
                                  TransportServer, encode_frame,
                                  encode_payload, read_frame,
                                  reconnect_backoff, spawn_remote_clients)
from repro.obs.trace import Tracer
from repro.train_fabric.round_engine import FederatedTrainer


# module-level so they pickle across the wire
def _square(x, static):
    return x * x


def _grad(x, static):
    w = static["weights"]
    return {"grad": x * 2, "loss": float(x), "round": w["round"]}


def chaos_profiles(n: int, *, seed: int = 0, speed_scale: float = 50.0,
                   churn_target: float = 0.2) -> list:
    """``n`` ClientProfiles drawn from the device-tier mix
    (``core/profiles.py``), speeds scaled up so wall-clock tests finish
    fast, latencies capped so a Pareto tail draw can't eat the test
    deadline."""
    fleet = scale_hazard(draw_fleet(n, seed=seed), churn_target)
    return [d.client_profile(speed=d.speed * speed_scale,
                             latency=min(d.latency, 0.05))
            for d in fleet]


class ChurningFleet:
    """A population of remote clients with a tab-close lever.

    ``spawn(profiles)`` dials clients at the server; ``kill(frac)``
    abruptly cancels that fraction of the *live* clients (socket dropped
    mid-whatever, no release — the server only finds out via eviction or
    the watchdog) and returns how many died.  ``backfill()`` replaces
    the dead with fresh devices drawn from the same tier mix, like new
    visitors opening the page."""

    def __init__(self, address, *, seed: int = 0, client_kw=None):
        self.address = address
        self.seed = seed
        self.client_kw = dict(client_kw or {})
        self.clients: list = []
        self.tasks: list = []
        self.killed = 0
        self._generation = 0

    def spawn(self, profiles):
        clients, tasks = spawn_remote_clients(self.address, profiles,
                                              **self.client_kw)
        self.clients.extend(clients)
        self.tasks.extend(tasks)
        return clients

    def live(self) -> list:
        return [(c, t) for c, t in zip(self.clients, self.tasks)
                if not c.done and not t.done()]

    def kill(self, frac: float) -> int:
        """Close tabs: every k-th live client dies abruptly (cancel +
        socket drop, nothing released)."""
        live = self.live()
        n = max(1, int(len(live) * frac)) if live else 0
        for c, t in live[:n]:
            t.cancel()
            c._disconnect()
            self.killed += 1
        return n

    def backfill(self, n: int, *, speed_scale: float = 50.0):
        """``n`` fresh devices join (a later page-load generation, so
        names never collide with the dead)."""
        self._generation += 1
        profiles = chaos_profiles(
            n, seed=self.seed + 1000 * self._generation,
            speed_scale=speed_scale)
        profiles = [ClientProfile(
            name=f"g{self._generation}-{p.name}", speed=p.speed,
            latency=p.latency) for p in profiles]
        return self.spawn(profiles)

    async def join(self):
        """Stop survivors and await every client task (cancelled tasks
        are absorbed)."""
        for c, _ in self.live():
            await c.stop()
        await asyncio.gather(*self.tasks, return_exceptions=True)


# ---------------------------------------------------------------------------
# Tentpole: all-remote FederatedTrainer rounds under per-round churn
# ---------------------------------------------------------------------------


def test_all_remote_trainer_rounds_survive_per_round_churn():
    """Every client is a RemoteBrowserClient; ~a third of the fleet is
    abruptly killed EVERY round and backfilled.  Heartbeat eviction (not
    the watchdog: grace is set prohibitively high) must bring the dead
    tabs' leases back fast enough that no round stalls and every round
    closes with all shards arrived."""
    ROUNDS, SHARDS, FLEET = 4, 8, 10

    async def go():
        fed = FederatedDistributor(
            2, n_shards=4, timeout=30.0, redistribute_min=0.02,
            sizer=FixedSizer(1), watchdog_interval=5.0, grace=1000.0)
        fed.register_task(TaskDef("backbone_shard", _grad,
                                  static_files=("weights",)))
        server = TransportServer(fed, heartbeat_timeout=0.25,
                                 eviction_interval=0.05)
        addr = await server.start()
        fleet = ChurningFleet(
            addr, client_kw=dict(reconnect_delay=0.02, backoff_cap=0.2,
                                 heartbeat_interval=0.05))
        fleet.spawn(chaos_profiles(FLEET))
        results = []
        async with FederatedTrainer(fed, timeout=25.0,
                                    stall_after=5.0) as trainer:
            for r in range(ROUNDS):
                fleet.kill(0.34)           # tabs close mid-round setup
                fleet.backfill(4)
                res = await trainer.run_round(
                    list(range(SHARDS)),
                    statics={"weights": {"round": r}})
                results.append(res)
            stalls = trainer.stalls
        await fleet.join()
        await fed.shutdown()
        stats = server.stats()
        await server.stop()
        return results, stalls, stats, fleet.killed

    results, stalls, stats, killed = asyncio.run(go())
    assert len(results) == 4 and killed >= 4
    for res in results:
        # no ticket lost: every round closed with every shard arrived,
        # and each shard's gradient is the exactly-once first result
        assert res.complete, (res.index, res.stragglers)
        assert [g["grad"] for g in res.results] == [2 * i for i in range(8)]
    assert stalls == 0
    # the recovery path was exercised: dead tabs were evicted (watchdog
    # grace is 1000x ETA, so eviction is the only way this passed)
    assert stats["evictions"] >= 1
    assert stats["evicted_leases"] >= 0


def test_heartbeats_keep_slow_client_alive_under_eviction():
    """Slow is not gone: an execute several times longer than the
    heartbeat timeout survives because the client heartbeats between
    compute chunks — zero evictions, work completes first try."""
    async def go():
        d = AsyncDistributor(timeout=20.0, redistribute_min=0.02,
                             sizer=FixedSizer(1), watchdog_interval=5.0,
                             grace=1000.0)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", [3])       # one ticket, work=1.0
        server = TransportServer(d, heartbeat_timeout=0.2,
                                 eviction_interval=0.04)
        addr = await server.start()
        # speed 1.25 -> ~0.8s execute, 4x the heartbeat timeout
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="slowpoke", speed=1.25)],
            heartbeat_interval=0.05)
        ok = await d.run_until_done(timeout=15.0)
        await asyncio.gather(*tasks)
        stats = server.stats()
        await server.stop()
        return ok, d.queue.results(), tids, stats, clients[0]

    ok, res, tids, stats, client = asyncio.run(go())
    assert ok and res[tids[0]] == 9
    assert stats["evictions"] == 0
    assert client.heartbeats_sent >= 3
    assert stats["heartbeats"] == client.heartbeats_sent
    assert client.reconnects == 0


def test_eviction_releases_silent_lease_long_before_watchdog():
    """A raw-socket puppet takes a lease and goes silent.  With the
    watchdog effectively disabled (grace 1000x), only heartbeat eviction
    can recover the ticket — and it must do so in well under a second so
    a real client finishes the round."""
    async def go():
        d = AsyncDistributor(timeout=20.0, redistribute_min=0.0,
                             sizer=FixedSizer(1), watchdog_interval=5.0,
                             grace=1000.0)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", [7])
        server = TransportServer(d, heartbeat_timeout=0.15,
                                 eviction_interval=0.03)
        addr = await server.start()
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(encode_frame({"type": "hello", "seq": 1,
                                   "client": "ghost",
                                   "proto": PROTOCOL_VERSION}))
        writer.write(encode_frame({"type": "lease_request", "seq": 2}))
        await writer.drain()
        hello = await asyncio.wait_for(read_frame(reader), timeout=5.0)
        grant = await asyncio.wait_for(read_frame(reader), timeout=5.0)
        assert hello["type"] == "hello_ok"
        assert grant["type"] == "lease_grant" and not grant["done"]
        lease_id = grant["lease_id"]
        assert d.queue.lease_is_outstanding(lease_id)
        # ... and now the ghost says nothing.  Eviction must fire within
        # ~timeout + sweep interval; poll with a hard 2s cap.
        t0 = asyncio.get_running_loop().time()
        while d.queue.lease_is_outstanding(lease_id):
            assert asyncio.get_running_loop().time() - t0 < 2.0, \
                "eviction never released the silent lease"
            await asyncio.sleep(0.01)
        took = asyncio.get_running_loop().time() - t0
        # a live client picks the freed ticket up and finishes the round
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=200.0)])
        ok = await d.run_until_done(timeout=15.0)
        await asyncio.gather(*tasks)
        stats = server.stats()
        writer.close()
        await server.stop()
        return ok, d.queue.results(), tids, stats, took

    ok, res, tids, stats, took = asyncio.run(go())
    assert ok and res[tids[0]] == 49
    assert stats["evictions"] == 1 and stats["evicted_leases"] == 1
    assert took < 1.0                      # vs grace x ETA ~ minutes


# ---------------------------------------------------------------------------
# Admission control
# ---------------------------------------------------------------------------


def test_admission_cap_refuses_overflow_and_work_still_completes():
    """Six clients dial a server capped at two accepted connections per
    endpoint: the overflow is refused with ``busy`` (not an error),
    retries with jittered backoff, and every ticket still completes —
    backpressure sheds load without shedding work."""
    async def go():
        d = AsyncDistributor(timeout=20.0, redistribute_min=0.02,
                             sizer=AdaptiveSizer(target_lease_time=0.05,
                                                 max_size=8),
                             watchdog_interval=0.01)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", list(range(40)))
        server = TransportServer(d, max_conns_per_member=2,
                                 retry_after=0.05)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name=f"c{i}", speed=500.0)
                   for i in range(6)],
            reconnect_delay=0.02, backoff_cap=0.2, max_reconnects=200)
        ok = await d.run_until_done(timeout=20.0)
        await asyncio.gather(*tasks)
        stats = server.stats()
        await server.stop()
        return ok, d.queue.results(), tids, stats, clients

    ok, res, tids, stats, clients = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i * i for i in range(40)]
    # the cap actually bit, server- and client-side views agree
    assert stats["busy_refusals"] >= 1
    assert sum(c.busy_refusals for c in clients) == stats["busy_refusals"]
    assert stats["by_type"]["frames_out"].get("busy", 0) \
        == stats["busy_refusals"]


# ---------------------------------------------------------------------------
# Satellite: reconnect-during-eviction race (no double-complete)
# ---------------------------------------------------------------------------


def test_evicted_client_inflight_submit_cannot_double_complete():
    """The lease-bookkeeping pin-down: a client evicted while its submit
    is in flight re-submits after reconnect under the OLD lease id,
    *after* another client already completed the ticket.  The late
    submit must be accepted 0 times and the first result must stand —
    the ticket never double-completes."""
    async def go():
        d = AsyncDistributor(timeout=20.0, redistribute_min=0.0,
                             sizer=FixedSizer(1), watchdog_interval=5.0,
                             grace=1000.0)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", [7])
        server = TransportServer(d, heartbeat_timeout=5.0)
        addr = await server.start()
        # puppet takes the lease...
        reader, writer = await asyncio.open_connection(*addr)
        writer.write(encode_frame({"type": "hello", "seq": 1,
                                   "client": "pup",
                                   "proto": PROTOCOL_VERSION}))
        writer.write(encode_frame({"type": "lease_request", "seq": 2}))
        await writer.drain()
        await asyncio.wait_for(read_frame(reader), timeout=5.0)
        grant = await asyncio.wait_for(read_frame(reader), timeout=5.0)
        lease_id = grant["lease_id"]
        # ...fires its submit into the socket (in flight, not awaited)
        # and is evicted in the same breath — either arrival order must
        # be safe
        writer.write(encode_frame(
            {"type": "submit", "seq": 3, "lease_id": lease_id,
             "results": {str(tids[0]): encode_payload(999)}}))
        released = await server.evict_client("pup")
        # eviction redistributes the ticket; a live client computes the
        # real answer
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=200.0)])
        ok = await d.run_until_done(timeout=15.0)
        await asyncio.gather(*tasks)
        writer.close()
        # puppet reconnects and replays the SAME submit under the old
        # lease id (reconnect-resume path), plus a stale heartbeat
        r2, w2 = await asyncio.open_connection(*addr)
        w2.write(encode_frame({"type": "hello", "seq": 10,
                               "client": "pup",
                               "proto": PROTOCOL_VERSION}))
        w2.write(encode_frame(
            {"type": "submit", "seq": 11, "lease_id": lease_id,
             "results": {str(tids[0]): encode_payload(999)}}))
        w2.write(encode_frame({"type": "heartbeat", "seq": 12,
                               "lease_id": lease_id}))
        await w2.drain()
        replies = [await asyncio.wait_for(read_frame(r2), timeout=5.0)
                   for _ in range(3)]
        w2.close()
        snap = d.queue.snapshot()
        await server.stop()
        return ok, released, d.queue.results(), tids, replies, snap

    ok, released, res, tids, replies, snap = asyncio.run(go())
    assert ok and released >= 0
    hello2, submit2, beat2 = replies
    assert hello2["type"] == "hello_ok"
    # the replayed submit is politely accepted as a frame but completes
    # NOTHING: the ticket already has its first result
    assert submit2["type"] == "submit_ok" and submit2["accepted"] == 0
    assert beat2["type"] == "heartbeat_ok"
    assert res[tids[0]] == 49              # first result stood
    assert snap["executed"] == 1           # exactly one completion


# ---------------------------------------------------------------------------
# Satellite: capped exponential reconnect backoff
# ---------------------------------------------------------------------------


def test_reconnect_backoff_schedule_is_capped_exponential():
    """The pure schedule: doubles from ``base``, saturates at ``cap``,
    and jitter only scales the span into [0.5x, 1.0x] — never above."""
    full = [reconnect_backoff(k, base=0.05, cap=2.0, rand=lambda: 1.0)
            for k in range(1, 10)]
    assert full == [0.05, 0.1, 0.2, 0.4, 0.8, 1.6, 2.0, 2.0, 2.0]
    half = [reconnect_backoff(k, base=0.05, cap=2.0, rand=lambda: 0.0)
            for k in range(1, 10)]
    assert half == [x * 0.5 for x in full]
    import random as _random
    rng = _random.Random(1)
    for k in range(1, 12):
        span = min(2.0, 0.05 * 2 ** (k - 1))
        d = reconnect_backoff(k, base=0.05, cap=2.0, rand=rng.random)
        assert span * 0.5 <= d <= span


def test_client_reconnect_backoff_observed_with_injected_clock():
    """A client dialing a dead address sleeps the exact capped-
    exponential schedule (injected ``_sleep`` records, injected rand
    pins jitter at 1.0) and gives up after ``max_reconnects``."""
    import types

    async def go():
        d = AsyncDistributor(timeout=5.0, redistribute_min=0.02,
                             sizer=FixedSizer(1), watchdog_interval=0.01)
        d.register_task(TaskDef("sq", _square))
        server = TransportServer(d)
        addr = await server.start()
        await server.stop()                # port is now refused
        client = RemoteBrowserClient(*addr, ClientProfile(name="lonely"),
                                     reconnect_delay=0.05, backoff_cap=0.4,
                                     max_reconnects=5)
        sleeps = []

        async def fake_sleep(s):
            sleeps.append(s)

        client._sleep = fake_sleep
        client._backoff_rand = types.SimpleNamespace(random=lambda: 1.0)
        try:
            await client.run()
        except ConnectionError:
            return sleeps, True
        return sleeps, False

    sleeps, gave_up = asyncio.run(go())
    assert gave_up
    assert sleeps == [0.05, 0.1, 0.2, 0.4, 0.4]


# ---------------------------------------------------------------------------
# Satellite: property test — exactly-once under random interleavings
# ---------------------------------------------------------------------------


class _SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


@settings(max_examples=20)
@given(st.lists(st.sampled_from(
    ["connect", "lease", "compute", "submit", "heartbeat", "evict",
     "reconnect", "tick"]), min_size=8, max_size=60),
    st.integers(min_value=2, max_value=7))
def test_property_interleavings_exactly_once_and_spans_balance(ops, n):
    """Random interleavings of connect/lease/compute/submit/heartbeat/
    evict/reconnect over the server's lease-bookkeeping discipline (the
    same queue calls ``TransportServer`` makes, including eviction's
    drain-then-release and reconnect's late submit): every ticket is
    accepted EXACTLY once across all submits — duplicates, evictions and
    replays included — and the ticket/lease trace from ``test_obs``'s
    balance property stays balanced under eviction."""
    from repro.core.tickets import TicketQueue

    clock = _SimClock()
    tr = Tracer(clock=clock)
    q = TicketQueue(timeout=1e9, redistribute_min=0.0, clock=clock,
                    tracer=tr)
    tids = q.add_many("t", list(range(n)))
    accepted_total = 0
    # per client: live flag, server-held leases, in-flight submits that
    # were cut off by an eviction (replayed on reconnect)
    clients = {c: {"live": False, "leases": {}, "cut": []}
               for c in ("a", "b")}
    which = 0
    for op in ops:
        c = ("a", "b")[which % 2]
        which += 1
        st_c = clients[c]
        clock.t += 0.01
        if op == "connect":
            st_c["live"] = True
        elif op == "tick" or op == "heartbeat":
            clock.t += 0.05                # liveness only; queue untouched
        elif op == "lease" and st_c["live"]:
            batch = q.lease(c, 2)
            if batch is not None:
                st_c["leases"][batch.lease_id] = batch
        elif op == "compute" and st_c["leases"]:
            # finish the oldest lease and submit it (the common path)
            lid, batch = next(iter(st_c["leases"].items()))
            del st_c["leases"][lid]
            results = {t.ticket_id: t.args * 10 for t in batch.tickets}
            accepted_total += q.submit_batch(lid, results, c)
        elif op == "submit" and st_c["cut"]:
            # an in-flight submit from BEFORE an eviction finally lands
            lid, results = st_c["cut"].pop(0)
            accepted_total += q.submit_batch(lid, results, c)
        elif op == "evict" and st_c["live"]:
            # server drains bookkeeping first, then force-releases; any
            # lease mid-submit becomes a cut-off (replayed later)
            st_c["live"] = False
            for lid, batch in list(st_c["leases"].items()):
                st_c["cut"].append(
                    (lid, {t.ticket_id: t.args * 10
                           for t in batch.tickets}))
                q.release(lid, client_failed=True)
            st_c["leases"].clear()
        elif op == "reconnect":
            st_c["live"] = True
            while st_c["cut"]:             # resume: replay cut submits
                lid, results = st_c["cut"].pop(0)
                accepted_total += q.submit_batch(lid, results, c)
    # drain: both clients reconnect and finish everything outstanding
    for c, st_c in clients.items():
        st_c["live"] = True
        while st_c["cut"]:
            lid, results = st_c["cut"].pop(0)
            accepted_total += q.submit_batch(lid, results, c)
        for lid, batch in list(st_c["leases"].items()):
            del st_c["leases"][lid]
            results = {t.ticket_id: t.args * 10 for t in batch.tickets}
            accepted_total += q.submit_batch(lid, results, c)
    while not q.all_done():
        clock.t += 0.1
        batch = q.lease("drain", 4)
        if batch is None:
            continue
        results = {t.ticket_id: t.args * 10 for t in batch.tickets}
        accepted_total += q.submit_batch(batch.lease_id, results, "drain")
    # exactly-once: across every submit (first, duplicate, replayed,
    # post-eviction) each ticket was accepted precisely one time
    assert accepted_total == n
    res = q.results()
    assert [res[t] for t in tids] == [i * 10 for i in range(n)]
    # and the span ledger balanced under eviction (test_obs invariant)
    assert tr.balanced(), tr.open_spans()
    assert tr.spans_opened == tr.spans_closed
