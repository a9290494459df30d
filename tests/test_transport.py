"""Transport-layer tests: framing, codecs, the loopback server, remote
clients, reconnect-with-resume, and cache coherence over the wire.

Everything runs on real sockets (loopback, ephemeral ports) — these tests
exercise genuine serialization boundaries, not shared references, so they
use wall-clock time with generous deadlines and tiny simulated workloads.
"""
import asyncio
import threading
import time

import pytest

from repro.core.distributor import (AdaptiveSizer, AsyncDistributor,
                                    ClientProfile, Fetched, FixedSizer,
                                    HttpServerBase, TaskDef)
from repro.core.federation import FederatedDistributor
from repro.core.tickets import LeaseBatch, Ticket
from repro.core.transport import (PROTOCOL_VERSION, ProtocolError,
                                  RemoteBrowserClient, TransportServer,
                                  decode_payload, encode_frame,
                                  encode_payload, read_frame,
                                  spawn_remote_clients)
from repro.obs import Tracer, trace


# module-level so they pickle across the wire
def _square(x, static):
    return x * x


def _plus_bias(x, static):
    return x + static["bias"]


def _read_weights(x, static):
    return (x, static["weights"])


def _always_raise(x, static):
    raise RuntimeError("boom")


def _fed_dist(n_members=2, **kw):
    kw.setdefault("timeout", 10.0)
    kw.setdefault("redistribute_min", 0.02)
    kw.setdefault("sizer", AdaptiveSizer(target_lease_time=0.05, max_size=8))
    kw.setdefault("watchdog_interval", 0.01)
    return FederatedDistributor(n_members, **kw)


def _dist(**kw):
    kw.setdefault("timeout", 10.0)
    kw.setdefault("redistribute_min", 0.02)
    kw.setdefault("sizer", AdaptiveSizer(target_lease_time=0.05, max_size=8))
    kw.setdefault("watchdog_interval", 0.01)
    return AsyncDistributor(**kw)


def _feed_reader(*chunks: bytes) -> asyncio.StreamReader:
    reader = asyncio.StreamReader()
    for c in chunks:
        reader.feed_data(c)
    reader.feed_eof()
    return reader


# ---------------------------------------------------------------------------
# Framing
# ---------------------------------------------------------------------------


def test_frame_roundtrip():
    msg = {"type": "hello", "seq": 1, "client": "c0", "proto": 1}

    async def go():
        return await read_frame(_feed_reader(encode_frame(msg)))

    assert asyncio.run(go()) == msg


def test_frame_clean_eof_returns_none():
    async def go():
        return await read_frame(_feed_reader())

    assert asyncio.run(go()) is None


@pytest.mark.parametrize("raw", [
    b"\x00\x00\x00",                      # EOF inside the length header
    b"\x00\x00\x00\x10{\"type\"",         # EOF inside the body
])
def test_frame_truncated_raises_instead_of_hanging(raw):
    async def go():
        with pytest.raises(ProtocolError) as ei:
            await read_frame(_feed_reader(raw))
        return ei.value

    assert asyncio.run(go()).code == "truncated-frame"


def test_frame_oversized_rejected_without_reading_body():
    async def go():
        with pytest.raises(ProtocolError) as ei:
            await read_frame(_feed_reader(b"\xff\xff\xff\xff"),
                             max_bytes=1024)
        return ei.value

    assert asyncio.run(go()).code == "frame-too-large"


@pytest.mark.parametrize("body,code", [
    (b"this is not json!!", "bad-json"),
    (b"[1,2,3]", "bad-message"),          # JSON but not an object
    (b"{\"no\":\"type\"}", "bad-message"),
])
def test_frame_bad_body_rejected(body, code):
    import struct
    raw = struct.pack(">I", len(body)) + body

    async def go():
        with pytest.raises(ProtocolError) as ei:
            await read_frame(_feed_reader(raw))
        return ei.value

    assert asyncio.run(go()).code == code


# ---------------------------------------------------------------------------
# Wire codecs (the dataclass layer)
# ---------------------------------------------------------------------------


def test_ticket_wire_roundtrip_preserves_execution_fields():
    t = Ticket(7, "knn", {"lo": 0, "hi": 10}, created_at=123.4, work=2.5,
               distribute_count=3, last_distributed_at=200.0,
               lease_id=11, task_version=9)
    back = Ticket.from_wire(t.to_wire(encode_payload), decode_payload)
    assert (back.ticket_id, back.task_name, back.args, back.work,
            back.lease_id, back.task_version) == \
        (7, "knn", {"lo": 0, "hi": 10}, 2.5, 11, 9)
    # scheduling state is server-only and never crosses the wire
    assert back.created_at == 0.0 and back.distribute_count == 0


def test_lease_batch_wire_roundtrip():
    tickets = [Ticket(i, "t", i * 10, created_at=1.0, lease_id=5,
                      task_version=2) for i in range(3)]
    batch = LeaseBatch(5, "c0", tickets, issued_at=50.0,
                       expected_duration=1.5, shards=["server-only"])
    wire = batch.to_wire(encode_payload)
    assert "shards" not in wire and "issued_at" not in wire
    back = LeaseBatch.from_wire(wire, decode_payload)
    assert back.lease_id == 5 and back.client == "c0"
    assert [t.args for t in back.tickets] == [0, 10, 20]
    assert back.ticket_ids == [0, 1, 2]


def test_fetched_wire_roundtrip():
    got = Fetched({"w": [1, 2]}, 4, current=False)
    back = Fetched.from_wire(got.to_wire(encode_payload), decode_payload)
    assert (back.value, back.version, back.not_modified, back.current) == \
        ({"w": [1, 2]}, 4, False, False)
    nm = Fetched(None, 9, not_modified=True)
    wire = nm.to_wire(encode_payload)
    assert "payload" not in wire
    back = Fetched.from_wire(wire, decode_payload)
    assert back.not_modified and back.version == 9 and back.value is None


# ---------------------------------------------------------------------------
# Server robustness: garbage in, error frame out
# ---------------------------------------------------------------------------


async def _raw_conn(server):
    host, port = server.address
    return await asyncio.open_connection(host, port)


def test_malformed_frame_gets_error_reply_not_a_hung_reader():
    async def go():
        d = _dist()
        server = TransportServer(d)
        await server.start()
        try:
            reader, writer = await _raw_conn(server)
            import struct
            body = b"!!! not json at all"
            writer.write(struct.pack(">I", len(body)) + body)
            await writer.drain()
            reply = await asyncio.wait_for(read_frame(reader), timeout=5.0)
            writer.close()
            return reply, server.protocol_errors
        finally:
            await server.stop()

    reply, errors = asyncio.run(go())
    assert reply["type"] == "error" and reply["code"] == "bad-json"
    assert errors == 1


def test_truncated_frame_after_hello_gets_error_reply():
    async def go():
        d = _dist()
        server = TransportServer(d)
        await server.start()
        try:
            reader, writer = await _raw_conn(server)
            writer.write(encode_frame({"type": "hello", "seq": 1,
                                       "client": "raw",
                                       "proto": PROTOCOL_VERSION}))
            await writer.drain()
            hello = await asyncio.wait_for(read_frame(reader), timeout=5.0)
            # announce a 64-byte body but send only 3 bytes, then EOF
            writer.write(b"\x00\x00\x00\x40abc")
            writer.write_eof()
            reply = await asyncio.wait_for(read_frame(reader), timeout=5.0)
            writer.close()
            return hello, reply
        finally:
            await server.stop()

    hello, reply = asyncio.run(go())
    assert hello["type"] == "hello_ok"
    assert reply["type"] == "error" and reply["code"] == "truncated-frame"


def test_unknown_message_type_rejected_but_connection_survives():
    async def go():
        d = _dist()
        d.register_task(TaskDef("sq", _square))
        server = TransportServer(d)
        await server.start()
        try:
            reader, writer = await _raw_conn(server)
            writer.write(encode_frame({"type": "hello", "seq": 1,
                                       "client": "raw",
                                       "proto": PROTOCOL_VERSION}))
            writer.write(encode_frame({"type": "frobnicate", "seq": 2}))
            # a well-formed request AFTER the bad one must still be served
            writer.write(encode_frame({"type": "fetch_task", "seq": 3,
                                       "name": "sq"}))
            await writer.drain()
            replies = [await asyncio.wait_for(read_frame(reader),
                                              timeout=5.0)
                       for _ in range(3)]
            writer.close()
            return replies
        finally:
            await server.stop()

    hello, bad, fetched = asyncio.run(go())
    assert hello["type"] == "hello_ok"
    assert bad["type"] == "error" and bad["code"] == "bad-type"
    assert fetched["type"] == "task_data" and fetched["seq"] == 3
    assert decode_payload(fetched["payload"]).name == "sq"


def test_hello_with_no_alive_endpoint_gets_error_not_silent_close():
    async def go():
        fed = _fed_dist(2, n_shards=4)
        server = TransportServer(fed)
        await server.start()
        try:
            for i in range(2):             # every member dead
                await fed.kill_member(i)
            reader, writer = await _raw_conn(server)
            writer.write(encode_frame({"type": "hello", "seq": 1,
                                       "client": "late",
                                       "proto": PROTOCOL_VERSION}))
            await writer.drain()
            reply = await asyncio.wait_for(read_frame(reader), timeout=5.0)
            writer.close()
            return reply
        finally:
            await server.stop()

    reply = asyncio.run(go())
    assert reply["type"] == "error" and reply["code"] == "no-endpoint"
    assert reply["seq"] == 1


def test_server_error_with_null_seq_is_fatal_not_a_reconnect_loop():
    """A framing error is reported with seq=null; the client must raise
    ProtocolError instead of discarding the frame and re-dialing to send
    the identical doomed bytes max_reconnects times."""
    async def go():
        d = _dist()
        d.register_task(TaskDef("big", _big_result))
        d.add_work("big", [0])
        # the server refuses to READ frames over 512 bytes; the client's
        # submit (a ~3 KB pickled result) trips it
        server = TransportServer(d, max_frame_bytes=512)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)],
            reconnect_delay=0.01)
        done, _ = await asyncio.wait(tasks, timeout=10.0)
        assert done, "client hung instead of failing fast"
        exc = list(done)[0].exception()
        await d.shutdown()
        await server.stop()
        return exc, clients[0].reconnects

    exc, reconnects = asyncio.run(go())
    assert isinstance(exc, ProtocolError) and exc.code == "frame-too-large"
    assert reconnects == 0                 # fatal on first sight, no loop


def _big_result(x, static):
    return "x" * 2000


def test_proto_mismatch_refused():
    async def go():
        d = _dist()
        server = TransportServer(d)
        await server.start()
        try:
            reader, writer = await _raw_conn(server)
            writer.write(encode_frame({"type": "hello", "seq": 1,
                                       "client": "old", "proto": 999}))
            await writer.drain()
            reply = await asyncio.wait_for(read_frame(reader), timeout=5.0)
            writer.close()
            return reply
        finally:
            await server.stop()

    reply = asyncio.run(go())
    assert reply["type"] == "error" and reply["code"] == "proto-mismatch"


# ---------------------------------------------------------------------------
# End-to-end rounds over the socket
# ---------------------------------------------------------------------------


def test_remote_round_completes_and_results_match():
    async def go():
        d = _dist()
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", list(range(40)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0),
                   ClientProfile(name="r1", speed=100.0)])
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, d.queue.results(), tids, clients, d

    ok, res, tids, clients, d = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i * i for i in range(40)]
    # every ticket ran on a RemoteBrowserClient, zero in-process clients
    assert d.clients == []
    assert sum(c.executed for c in clients) >= 40
    # the adaptive sizer saw the remote clients' EWMA rates
    assert all(s.rate for s in d.queue.stats.values())


def test_remote_static_fetch_and_version_pins():
    async def go():
        d = _dist()
        d.add_static("bias", 5)
        d.register_task(TaskDef("pb", _plus_bias, static_files=("bias",)))
        tids = d.add_work("pb", list(range(20)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)])
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, d.queue.results(), tids

    ok, res, tids = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i + 5 for i in range(20)]


def test_remote_errors_reported_and_work_still_completes():
    async def go():
        d = _dist(grace=2.0)
        d.register_task(TaskDef("sq", _square))
        d.register_task(TaskDef("boom", _always_raise))
        sq_tids = d.add_work("sq", list(range(10)))
        boom_tid = d.add_work("boom", [0])[0]
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)])
        # the boom ticket can never complete; wait for the sq tickets,
        # for its first error report and for the client's reload after it
        deadline = asyncio.get_event_loop().time() + 30.0
        while (d.queue.results_for(sq_tids) is None
               or not d.queue._tickets[boom_tid].error_reports
               or clients[0].reloads < 1):
            assert asyncio.get_event_loop().time() < deadline, d.console()
            await asyncio.sleep(0.02)
        reports = []
        for tid in [boom_tid]:
            t = d.queue._tickets[tid]
            reports.extend(t.error_reports)
        for c in clients:
            await c.stop()
        await asyncio.gather(*tasks, return_exceptions=True)
        await d.shutdown()
        await server.stop()
        return d.queue.results_for(sq_tids), reports, clients[0]

    res, reports, client = asyncio.run(go())
    assert res == [i * i for i in range(10)]
    assert reports and "boom" in reports[0][1]       # traceback crossed wire
    assert client.errors >= 1 and client.reloads >= 1


def test_die_after_releases_lease_over_wire():
    async def go():
        d = _dist(grace=2.0)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", list(range(30)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="mortal", speed=200.0, die_after=1),
                   ClientProfile(name="survivor", speed=200.0)])
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, d.queue.results(), tids, clients

    ok, res, tids, clients = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i * i for i in range(30)]
    mortal = next(c for c in clients if c.profile.name == "mortal")
    assert mortal.done and mortal.leases_taken == 2   # died on its 2nd lease


# ---------------------------------------------------------------------------
# Conditional fetch parity with the in-process path
# ---------------------------------------------------------------------------


def test_versioned_fetch_not_modified_parity_with_inprocess():
    """A conditional fetch answered over the wire must be byte-for-byte
    the minimal not_modified frame, and decode to exactly the Fetched the
    in-process path returns."""
    async def go():
        d = _dist()
        d.add_static("w", [1, 2, 3])
        d.register_task(TaskDef("sq", _square, static_files=("w",)))
        v_task = d.tasks["sq"].version
        v_static = d.static_version("w")
        server = TransportServer(d)
        await server.start()
        try:
            reader, writer = await _raw_conn(server)
            writer.write(encode_frame({"type": "hello", "seq": 1,
                                       "client": "raw",
                                       "proto": PROTOCOL_VERSION}))
            writer.write(encode_frame({"type": "fetch_task", "seq": 2,
                                       "name": "sq", "if_version": v_task}))
            writer.write(encode_frame({"type": "fetch_static", "seq": 3,
                                       "key": "w", "if_version": v_static}))
            await writer.drain()
            await read_frame(reader)                       # hello_ok
            # capture the raw bytes of the task reply for the byte-level
            # comparison, then parse it
            import struct as _struct
            header = await reader.readexactly(4)
            (length,) = _struct.unpack(">I", header)
            body = header + await reader.readexactly(length)
            static_reply = await asyncio.wait_for(read_frame(reader),
                                                  timeout=5.0)
            writer.close()
            return d, v_task, v_static, body, static_reply
        finally:
            await server.stop()

    d, v_task, v_static, task_bytes, static_reply = asyncio.run(go())
    # byte-for-byte: the wire frame is exactly the canonical encoding of
    # the minimal not_modified message
    assert task_bytes == encode_frame({"type": "not_modified", "seq": 2,
                                       "version": v_task})
    assert static_reply == {"type": "not_modified", "seq": 3,
                            "version": v_static}
    # and the in-process path agrees field-for-field
    inproc = d.fetch_task_versioned("sq", if_version=v_task)
    assert inproc.not_modified and inproc.version == v_task
    inproc_s = d.serve_static_versioned("w", if_version=v_static)
    assert inproc_s.not_modified and inproc_s.version == v_static
    # both wire revalidations landed on the origin's revalidation ledger
    assert d.revalidation_count["task:sq"] >= 1
    assert d.revalidation_count["w"] >= 1


# ---------------------------------------------------------------------------
# Reconnect with resume
# ---------------------------------------------------------------------------


def test_reconnect_after_drop_completes_all_work():
    async def go():
        d = _dist(grace=2.0)
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", list(range(30)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=100.0)],
            reconnect_delay=0.02)
        await asyncio.sleep(0.1)           # let a lease get in flight
        assert server.drop_connections() == 1
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, d.queue.results(), tids, clients[0]

    ok, res, tids, client = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i * i for i in range(30)]
    assert client.reconnects >= 1


def test_reconnect_after_server_side_lease_expiry_releases_cleanly():
    """Connection dies mid-lease; the client's reconnect is slower than
    the watchdog, so the server releases the lease (the dead-client path)
    BEFORE the client comes back.  The reconnected client re-leases and
    the round still completes exactly."""
    async def go():
        d = _dist(grace=1.0,
                  sizer=AdaptiveSizer(target_lease_time=0.05, max_size=4))
        d.register_task(TaskDef("sq", _square))
        tids = d.add_work("sq", list(range(24)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=50.0)],
            reconnect_delay=0.5)           # reconnect slower than watchdog
        await asyncio.sleep(0.15)          # mid-lease
        server.drop_connections()
        # wait for the watchdog to actually release the orphaned lease
        deadline = asyncio.get_event_loop().time() + 10.0
        while d.queue.releases == 0:
            assert asyncio.get_event_loop().time() < deadline
            await asyncio.sleep(0.01)
        ok = await d.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, d.queue.results(), tids, clients[0], d.queue.releases

    ok, res, tids, client, releases = asyncio.run(go())
    assert ok
    assert [res[t] for t in tids] == [i * i for i in range(24)]
    assert releases >= 1                   # server-side expiry happened
    assert client.reconnects >= 1          # and the client came back


# ---------------------------------------------------------------------------
# Federation over the wire
# ---------------------------------------------------------------------------


def test_federation_over_transport_spreads_clients_and_serves_edges():
    async def go():
        fed = _fed_dist(2, n_shards=4)
        fed.add_static("bias", 7)
        fed.register_task(TaskDef("pb", _plus_bias, static_files=("bias",)))
        tids = fed.add_work("pb", list(range(40)))
        server = TransportServer(fed)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name=f"r{i}", speed=500.0)
                   for i in range(4)])
        ok = await fed.run_until_done(timeout=30.0)
        await asyncio.gather(*tasks)
        await server.stop()
        return ok, fed, tids, clients

    ok, fed, tids, clients = asyncio.run(go())
    assert ok
    res = fed.queue.results()
    assert [res[t] for t in tids] == [i + 7 for i in range(40)]
    # hello bound two clients to each member, least-connected
    assert sorted(c.member for c in clients) == [0, 0, 1, 1]
    # asset traffic went through the members' edges, not the origin:
    # the origin saw at most one cold miss per key per edge
    for key, count in fed.download_count.items():
        assert count <= len(fed.members), (key, count)
    edge_requests = sum(m.edge.stats()["requests"] for m in fed.members)
    assert edge_requests > 0


# ---------------------------------------------------------------------------
# Cache coherence across the serialization boundary
# ---------------------------------------------------------------------------


def test_reregister_storm_over_wire_zero_stale_serves():
    """The PR 3 storm, but with every client on the far side of a socket:
    weights re-registered each round, tickets pin the new coherence
    version, and no ticket may ever observe a stale weight."""
    async def go():
        d = _dist(keep_alive=True)
        d.add_static("weights", -1)
        d.register_task(TaskDef("rw", _read_weights,
                                static_files=("weights",)))
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name=f"c{i}", speed=2000.0)
                   for i in range(3)])
        stale = total = 0
        for rnd in range(8):
            d.add_static("weights", rnd)
            tids = d.add_work("rw", list(range(12)))
            deadline = asyncio.get_event_loop().time() + 30.0
            while True:
                wake = d._wake_event()
                out = d.queue.results_for(tids)
                if out is not None:
                    break
                assert asyncio.get_event_loop().time() < deadline, \
                    d.console()
                await d._wait_on(wake, 0.05)
            for _, w in out:
                total += 1
                stale += (w != rnd)
            d.queue.prune(tids)
        for c in clients:
            await c.stop()
        await asyncio.gather(*tasks, return_exceptions=True)
        await d.shutdown()
        await server.stop()
        return stale, total, clients

    stale, total, clients = asyncio.run(go())
    assert total == 8 * 12
    assert stale == 0
    # unchanged task code revalidated as counter bumps, not payloads
    assert sum(c.revalidations for c in clients) > 0
    # and the origin's push invalidations reached the remote caches
    assert sum(c.push_invalidations for c in clients) > 0


# ---------------------------------------------------------------------------
# Execution off the event loop
# ---------------------------------------------------------------------------

BLOCK_S = 0.4
# (arg, thread id, start, end) of each _block_and_log call, and the
# event loop's thread id and the clients' tracer, as each test sets them;
# the task reaches them by module, as it is pickled by reference
_EXEC_LOG: list = []
_LOOP: dict = {}


def _block_and_log(x, static):
    t0 = time.monotonic()
    time.sleep(BLOCK_S)
    _EXEC_LOG.append((x, threading.get_ident(), t0, time.monotonic()))
    return x


def _traced_probe(x, static):
    with trace.span("probe.block", cat="test", args={"x": x}):
        pass
    return {"current": trace.current() is _LOOP["tracer"],
            "off_loop": threading.get_ident() != _LOOP["thread"]}


def _raise_in_thread(x, static):
    if threading.get_ident() == _LOOP["thread"]:
        return "ran on the loop"
    raise RuntimeError("boom in the worker thread")


async def _leases_on_parked_clients(task, args, n_clients, *, lease_size=1,
                                    tracer=None):
    """Run ``args`` as tickets of ``task`` on ``n_clients`` remote
    clients that are already parked on a lease request, each lease
    ``lease_size`` tickets.  Returns the results."""
    _LOOP["thread"] = threading.get_ident()
    d = _dist(keep_alive=True, redistribute_min=10.0,
              sizer=FixedSizer(lease_size))
    d.register_task(TaskDef("t", task))
    server = TransportServer(d)
    addr = await server.start()
    clients, tasks = spawn_remote_clients(
        addr, [ClientProfile(name=f"r{i}", speed=0.0)
               for i in range(n_clients)], tracer=tracer)
    try:
        deadline = time.monotonic() + 30.0
        while (server.stats()["by_type"]["frames_in"]
               .get("lease_request", 0) < n_clients):
            assert time.monotonic() < deadline
            await asyncio.sleep(0.005)
        tids = d.add_work("t", args)
        while True:
            wake = d._wake_event()
            out = d.queue.results_for(tids)
            if out is not None:
                break
            assert time.monotonic() < deadline, d.console()
            await d._wait_on(wake, 0.05)
    finally:
        for c in clients:
            await c.stop()
        await asyncio.gather(*tasks, return_exceptions=True)
        await d.shutdown()
        await server.stop()
    return out


def test_tickets_of_two_clients_take_turns_on_one_worker_thread():
    _EXEC_LOG.clear()
    out = asyncio.run(_leases_on_parked_clients(_block_and_log, [0, 1], 2))
    assert out == [0, 1]
    (_, th0, _, e0), (_, th1, s1, _) = sorted(_EXEC_LOG, key=lambda r: r[2])
    # the process's one ticket worker ran both, not the loop's thread,
    # and the second began after the first ended
    assert th0 == th1 != _LOOP["thread"]
    assert e0 <= s1


def test_event_loop_keeps_ticking_while_a_task_blocks():
    ticks = []

    async def go():
        async def ticker():
            while True:
                ticks.append(time.monotonic())
                await asyncio.sleep(0.005)
        tick = asyncio.get_running_loop().create_task(ticker())
        try:
            return await _leases_on_parked_clients(_block_and_log, [7], 1)
        finally:
            tick.cancel()

    _EXEC_LOG.clear()
    assert asyncio.run(go()) == [7]
    (_, thread, t0, t1), = _EXEC_LOG
    assert thread != _LOOP["thread"]
    inside = [t for t in ticks if t0 <= t <= t1]
    # a task run on the loop would leave one gap of BLOCK_S
    assert len(inside) >= 5, len(inside)
    assert max(b - a for a, b in zip(inside, inside[1:])) < BLOCK_S / 2


def test_one_client_runs_its_lease_one_ticket_at_a_time_in_order():
    _EXEC_LOG.clear()
    tr = Tracer()
    out = asyncio.run(_leases_on_parked_clients(
        _block_and_log, [5, 3, 4], 1, lease_size=3, tracer=tr))
    assert out == [5, 3, 4]
    assert [r[0] for r in _EXEC_LOG] == [5, 3, 4]
    assert all(a[3] <= b[2] for a, b in zip(_EXEC_LOG, _EXEC_LOG[1:]))
    assert len({r[1] for r in _EXEC_LOG}) == 1
    assert _EXEC_LOG[0][1] != _LOOP["thread"]
    (span,) = [e for e in tr.events() if e["name"] == "client.execute"]
    assert span["args"]["tickets"] == 3 and span["args"]["executed"] == 3


def test_task_raising_in_the_worker_thread_is_reported_and_reloads():
    async def go():
        _LOOP["thread"] = threading.get_ident()
        d = _dist(grace=2.0)
        d.register_task(TaskDef("sq", _square))
        d.register_task(TaskDef("boom", _raise_in_thread))
        sq_tids = d.add_work("sq", list(range(6)))
        boom_tid = d.add_work("boom", [0])[0]
        server = TransportServer(d)
        addr = await server.start()
        clients, tasks = spawn_remote_clients(
            addr, [ClientProfile(name="r0", speed=500.0)])
        deadline = time.monotonic() + 30.0
        while (d.queue.results_for(sq_tids) is None
               or not d.queue._tickets[boom_tid].error_reports
               or clients[0].reloads < 1):
            assert time.monotonic() < deadline, d.console()
            await asyncio.sleep(0.02)
        reports = list(d.queue._tickets[boom_tid].error_reports)
        for c in clients:
            await c.stop()
        await asyncio.gather(*tasks, return_exceptions=True)
        await d.shutdown()
        await server.stop()
        return d.queue.results_for(sq_tids), reports, clients[0]

    res, reports, client = asyncio.run(go())
    assert res == [i * i for i in range(6)]
    # the traceback names the task's frame in the worker thread
    assert "boom in the worker thread" in reports[0][1]
    assert "_raise_in_thread" in reports[0][1]
    assert client.errors >= 1 and client.reloads >= 1


def test_task_on_the_worker_thread_records_on_the_clients_tracer():
    tr = Tracer()
    _LOOP["tracer"] = tr
    out = asyncio.run(_leases_on_parked_clients(
        _traced_probe, [1, 2], 2, tracer=tr))
    assert out == [{"current": True, "off_loop": True}] * 2
    probes = [e for e in tr.events() if e["name"] == "probe.block"]
    assert sorted(e["args"]["x"] for e in probes) == [1, 2]
    assert all(e.get("block") for e in probes)
    assert tr.balanced(), tr.open_spans()
