"""Benchmark harness: one entry per paper table/figure and the two
virtual-clock scheduler benchmarks.

Prints ``name,us_per_call,derived`` CSV rows (plus the detailed records) so
results are machine-comparable across runs.  Scaled-down sizes run inside a
CPU budget; pass --full for paper-scale settings.

The ``scheduler``, ``federation``, ``cache``, ``transport``,
``training``, ``server_step``, ``obs`` and ``churn`` entries
additionally write machine-readable ``BENCH_<name>.json`` files
(throughput, speedup, stale-serve, egress, loss-equivalence,
kernel-fusion and churn-resilience numbers) so the perf trajectory is
tracked across PRs — CI uploads them as artifacts.  ``--out-dir``
relocates them.

A benchmark that raises is reported with its full traceback and the run
exits nonzero; JSON files are written atomically (temp file + rename)
only after their benchmark's own assertions pass, so a failed run can
never leave a partial or stale-looking BENCH_*.json behind.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

OUT_DIR = "."
WRITTEN: dict = {}     # bench name -> BENCH_*.json filename, this run


def _write_json(name: str, payload: dict) -> str:
    """Atomically write BENCH_<name>.json (temp + rename): readers and CI
    artifact uploads can never observe a half-written file."""
    os.makedirs(OUT_DIR, exist_ok=True)
    fname = f"BENCH_{name}.json"
    path = os.path.join(OUT_DIR, fname)
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)
    WRITTEN[name] = fname
    print(f"  wrote {path}")
    return path


def write_summary(statuses: dict) -> str:
    """Consolidated ``BENCH_summary.json``: one entry per benchmark with
    its gate verdict and the BENCH_*.json it wrote (null when its gates
    failed before the write).  **Merges** with an existing summary in
    ``OUT_DIR`` — CI invokes the harness once per ``--only`` entry, and
    each invocation must extend the index, not erase the others'
    results.  Written atomically, like every BENCH file."""
    path = os.path.join(OUT_DIR, "BENCH_summary.json")
    benches: dict = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                benches = json.load(f).get("benches", {})
        except (OSError, ValueError):
            benches = {}          # corrupt summary: rebuild from here
    for name, status in statuses.items():
        benches[name] = {"ok": status["ok"],
                         "json": WRITTEN.get(name),
                         "error": status.get("error")}
    payload = {
        "benches": {k: benches[k] for k in sorted(benches)},
        "passed": sum(1 for b in benches.values() if b["ok"]),
        "failed": sum(1 for b in benches.values() if not b["ok"]),
    }
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2)
    os.replace(tmp, path)
    print(f"wrote {path} ({payload['passed']} pass / "
          f"{payload['failed']} fail across {len(benches)} indexed)")
    return path


def _csv(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}", flush=True)


def bench_table2(full: bool):
    from benchmarks import table2_knn

    kw = dict(n_train=20000, n_test=1000, image_size=28, tickets=50) \
        if full else {}
    t0 = time.perf_counter()
    rows = table2_knn.run(**kw)
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        print(f"  {r}")
    ratios = "|".join(str(r["ratio"]) for r in rows)
    _csv("table2_knn_scaling", us, f"elapsed_ratios={ratios}")
    return rows


def bench_table4(full: bool):
    from benchmarks import table4_speed

    t0 = time.perf_counter()
    rows = table4_speed.run(seconds=20.0 if full else 6.0)
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        print(f"  {r}")
    _csv("table4_sukiyaki_speedup", us,
         f"jit_over_eager={rows[-1]['batches_per_min']}x")
    return rows


def bench_fig3(full: bool):
    from benchmarks import fig3_convergence

    t0 = time.perf_counter()
    rows = fig3_convergence.run(batches=200 if full else 40)
    fabric = fig3_convergence.run_fabric(rounds=8 if full else 5)
    us = (time.perf_counter() - t0) * 1e6
    last = {r["optimizer"]: r["error_rate"] for r in rows}
    for r in rows:
        print(f"  {r}")
    print(f"  fabric: {fabric}")
    _csv("fig3_convergence", us,
         f"final_err={last}|"
         f"fabric_delta={fabric['max_loss_delta_vs_in_process']:.1e}")
    return rows


def bench_fig5(full: bool):
    from benchmarks import fig5_split

    t0 = time.perf_counter()
    rows = fig5_split.run(seconds=12.0 if full else 5.0, max_clients=4)
    us = (time.perf_counter() - t0) * 1e6
    for r in rows:
        print(f"  {r}")
    conv = [r["conv_batches_per_min"] for r in rows]
    _csv("fig5_split_scaling", us, f"conv_bpm={conv}")
    return rows


def bench_scheduler(full: bool):
    """Distributor v2 policy sweep (virtual clock, deterministic); writes
    BENCH_scheduler.json with the per-mix makespans and the adaptive-vs-v1
    speedup on the bimodal mix."""
    from benchmarks import scheduler_throughput

    t0 = time.perf_counter()
    results = scheduler_throughput.run_sweep()
    us = (time.perf_counter() - t0) * 1e6
    bi = results["bimodal"]
    speedup = round(bi["v1-fixed-1"]["makespan_s"]
                    / bi["adaptive"]["makespan_s"], 2)
    payload = {
        "results": results,
        "speedup_adaptive_v_fixed1_bimodal": speedup,
        "client_mix": {"clients": scheduler_throughput.N_CLIENTS,
                       "tickets": scheduler_throughput.N_TICKETS,
                       "base_rate": scheduler_throughput.BASE_RATE,
                       "rtt_s": scheduler_throughput.RTT},
    }
    _write_json("scheduler", payload)
    _csv("scheduler_policies", us, f"adaptive_speedup={speedup}x")
    return results


def bench_federation(full: bool):
    """Federation fabric sweep (virtual clock, deterministic); writes
    BENCH_federation.json with per-member-count throughput, the 4v1
    speedup, and the member-death recovery cell."""
    from benchmarks import federation_throughput

    t0 = time.perf_counter()
    results = federation_throughput.run_sweep(
        n_tickets=600 if full else 200)
    us = (time.perf_counter() - t0) * 1e6
    _write_json("federation", results)
    death = results["bimodal+death"]["fed-4-kill-m0"]
    _csv("federation_throughput", us,
         f"speedup_4v1={results['speedup_4v1_bimodal']}x|"
         f"death_completed={death['completed']}/{death['total']}")
    return results


def bench_cache(full: bool):
    """Cache-coherence storm (virtual clock, deterministic); writes
    BENCH_cache.json with per-strategy stale-serve counts and the egress
    saved by versioned invalidation vs clear()-everything."""
    from benchmarks import cache_coherence

    t0 = time.perf_counter()
    results = cache_coherence.run_sweep()
    us = (time.perf_counter() - t0) * 1e6
    v = results["versioned"]
    # assert BEFORE writing: a failed coherence bar must not leave a
    # fresh-looking BENCH_cache.json behind
    assert v["stale_serves"] == 0, v
    _write_json("cache", results)
    _csv("cache_coherence", us,
         f"stale_serves={v['stale_serves']}|"
         f"egress_saved_vs_clear={results['egress_saved_vs_clear_pct']}%")
    return results


def bench_transport(full: bool):
    """Wire-protocol overhead (real loopback sockets, wall clock); writes
    BENCH_transport.json with serialized-vs-in-process round throughput,
    the wire byte ledger, and the over-the-wire re-register storm."""
    from benchmarks import transport_overhead

    t0 = time.perf_counter()
    results = transport_overhead.run_sweep()
    us = (time.perf_counter() - t0) * 1e6
    # acceptance bars first (see transport_overhead.main): coherence
    # survives serialization; wire costs <= half the round throughput
    assert results["storm"]["stale_serves"] == 0, results["storm"]
    assert results["throughput_ratio"] >= 0.5, results
    _write_json("transport", results)
    _csv("transport_overhead", us,
         f"throughput_ratio={results['throughput_ratio']}x|"
         f"storm_stale={results['storm']['stale_serves']}")
    return results


def bench_training(full: bool):
    """Training-fabric sweep (virtual-clock throughput sim + real asyncio
    trainer cells); writes BENCH_training.json with the 4v1 round-
    throughput speedup, loss-equivalence deltas, fault-tolerance
    counters, and the kill/resume reproduction delta."""
    from benchmarks import federated_training

    t0 = time.perf_counter()
    results = federated_training.run_sweep(smoke=not full)
    us = (time.perf_counter() - t0) * 1e6
    # acceptance bars BEFORE writing (a failed bar must not leave a
    # fresh-looking BENCH_training.json behind)
    federated_training.check(results)
    _write_json("training", results)
    _csv("federated_training", us,
         f"speedup_4v1_rounds={results['throughput']['speedup_4v1_rounds']}x|"
         f"equiv_delta={results['equivalence']['max_loss_delta']:.1e}|"
         f"resume_delta={results['resume']['max_loss_delta']:.1e}")
    return results


def bench_server_step(full: bool):
    """Fused server-step kernel vs the seed's unfused tree_map pipeline
    (wall clock); writes BENCH_server_step.json with the three medians
    and the fused/baseline ratio, gated against the checked-in
    benchmarks/baselines/server_step_baseline.json with x1.2 headroom
    (plus the interpret-mode bit-equivalence bar)."""
    from benchmarks import server_step_fusion

    t0 = time.perf_counter()
    results = server_step_fusion.run(trials=50 if full else 20)
    us = (time.perf_counter() - t0) * 1e6
    # acceptance bars BEFORE writing (a regressed ratio must not leave a
    # fresh-looking BENCH_server_step.json behind)
    server_step_fusion.check(results)
    _write_json("server_step", results)
    _csv("server_step_fusion", us,
         f"fused_over_tree={results['fused_over_tree_ratio']}|"
         f"mode={results['fused_mode']}")
    return results


def bench_obs(full: bool):
    """Observability layer: trace determinism, span balance, the
    tracing-overhead gate, fleet-export determinism, and the SLO gate
    (which must trip on an injected regression — a gate that cannot
    fail is not a gate); writes BENCH_obs.json."""
    import sys as _sys
    if "src" not in _sys.path:
        _sys.path.insert(0, "src")
    from benchmarks import scheduler_throughput
    from repro.obs import (DEFAULT_ROUND_SLOS, FleetAggregator,
                           MetricsRegistry, SloMonitor, Tracer,
                           collect_queue)

    t0 = time.perf_counter()
    # determinism: two same-seed virtual-clock runs must serialize to
    # byte-identical Perfetto JSON (the tracer never reads wall time)
    sizer, watchdog = scheduler_throughput.POLICIES["adaptive"]
    traces = []
    for _ in range(2):
        tr = Tracer()
        scheduler_throughput.simulate("churn", sizer, watchdog=watchdog,
                                      tracer=tr)
        assert tr.balanced(), tr.open_spans()
        traces.append(tr.to_json())
    assert traces[0] == traces[1], "same-seed traces differ"
    events = traces[0].count('"ph"')

    # metrics registry absorbs a live queue snapshot without error
    reg = MetricsRegistry()
    clock = scheduler_throughput.SimClock()
    from repro.core.tickets import TicketQueue
    q = TicketQueue(timeout=300.0, clock=clock)
    q.add_many("work", list(range(16)))
    collect_queue(reg, q)
    assert reg.get("queue.tickets_count").value() == 16, reg.snapshot()

    # fleet-export determinism: two identically-fed aggregators (same
    # synthetic remote batch, same skew sample) must serialize the
    # merged skew-corrected timeline byte-identically
    batch = {"metrics": {"client.executed_total": {
                 "kind": "counter", "help": "Tickets executed",
                 "values": [{"labels": {}, "value": 7}]}},
             "spans": [{"ph": "X", "name": "client.execute",
                        "cat": "client", "track": "client:tab-0",
                        "ts": 3.0, "dur": 0.5, "args": {}}],
             "dropped": 0, "local_drops": 0}
    fleet_json = []
    for _ in range(2):
        fl = FleetAggregator()
        fl.clock_sample("tab-0", offset=2.5, rtt=0.01)
        assert fl.ingest("tab-0", dict(batch)), "synthetic batch refused"
        fleet_json.append(fl.to_json())
    assert fleet_json[0] == fleet_json[1], "fleet exports differ"
    remote_ts = json.loads(fleet_json[0])["traceEvents"]
    corrected = [e for e in remote_ts if e["name"] == "client.execute"]
    assert corrected and corrected[0]["ts"] == 5.5e6, corrected  # 3.0+2.5 s→us

    # SLO gate: clean registry passes; an injected latency regression
    # (rounds past the histogram's 60 s edge) MUST trip it
    def slo_eval(durations):
        reg2 = MetricsRegistry()
        h = reg2.histogram("round.duration_seconds",
                           "Virtual-clock duration of each closed round")
        for d in durations:
            h.observe(d)
        mon = SloMonitor(reg2, DEFAULT_ROUND_SLOS)
        results = mon.evaluate()
        return results, mon
    clean, _ = slo_eval([0.4, 0.6, 0.8, 1.2])
    assert all(r.ok for r in clean), [r.as_dict() for r in clean]
    regressed, mon = slo_eval([0.4, 0.6] + [120.0] * 18)
    tripped = [r for r in regressed if not r.ok]
    assert tripped and mon.breaches_total > 0, \
        "injected regression did NOT trip the SLO gate"
    assert {r.slo.name for r in tripped} == {"round-latency-p95"}, tripped

    gate = scheduler_throughput.overhead_gate()
    us = (time.perf_counter() - t0) * 1e6
    # acceptance bars BEFORE writing (a failed gate must not leave a
    # fresh-looking BENCH_obs.json behind)
    assert gate["ok"], gate
    payload = {"determinism": {"runs": 2, "identical": True,
                               "events": events},
               "fleet_determinism": {"runs": 2, "identical": True},
               "slo_gate": {"clean_ok": True, "regression_tripped": True,
                            "tripped": [r.as_dict() for r in tripped]},
               "overhead": gate,
               "metric_series": len(reg.names())}
    _write_json("obs", payload)
    _csv("obs_layer", us,
         f"overhead_ratio={gate['ratio']}x|trace_events={events}|"
         f"slo_gate=trips_on_regression")
    return payload


def bench_churn(full: bool):
    """Browser-scale churn sim (virtual clock, deterministic): 10k
    clients (1k without --full) at 20%/round churn under admission
    control + heartbeat eviction; writes BENCH_churn.json gated on zero
    stalled rounds, zero lost/duplicated tickets, and churned throughput
    >= 0.9x the no-churn ceiling."""
    from benchmarks import churn_scale

    t0 = time.perf_counter()
    results = churn_scale.run_sweep(
        population=churn_scale.POPULATION if full
        else churn_scale.SMOKE_POPULATION)
    us = (time.perf_counter() - t0) * 1e6
    # acceptance bars BEFORE writing (a stalled or lossy run must not
    # leave a fresh-looking BENCH_churn.json behind)
    churn_scale.check(results)
    _write_json("churn", results)
    ch = results["churned"]
    _csv("churn_scale", us,
         f"ratio_vs_ceiling={results['throughput_ratio_vs_ceiling']}|"
         f"stalled={ch['stalled_rounds']}|lost={ch['lost_tickets']}|"
         f"dup={ch['duplicate_completions']}|"
         f"speedup_4v1={results['speedup_4v1']}x")
    return results


BENCHES = {
    "table2": bench_table2,
    "table4": bench_table4,
    "fig3": bench_fig3,
    "fig5": bench_fig5,
    "scheduler": bench_scheduler,
    "federation": bench_federation,
    "cache": bench_cache,
    "transport": bench_transport,
    "training": bench_training,
    "server_step": bench_server_step,
    "obs": bench_obs,
    "churn": bench_churn,
}


def main() -> None:
    global OUT_DIR
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, choices=list(BENCHES))
    ap.add_argument("--full", action="store_true",
                    help="paper-scale sizes (slow on CPU)")
    ap.add_argument("--out-dir", default=".",
                    help="where BENCH_*.json files land")
    args = ap.parse_args()
    OUT_DIR = args.out_dir
    print("name,us_per_call,derived")
    names = [args.only] if args.only else list(BENCHES)
    failures = 0
    statuses: dict = {}
    for name in names:
        print(f"== {name} ==", flush=True)
        try:
            BENCHES[name](args.full)
            statuses[name] = {"ok": True}
        except Exception as e:
            # keep the harness going so one broken benchmark doesn't hide
            # the others' results, but fail LOUDLY: full traceback now,
            # nonzero exit at the end (no BENCH json is written for a
            # failed entry — _write_json runs after a bench's assertions)
            failures += 1
            statuses[name] = {"ok": False,
                              "error": f"{type(e).__name__}: {e}"[:500]}
            print(f"  FAILED: {name}")
            traceback.print_exc()
    write_summary(statuses)
    if failures:
        print(f"{failures} benchmark(s) failed", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
