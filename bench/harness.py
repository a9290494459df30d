"""The benchmark harness, driven by data.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  The harness finds each by its name:

* the configuration at its ``file`` (``bench/configs/<config>.json``),
  with its plain reference ``bench/configs/<reference>.py`` beside it;
* the program module the configuration names,
  ``bench/programs/<program>.py``: everything about the model that the
  harness needs of the program (its config, its gradient task, how many
  rows a shard takes, the rows the reference follows, the counts, the
  gradient program's stable name);
* the traffic mix at ``bench/traffic/<traffic>.json``, read by the one
  generator in ``traffic.py``;
* the limits of the comparison at ``bench/checks/<workload>.json``;
* each per-layer metric's reader at ``bench/metrics/<metric>.py``.

A run is one process, as the deployment is: the ``TransportServer``, the
round loop and the remote clients share one event loop and the process
that holds the chip.  It makes the weights on the device and the
clients' rows from the seed, compiles the shapes the window drives,
drives the program's training loop through the three rounds the
comparison follows (``compare.py``), measures whole rounds for the
window, and only then runs the reference.
"""
from __future__ import annotations

import asyncio
import gc
import hashlib
import importlib.util
import json
import statistics
import sys
import tempfile
import time
from contextlib import contextmanager
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Optional

import numpy as np

import compare
import traffic as traffic_gen

BENCH = Path(__file__).resolve().parent
CHECKED_ROUNDS = 3
# server settings of the paper's deployment, the same in every mix
WATCHDOG_INTERVAL_S = 0.01
GRACE_S = 2.0
RECONNECT_DELAY_S = 0.02


@dataclass(frozen=True)
class Cell:
    """One workload with everything its name leads to."""

    name: str
    chips: int
    config: dict
    program: Any             # the module ``programs/<program>.py``
    pcfg: Any                # the program's own config of ``config``
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list
    config_dir: Path

    def reference(self):
        """The configuration's plain reference module."""
        path = self.config_dir / f"{self.config['reference']}.py"
        return _load_module("bench_reference", path)


def _load_module(kind: str, path: Path):
    """The module at ``path``, loaded once.  Its name in ``sys.modules``
    (which a pickled task's class is found by) holds ``kind``, the
    file's stem and a digest of its resolved path, so that two
    directories' files of the same name stay two modules."""
    path = Path(path).resolve()
    digest = hashlib.sha1(str(path).encode()).hexdigest()[:12]
    name = f"{kind}_{path.stem.replace('.', '_')}_{digest}"
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def program_module(name: str, bench_dir: Path = BENCH):
    """The program module ``programs/<name>.py`` under ``bench_dir``."""
    path = Path(bench_dir) / "programs" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no program module {name!r}: {path} "
                                f"does not exist")
    return _load_module("bench_program", path)


def _applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(spec: dict, workload: str, root: Path,
              bench_dir: Path = BENCH) -> Cell:
    """The cell ``workload`` of the benchmark ``spec``: configuration
    files resolve against ``root``, program modules, traffic mixes and
    limits under ``bench_dir``."""
    by_name = {w["name"]: w for w in spec["workloads"]}
    if workload not in by_name:
        raise KeyError(f"no workload {workload!r}; have {sorted(by_name)}")
    w = by_name[workload]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[w["config"]]
    cfg_path = Path(root) / cfg_entry["file"]
    config = json.loads(cfg_path.read_text())
    program = program_module(config["program"], bench_dir)
    mix = traffic_gen.load(Path(bench_dir) / "traffic" / f"{w['traffic']}.json")
    limits = json.loads((Path(bench_dir) / "checks" / f"{workload}.json")
                        .read_text())["limits"]
    missing = [k for k in compare.NUMBERS if k not in limits]
    if missing:
        raise KeyError(f"checks/{workload}.json lacks limits for {missing}")
    return Cell(name=workload, chips=w["chips"], config=config,
                program=program, pcfg=program.program_config(config),
                traffic=mix, limits=limits,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, workload)],
                per_layer=[m for m in spec["per_layer"]
                           if _applies(m, workload)],
                config_dir=cfg_path.parent)


# -- spans ------------------------------------------------------------------


class SpanLog:
    """Host spans of one run on the ``perf_counter`` clock, each also a
    ``jax.profiler.TraceAnnotation`` named ``bench.<name>``, so that a
    traced run shows what the host was doing on the profiler's own
    timeline."""

    def __init__(self):
        self.spans: dict[str, list[tuple[float, float]]] = {}

    @contextmanager
    def span(self, name: str):
        from jax.profiler import TraceAnnotation
        with TraceAnnotation(f"bench.{name}"):
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.spans.setdefault(name, []).append(
                    (t0, time.perf_counter()))

    def durations(self, name: str, lo: float, hi: float) -> list[float]:
        """Durations of the ``name`` spans that lie within [lo, hi]."""
        return [e - s for s, e in self.spans.get(name, ())
                if s >= lo and e <= hi]


# The remote clients receive the gradient task by pickle over the wire, so
# the task finds its run's SpanLog by key rather than holding it.
_SPAN_LOGS: dict[str, SpanLog] = {}


@dataclass(frozen=True)
class TimedGradShard:
    """The program's gradient task, inside a ``grad`` span."""

    inner: Any
    log_key: str

    def __call__(self, args, static):
        with _SPAN_LOGS[self.log_key].span("grad"):
            return self.inner(args, static)


def timed_server_step(inner, log: SpanLog):
    """The program's server step, inside a ``server_step`` span that ends
    when its new parameters are ready on the device."""
    import jax
    from repro.train_fabric import ServerStep

    class TimedServerStep(ServerStep):
        name = f"timed-{inner.name}"

        def step(self, grads, works, params, opt_state):
            with log.span("server_step"):
                return jax.block_until_ready(
                    inner.step(grads, works, params, opt_state))

    return TimedServerStep()


# -- the run ----------------------------------------------------------------


@dataclass
class Run:
    """What a run measured, for the end-to-end metrics and the readers."""

    cell: Cell
    device: dict
    setup_s: float
    window_s: float
    round_walls: list
    samples: int
    shards: int
    failed: int
    spans: dict              # span name -> durations within the window
    wire_bytes: int
    trace: Any = None        # trace_reduce.TraceSummary, traced runs only

    def peak(self, key: str) -> float:
        """The device kind's peak ``key`` from ``peaks.json``; an unknown
        kind is an error."""
        return load_peaks(self.device["kind"])[key]


def load_peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["kinds"]:
        raise KeyError(f"device kind {kind!r} is not in peaks.json "
                       f"({sorted(table['kinds'])})")
    return table["kinds"][kind]


class _CompileCounter:
    """Counts XLA compilations while armed."""

    def __init__(self):
        self.armed = False
        self.count = 0
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


async def _drive(cell: Cell, seed: int, seconds: float, log: SpanLog,
                 tracer, compiles: _CompileCounter, say) -> dict:
    import jax
    from repro.core.distributor import ClientProfile, TaskDef
    from repro.core.federation import FederatedDistributor
    from repro.core.split_parallel import TrainState
    from repro.core.transport import TransportServer, spawn_remote_clients
    from repro.optim import adagrad
    from repro.train_fabric import (FederatedTrainer, FederatedTrainingLoop,
                                    FusedServerStep)

    cfg, mix, program = cell.config, cell.traffic, cell.program
    batch, m = program.rows_per_shard(cfg), mix["shards_per_round"]
    ref = cell.reference()
    opt = adagrad(cfg["optimizer"]["lr"], beta=cfg["optimizer"]["beta"])
    params0 = ref.init_params(cfg, seed)
    params0_host = ref.as_host(params0)
    log_key = f"{cell.name}:{seed}:{id(log)}"
    _SPAN_LOGS[log_key] = log
    n_rows = traffic_gen.dataset_rows(mix, batch)
    task = TimedGradShard(program.grad_task(cfg, cell.pcfg, n_rows, seed),
                          log_key)
    fused = FusedServerStep(opt, lr=cfg["optimizer"]["lr"],
                            beta=cfg["optimizer"]["beta"],
                            mode=cfg["server_step"])
    if fused.mode != cfg["server_step"]:
        raise RuntimeError(f"server step resolved to {fused.mode!r}, the "
                           f"configuration states {cfg['server_step']!r}")
    works = [float(batch)] * m

    # compile the gradient at its batch and the server step at M before
    # the first round: a compile inside a round blocks the event loop
    first = task.inner(traffic_gen.round_shards(mix, batch, 0)[0],
                       {"weights": {"round": -1, "params": params0}})
    jax.block_until_ready(fused.step([first["grad"]] * m, works, params0,
                                     opt.init(params0)))
    del first

    fed = FederatedDistributor(
        mix["members"], n_shards=mix["queue_shards"],
        timeout=mix["lease_timeout_s"],
        redistribute_min=mix["redistribute_min_s"],
        watchdog_interval=WATCHDOG_INTERVAL_S, grace=GRACE_S,
        project_name="Bench")
    fed.register_task(TaskDef(program.TASK_NAME, task,
                              static_files=("weights",)))
    # a task that raises is reported and redistributed like a crashed
    # browser: count each report, and show the first
    errors: list[tuple[str, str]] = []
    queue_report = fed.queue.report_error

    def report_error(ticket_id, error, client="?"):
        if not errors:
            say(f"ticket error report from {client}:\n{error}")
        errors.append((client, error))
        queue_report(ticket_id, error, client)

    fed.queue.report_error = report_error
    server = TransportServer(fed)
    remote, client_tasks = [], []
    try:
        host, port = await server.start()
        remote, client_tasks = spawn_remote_clients(
            (host, port),
            [ClientProfile(name=f"c{i}", speed=mix["client_speed"])
             for i in range(mix["clients"])],
            reconnect_delay=RECONNECT_DELAY_S)
        state = TrainState(params=params0, head={}, head_stale={},
                           opt_state=opt.init(params0), head_opt_state={},
                           prev_features=(), prev_labels=(), prev_mask=(),
                           step=np.zeros((), np.int32))
        trainer = FederatedTrainer(
            fed, task_name=program.TASK_NAME, barrier_k=mix["barrier_k"],
            straggler_policy=mix["straggler_policy"],
            timeout=mix["round_timeout_s"])
        loop = FederatedTrainingLoop(
            trainer, opt, state,
            server_step=timed_server_step(fused, log))
        async with trainer:
            # the rounds the comparison follows: same loop, same feed
            for t in range(CHECKED_ROUNDS):
                await loop.run_round(
                    traffic_gen.round_shards(mix, batch, t), works)
                if t == 0:
                    acc1 = ref.as_host(loop.state.opt_state["acc"])
            program = compare.readings(
                loop.losses[:CHECKED_ROUNDS],
                compare.accumulator_grad(acc1), params0_host,
                ref.as_host(loop.state.params))
            del acc1
            wire0 = server.stats()
            errors0 = len(errors)
            if tracer is not None:
                tracer.start()
            compiles.armed = True
            walls, samples, shards, failed = [], 0, 0, 0
            t = CHECKED_ROUNDS
            t_w0 = time.perf_counter()
            with log.span("window"):
                while True:
                    r0 = time.perf_counter()
                    with log.span("round"):
                        res = await loop.run_round(
                            traffic_gen.round_shards(mix, batch, t), works)
                    r1 = time.perf_counter()
                    walls.append(r1 - r0)
                    samples += len(res.arrived) * batch
                    shards += len(res.ticket_ids)
                    failed += len(res.stragglers)
                    t += 1
                    if r1 - t_w0 >= seconds:
                        break
            t_w1 = r1
            compiles.armed = False
            dev = jax.devices()[0]
            peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
            if tracer is not None:
                tracer.stop()
            wire1 = server.stats()
            stale = loop.stale_executions
    finally:
        for c in remote:
            await c.stop()
        await asyncio.gather(*client_tasks, return_exceptions=True)
        await server.stop()
        await fed.shutdown()
        _SPAN_LOGS.pop(log_key, None)
    return {"program": program, "params0": params0_host, "stale": stale,
            "t_w0": t_w0, "t_w1": t_w1, "walls": walls, "samples": samples,
            "shards": shards, "failed": failed + len(errors) - errors0,
            "memory_peak_bytes": peak,
            "wire_bytes": (wire1["bytes_in"] + wire1["bytes_out"]
                           - wire0["bytes_in"] - wire0["bytes_out"])}


class _Tracer:
    """The profiler over the window, its trace read once it stops."""

    def __init__(self, out_dir: Path):
        self.out_dir = Path(out_dir)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(str(self.out_dir), profiler_options=opts)

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def path(self) -> Path:
        found = sorted(self.out_dir.glob("**/*.xplane.pb"))
        if not found:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        return found[-1]


def use_compile_cache() -> str:
    """Keep every compiled program, however quick its compile, in the
    program's persistent cache directory (``JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``.jax_cache/`` of the checkout), so that
    only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import use_compile_cache as use
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return use()


def _say(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, trace_dir: Optional[Path] = None, say=_say) -> dict:
    """One run of ``cell``: set-up, the window, the reference.  Returns
    the result line as a dict.  The caller has checked for the chips."""
    import jax

    compiles = _CompileCounter()
    log = SpanLog()
    with tempfile.TemporaryDirectory(prefix="bench-trace-") as tmp:
        tracer = (_Tracer(Path(trace_dir) if trace_dir else Path(tmp))
                  if trace else None)
        out = asyncio.run(_drive(cell, seed, seconds, log, tracer, compiles,
                                 say))
        t_read = time.perf_counter()
        grads = len(log.durations("grad", out["t_w0"], out["t_w1"]))
        say(f"set-up {out['t_w0'] - t_start:.3f} s, window "
            f"{out['t_w1'] - out['t_w0']:.3f} s of {len(out['walls'])} "
            f"rounds, {grads} gradients for {out['shards']} shards, "
            f"{compiles.count} compile(s) in the window, teardown "
            f"{t_read - out['t_w1']:.3f} s")
        summary = None
        if tracer is not None:
            import trace_reduce
            summary = trace_reduce.summarize(
                tracer.path(), chips=cell.chips, window="bench.window")
            say(f"trace read in {time.perf_counter() - t_read:.3f} s")
    gc.collect()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    lo, hi = out["t_w0"], out["t_w1"]
    result = Run(cell=cell, device=device, setup_s=lo - t_start,
                 window_s=hi - lo, round_walls=out["walls"],
                 samples=out["samples"], shards=out["shards"],
                 failed=out["failed"],
                 spans={name: log.durations(name, lo, hi)
                        for name in ("grad", "server_step", "round")},
                 wire_bytes=out["wire_bytes"], trace=summary)

    # the reference, after the window, the memory peak and the program's
    # state: it must set neither the peak nor the window's pace
    t_ref = time.perf_counter()
    reference = reference_readings(cell, seed, out["params0"])
    values = compare.numbers(out["program"], reference, out["stale"])
    say(f"reference in {time.perf_counter() - t_ref:.3f} s")
    return result_line(result, values, trace=trace)


def reference_readings(cell: Cell, seed: int, params0,
                       precision: str = "highest", half: bool = False
                       ) -> dict:
    """The plain reference over the checked rounds from ``params0``, the
    host copy of the seed's weights.  ``half`` leaves out the second half
    of each round's rows: the fault of a step that averages over half of
    its batch, planted in the reference."""
    ref = cell.reference()
    rows = cell.program.round_rows(cell.config, cell.traffic, seed,
                                   CHECKED_ROUNDS)
    if half:
        rows = [(x[:len(x) // 2], y[:len(y) // 2]) for x, y in rows]
    losses, g1, p3 = ref.train_rounds(cell.config, params0, rows,
                                      precision=precision)
    return compare.readings(losses, g1, params0, p3)


def end_to_end(run_: Run) -> dict[str, float]:
    """The end-to-end metrics, all taken on the host's clock."""
    values = {"samples_per_s": run_.samples / run_.window_s,
              "setup_s": run_.setup_s}
    walls = run_.round_walls
    if len(walls) >= 2:
        values["round_p90_s"] = statistics.quantiles(
            walls, n=10, method="inclusive")[8]
    return values


def read_metric(run_: Run, name: str):
    """The per-layer metric ``name``, from ``metrics/<name>.py``: its
    ``read(run)`` returns a number, or None where it finds nothing."""
    module = _load_module("bench_metric", BENCH / "metrics" / f"{name}.py")
    return module.read(run_)


def result_line(run_: Run, values: dict, *, trace: bool) -> dict:
    metrics = {}
    if trace:
        for m in run_.cell.per_layer:
            v = read_metric(run_, m["name"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        e2e = end_to_end(run_)
        for m in run_.cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    device = dict(run_.device)
    line = {"correct": compare.verdict(values, run_.cell.limits),
            "attempted": run_.shards, "failed": run_.failed,
            "metrics": metrics, "device": device}
    if trace and run_.trace is not None:
        device["busy_s"] = run_.trace.busy_s
        device["window_s"] = run_.trace.window_s
        line["breakdown"] = run_.trace.breakdown()
    line["checks"] = {k: {"value": values[k], "limit": run_.cell.limits[k]}
                      for k in compare.NUMBERS}
    return line


def emit(line: dict):
    """Print the compared numbers beside their limits as the last lines
    on standard error, and the result as the last line on standard
    output."""
    for k, c in line["checks"].items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(line), flush=True)
