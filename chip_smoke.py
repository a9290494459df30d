#!/usr/bin/env python3
"""Smoke run of the training fabric's main path on one TPU chip.

Remote browser clients, connected to a ``TransportServer`` over loopback
sockets, compute paper Fig. 4 CNN gradients (609,258 parameters,
32x32x3 inputs, batch 50) on the device.  ``FederatedTrainingLoop``
closes each round through the K-of-N barrier and the fused Pallas server
step applies the paper's modified AdaGrad.  Every round the fused step is
checked against ``TreeServerStep`` on the same arrived gradients.

    python3 chip_smoke.py

Needs a TPU and exits non-zero without one.  Prints one round per line,
then, as its last line, ``{"ok": true, "device": {...}}``.  The numbers
are a smoke run's, not a benchmark's.
"""
from __future__ import annotations

import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.train_fabric import ServerStep  # noqa: E402

CLIENTS = 16          # remote clients, one 50-row shard each per round
ROUNDS = 4            # timed rounds, after one warm-up round
LR = 0.02
REL_BOUND = 1e-5      # fused vs tree: max |diff| <= REL_BOUND * max |theta|


class SmokeFailure(RuntimeError):
    """A phase of the smoke run failed."""


class CheckedServerStep(ServerStep):
    """The fused server step, checked each round against the tree
    reference on the same gradients, works, params and optimizer state.
    Records the fused step's time (ending in ``block_until_ready``) and
    the max abs difference of the new params."""

    name = "fused+tree-check"

    def __init__(self, fused, tree):
        self.fused, self.tree = fused, tree
        self.step_seconds: list[float] = []
        self.diffs: list[tuple[float, float]] = []   # (max |diff|, max |θ|)

    def step(self, grads, works, params, opt_state):
        t0 = time.perf_counter()
        new_params, new_state = jax.block_until_ready(
            self.fused.step(grads, works, params, opt_state))
        self.step_seconds.append(time.perf_counter() - t0)
        ref_params, _ = self.tree.step(grads, works, params, opt_state)
        leaves = jax.tree_util.tree_leaves
        diff = max(float(jnp.max(jnp.abs(a - b)))
                   for a, b in zip(leaves(new_params), leaves(ref_params)))
        scale = max(float(jnp.max(jnp.abs(a))) for a in leaves(ref_params))
        self.diffs.append((diff, scale))
        return new_params, new_state


async def _fabric_rounds(ccfg, *, clients: int, rounds: int, mode: str,
                         lr: float, seed: int, log) -> dict:
    from repro.core.distributor import ClientProfile, TaskDef
    from repro.core.federation import FederatedDistributor
    from repro.core.split_parallel import TrainState
    from repro.core.transport import TransportServer, spawn_remote_clients
    from repro.models import cnn
    from repro.optim import adagrad
    from repro.sharding.spec import values_tree
    from repro.train_fabric import (FederatedTrainer, FederatedTrainingLoop,
                                    FusedServerStep, TreeServerStep)

    rows = ccfg.batch_size
    task = cnn.CnnGradShard(ccfg, n_rows=clients * rows, seed=seed)
    args = [(i * rows, (i + 1) * rows) for i in range(clients)]
    work = [float(rows)] * clients
    opt = adagrad(lr)
    params = jax.device_get(
        values_tree(cnn.init_cnn(jax.random.PRNGKey(seed), ccfg)))
    checked = CheckedServerStep(FusedServerStep(opt, lr=lr, mode=mode),
                                TreeServerStep(opt))

    # compile the gradient, both server steps and their coefficients
    # before the first round: a compile inside a round blocks the event
    # loop and can push leases past the lease timeout
    opt_state = opt.init(params)
    t0 = time.perf_counter()
    out = task(args[0], {"weights": {"round": -1, "params": params}})
    grads = [out["grad"]] * clients
    for warm in (checked.fused, checked.tree):
        jax.block_until_ready(warm.step(grads, work, params, opt_state))
    log(f"compile_s: {time.perf_counter() - t0}")

    fed = FederatedDistributor(2, n_shards=4, timeout=20.0,
                               redistribute_min=0.02,
                               watchdog_interval=0.01, grace=2.0,
                               project_name="ChipSmoke")
    fed.register_task(TaskDef("cnn_grad_shard", task,
                              static_files=("weights",)))
    # a client that raises inside a task is treated as a crashed browser:
    # its ticket is reported and redistributed, so a device fault would
    # only show as a slow round.  Fail on the first report instead.
    reports: list[tuple[str, str]] = []
    first_report = asyncio.Event()
    queue_report = fed.queue.report_error

    def report_error(ticket_id, error, client="?"):
        reports.append((client, error))
        first_report.set()
        queue_report(ticket_id, error, client)

    fed.queue.report_error = report_error
    server = TransportServer(fed)
    host, port = await server.start()
    remote, client_tasks = spawn_remote_clients(
        (host, port),
        [ClientProfile(name=f"c{i}", speed=0.0) for i in range(clients)],
        reconnect_delay=0.02)
    state = TrainState(params=params, head={}, head_stale={},
                       opt_state=opt_state, head_opt_state={},
                       prev_features=(), prev_labels=(), prev_mask=(),
                       step=np.zeros((), np.int32))
    trainer = FederatedTrainer(fed, task_name="cnn_grad_shard",
                               barrier_k=0.75, straggler_policy="reticket",
                               timeout=120.0)
    loop = FederatedTrainingLoop(trainer, opt, state, server_step=checked)
    complete = []
    try:
        async with trainer:
            for r in range(rounds + 1):
                t0 = time.perf_counter()
                run = asyncio.ensure_future(loop.run_round(args, work))
                watch = asyncio.ensure_future(first_report.wait())
                await asyncio.wait({run, watch},
                                   return_when=asyncio.FIRST_COMPLETED)
                watch.cancel()
                if first_report.is_set():
                    run.cancel()
                    await asyncio.gather(run, return_exceptions=True)
                    client, error = reports[0]
                    raise SmokeFailure(f"round {r}: ticket error report "
                                       f"from {client}:\n{error}")
                res = run.result()
                wall = time.perf_counter() - t0
                complete.append(len(res.arrived) == len(args))
                diff, scale = checked.diffs[-1]
                log(f"round {r}{' (warm-up)' if r == 0 else ''}: "
                    f"loss {loop.losses[-1]} wall_s {wall} "
                    f"server_step_s {checked.step_seconds[-1]} "
                    f"fused_vs_tree {diff} bound {REL_BOUND * scale} "
                    f"arrived {len(res.arrived)}/{len(args)}")
    finally:
        for c in remote:
            await c.stop()
        await asyncio.gather(*client_tasks, return_exceptions=True)
        await server.stop()
        await fed.shutdown()
    return {"mode": checked.fused.mode, "losses": list(loop.losses),
            "diffs": checked.diffs, "complete": complete,
            "error_reports": len(reports),
            "stale_executions": loop.stale_executions}


def run_rounds(ccfg, *, clients: int, rounds: int, mode: str,
               lr: float = LR, seed: int = 0, log=print) -> dict:
    """One warm-up round and ``rounds`` timed rounds of ``ccfg`` through
    the fabric, with ``clients`` remote clients each computing one
    ``ccfg.batch_size``-row shard per round.  Raises
    :class:`SmokeFailure` on the first ticket error report."""
    return asyncio.run(_fabric_rounds(ccfg, clients=clients, rounds=rounds,
                                      mode=mode, lr=lr, seed=seed, log=log))


def failures(result: dict, *, rounds: int, mode: str) -> list[str]:
    """Every check of a :func:`run_rounds` result that failed."""
    out = []
    if result["mode"] != mode:
        out.append(f"server step resolved to {result['mode']!r}, "
                   f"not {mode!r}")
    if result["error_reports"]:
        out.append(f"{result['error_reports']} ticket error report(s)")
    if result["stale_executions"]:
        out.append(f"{result['stale_executions']} stale execution(s)")
    if len(result["losses"]) != rounds + 1 or not all(result["complete"]):
        out.append(f"rounds missing: {len(result['losses'])} of "
                   f"{rounds + 1} closed, complete {result['complete']}")
    for r, (diff, scale) in enumerate(result["diffs"]):
        if not diff <= REL_BOUND * scale:
            out.append(f"round {r}: fused vs tree differ by {diff}, "
                       f"over {REL_BOUND} x max|theta| = {REL_BOUND * scale}")
    losses = result["losses"]
    if not (losses and all(np.isfinite(losses)) and losses[-1] < losses[0]):
        out.append(f"loss did not fall: {losses}")
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              f"this smoke run needs one and does not fall back",
              file=sys.stderr)
        return 1
    from repro.configs.paper_cnn import FIG4_CNN
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"device: {json.dumps(device)}")
    result = run_rounds(FIG4_CNN, clients=CLIENTS, rounds=ROUNDS,
                        mode="pallas")
    print(f"server step mode: {result['mode']}")
    print(f"peak_bytes_in_use: "
          f"{(dev.memory_stats() or {}).get('peak_bytes_in_use')}")
    print(f"ticket error reports: {result['error_reports']} "
          f"stale executions: {result['stale_executions']}")
    failed = failures(result, rounds=ROUNDS, mode="pallas")
    for f in failed:
        print(f"chip_smoke: FAILED: {f}", file=sys.stderr)
    if failed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
