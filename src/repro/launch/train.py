"""Training driver: real end-to-end training on whatever devices exist.

On this CPU host it trains reduced configs (the same code path that targets
the production mesh); on a TPU fleet the identical script drives the
16x16(x2) meshes via --mesh.

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --smoke \
      --steps 50 --strategy split_concurrent
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import INPUT_SHAPES, InputShape, RunConfig, \
    get_arch_config, get_smoke_config
from repro.core.split_parallel import init_prev_features, make_train_step
from repro.data import TicketDataLoader, make_lm_batch
from repro.data.synthetic import InlineWorker
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import arch_for_run, make_rules
from repro.models.model import build_model
from repro.optim import get_optimizer
from repro.sharding.spec import ShardCtx, use_shard_ctx


def train_loop(cfg, run: RunConfig, *, steps: int, batch: int, seq: int,
               mesh=None, log_every: int = 10, checkpoint_path=None):
    """Train ``cfg`` for ``steps`` on synthetic LM batches with the
    strategy named in ``run``; returns the per-step metric history."""
    compute_dtype = jnp.dtype(run.compute_dtype)
    shape = InputShape("custom", seq, batch, "train")
    cfg = arch_for_run(cfg, shape, run.strategy)
    api = build_model(cfg, compute_dtype=compute_dtype, remat=run.remat)
    opt = get_optimizer(run.optimizer, run.learning_rate,
                        adagrad_beta=run.adagrad_beta,
                        weight_decay=run.weight_decay)
    init_state, step_fn = make_train_step(
        api, opt, strategy=run.strategy,
        head_sync_period=run.head_sync_period)

    rng = np.random.default_rng(run.seed)
    loader = TicketDataLoader(
        lambda step, i: make_lm_batch(rng, batch // run.microbatch_per_ticket
                                      if run.microbatch_per_ticket > 1
                                      else batch, seq, cfg.vocab_size),
        num_microbatches=1)
    ctx = None
    if mesh is not None:
        rules = make_rules(run.strategy, mesh, shape)
        ctx = ShardCtx(mesh, rules)

    with use_shard_ctx(ctx):
        state = init_state(jax.random.PRNGKey(run.seed))
        first = loader.global_batch(0, [InlineWorker()])
        first = {k: jnp.asarray(v) for k, v in first.items()}
        if run.strategy in ("split_concurrent", "split_server_sharded"):
            state = init_prev_features(state, api, first,
                                       dtype=compute_dtype)
        jstep = jax.jit(step_fn, donate_argnums=(0,))
        losses = []
        t0 = time.time()
        for i in range(steps):
            b = first if i == 0 else {
                k: jnp.asarray(v) for k, v in loader.global_batch(
                    i, [InlineWorker()]).items()}
            state, metrics = jstep(state, b)
            loss = float(metrics["loss"])
            losses.append(loss)
            if i % log_every == 0 or i == steps - 1:
                dt = time.time() - t0
                print(f"step {i:5d} loss {loss:.4f} "
                      f"({dt/(i+1):.3f}s/step)", flush=True)
    if checkpoint_path:
        from repro.checkpoint import save_npz
        from repro.core.split_parallel import merge_params
        save_npz(checkpoint_path, merge_params(
            jax.tree_util.tree_map(np.asarray, state.params),
            jax.tree_util.tree_map(np.asarray, state.head)))
        print(f"checkpoint -> {checkpoint_path}")
    return losses, state


def main():
    """CLI entry: train an arch config with a chosen strategy/optimizer."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--strategy", default="split_concurrent")
    ap.add_argument("--optimizer", default="adagrad")
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--adagrad-beta", type=float, default=1.0)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--compute-dtype", default="float32")
    ap.add_argument("--checkpoint", default=None)
    args = ap.parse_args()

    use_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_arch_config(args.arch))
    run = RunConfig(arch=args.arch, strategy=args.strategy,
                    optimizer=args.optimizer, learning_rate=args.lr,
                    adagrad_beta=args.adagrad_beta,
                    compute_dtype=args.compute_dtype)
    mesh = None
    if args.data_par * args.model_par > 1:
        mesh = make_local_mesh(args.data_par, args.model_par)
    losses, _ = train_loop(cfg, run, steps=args.steps, batch=args.batch,
                           seq=args.seq, mesh=mesh,
                           checkpoint_path=args.checkpoint)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")


if __name__ == "__main__":
    main()
