"""A two-layer MLP as a program module, for the CPU tests: what a
configuration of a model other than the paper CNN brings as new files.

The rows are seeded random vectors with random class labels.  The
gradient task is picklable (the remote clients receive it over the
wire) and keeps its rows in a per-process cache, so that only its sizes
and seed cross the wire.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import numpy as np

import traffic

GRAD_PROGRAM = "tiny_mlp_loss_and_grads"
TASK_NAME = "tiny_mlp_grad_shard"


def program_config(cfg: dict) -> tuple[int, int, int]:
    return cfg["in_dim"], cfg["hidden"], cfg["classes"]


def rows_per_shard(cfg: dict) -> int:
    return cfg["batch_size"]


def vectors(n: int, in_dim: int, classes: int, seed: int):
    rng = np.random.default_rng(seed)
    x = rng.normal(0.0, 1.0, (n, in_dim)).astype(np.float32)
    return x, rng.integers(0, classes, size=n).astype(np.int32)


@functools.lru_cache(maxsize=None)
def _rows(pcfg: tuple, n_rows: int, seed: int):
    return vectors(n_rows, pcfg[0], pcfg[2], seed)


@functools.lru_cache(maxsize=None)
def _loss_and_grads():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def tiny_mlp_loss_and_grads(params, x, y):
        def loss(p):
            h = jax.nn.relu(x @ p["w1"] + p["b1"])
            logp = jax.nn.log_softmax(h @ p["w2"] + p["b2"], -1)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))
        return jax.value_and_grad(loss)(params)

    return tiny_mlp_loss_and_grads


@dataclass(frozen=True)
class MlpGradShard:
    """Loss and gradients of the rows ``args = (lo, hi)`` against the
    round's published weights."""

    pcfg: tuple
    n_rows: int
    seed: int

    def __call__(self, args, static):
        import jax
        lo, hi = args
        x, y = _rows(self.pcfg, self.n_rows, self.seed)
        served = static["weights"]
        loss, grads = _loss_and_grads()(served["params"], x[lo:hi],
                                        y[lo:hi])
        return {"grad": jax.device_get(grads), "loss": float(loss),
                "round": served.get("round", -1)}


def grad_task(cfg: dict, pcfg, n_rows: int, seed: int) -> MlpGradShard:
    return MlpGradShard(pcfg, n_rows, seed)


def round_rows(cfg: dict, mix: dict, seed: int, rounds: int):
    batch = rows_per_shard(cfg)
    x, y = vectors(traffic.dataset_rows(mix, batch), cfg["in_dim"],
                   cfg["classes"], seed)
    out = []
    for t in range(rounds):
        shards = traffic.round_shards(mix, batch, t)
        lo, hi = shards[0][0], shards[-1][1]
        out.append((x[lo:hi], y[lo:hi]))
    return out


def param_count(cfg: dict) -> int:
    i, h, c = program_config(cfg)
    return i * h + h + h * c + c


def forward_flops_per_sample(cfg: dict) -> int:
    i, h, c = program_config(cfg)
    return 2 * (i * h + h * c)


def train_flops_per_sample(cfg: dict) -> int:
    """The forward pass, both layers' weight gradients and the second
    layer's input gradient."""
    i, h, c = program_config(cfg)
    return 2 * forward_flops_per_sample(cfg) + 2 * h * c
