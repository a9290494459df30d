"""Plain float32 reference of the tiny MLP and of its federated rounds:
dense, ReLU, dense, softmax cross-entropy averaged over a round's rows,
and the paper's modified AdaGrad ``acc += g**2; theta -= lr * g /
sqrt(beta + acc)``."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(cfg: dict, seed: int) -> dict:
    """normal(0, 1) / sqrt(fan_in) weights and zero biases from ``seed``,
    made on the device in one jitted call."""
    i, h, c = cfg["in_dim"], cfg["hidden"], cfg["classes"]

    @jax.jit
    def make(key):
        k1, k2 = jax.random.split(key)
        return {"w1": jax.random.normal(k1, (i, h)) / math.sqrt(i),
                "b1": jnp.zeros((h,)),
                "w2": jax.random.normal(k2, (h, c)) / math.sqrt(h),
                "b2": jnp.zeros((c,))}

    return make(jax.random.PRNGKey(seed))


def _loss(p, x, y):
    h = jax.nn.relu(jnp.dot(x, p["w1"], precision=HIGHEST) + p["b1"])
    logits = jnp.dot(h, p["w2"], precision=HIGHEST) + p["b2"]
    logp = jax.nn.log_softmax(logits, -1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=1))


def train_rounds(cfg: dict, params0, rounds, *, precision: str = "highest"):
    """Each round's loss before its update, the first round's gradient
    and the parameters after the last update."""
    if precision != "highest":
        raise ValueError(f"only 'highest', got {precision!r}")
    lr, beta = cfg["optimizer"]["lr"], cfg["optimizer"]["beta"]
    params = jax.tree_util.tree_map(jnp.asarray, params0)
    acc = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for x, y in rounds:
        loss, grad = jax.value_and_grad(_loss)(params, jnp.asarray(x),
                                               jnp.asarray(y))
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.device_get(grad)
        acc = jax.tree_util.tree_map(lambda a, g: a + g * g, acc, grad)
        params = jax.tree_util.tree_map(
            lambda p, g, a: p - lr * g / jnp.sqrt(beta + a), params, grad,
            acc)
    return losses, first_grad, jax.device_get(params)


def as_host(tree):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), jax.device_get(tree))
