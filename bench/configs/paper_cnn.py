"""Plain reference of the paper's CNN family (arXiv:1503.05743, Figs. 2
and 4) and of its federated training rounds.

Written from the configuration's sizes in straightforward ``jax.numpy``
float32, imports nothing of the program under test, and takes nothing
that it made.  The network: ``convs`` of 5x5 "SAME" convolutions, each
followed by ReLU and a 2x2 max-pool, then dense layers ``fc_hidden``
with ReLU and a final dense layer to ``num_classes``, softmax
cross-entropy averaged over the rows.  A round averages the gradient
over all its rows (its shards are of equal size and weighted by their
rows) and applies the paper's modified AdaGrad
``acc += g**2; theta -= lr * g / sqrt(beta + acc)``.

``precision`` chooses how each convolution and dense layer multiplies:

* ``"highest"``: float32 operands, float32 products and sums (the
  reference);
* ``"fp8"``: the control, one step below the bfloat16 operands that the
  configuration states: every operand of every convolution and dense
  layer, in the forward and the backward pass, is rounded to float8
  e4m3 under a per-tensor scale, then multiplied and summed in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
PRECISIONS = ("highest", "fp8")
_E4M3_MAX = 448.0


def _shapes(cfg: dict) -> list[tuple[str, tuple, int]]:
    """(group, weight shape, fan-in) of every layer, in network order."""
    out = []
    size, cin = cfg["image_size"], cfg["in_channels"]
    for conv in cfg["convs"]:
        k, cout = conv["kernel"], conv["out_channels"]
        out.append(("convs", (k, k, cin, cout), k * k * cin))
        size //= conv["pool"]
        cin = cout
    dims = [size * size * cin, *cfg["fc_hidden"], cfg["num_classes"]]
    for d_in, d_out in zip(dims, dims[1:]):
        out.append(("fc", (d_in, d_out), d_in))
    return out


def init_params(cfg: dict, seed: int) -> dict:
    """The weights from ``seed``, made on the device in one jitted call:
    normal(0, 1) / sqrt(fan_in) weights and zero biases."""
    shapes = _shapes(cfg)

    @jax.jit
    def make(key):
        keys = jax.random.split(key, len(shapes))
        tree = {"convs": [], "fc": []}
        for k, (group, shape, fan_in) in zip(keys, shapes):
            tree[group].append({
                "w": jax.random.normal(k, shape, jnp.float32)
                / math.sqrt(fan_in),
                "b": jnp.zeros((shape[-1],), jnp.float32)})
        return tree

    return make(jax.random.PRNGKey(seed))


def _round_fp8(x):
    scale = jnp.max(jnp.abs(x)) / _E4M3_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


@jax.custom_vjp
def _fp8_operand(x):
    return _round_fp8(x)


_fp8_operand.defvjp(lambda x: (_round_fp8(x), None), lambda _, g: (g,))


@jax.custom_vjp
def _fp8_cotangent(y):
    return y


_fp8_cotangent.defvjp(lambda y: (y, None), lambda _, g: (_round_fp8(g),))


def _conv(x, w, precision):
    if precision == "fp8":
        x, w = _fp8_operand(x), _fp8_operand(w)
    y = jax.lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding="SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=HIGHEST)
    return _fp8_cotangent(y) if precision == "fp8" else y


def _dense(x, w, precision):
    if precision == "fp8":
        x, w = _fp8_operand(x), _fp8_operand(w)
    y = jnp.dot(x, w, precision=HIGHEST)
    return _fp8_cotangent(y) if precision == "fp8" else y


def logits(params, cfg, images, precision="highest"):
    x = images
    for conv, layer in zip(cfg["convs"], params["convs"]):
        x = jax.nn.relu(_conv(x, layer["w"], precision) + layer["b"])
        p = conv["pool"]
        x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, p, p, 1),
                                  (1, p, p, 1), "VALID")
    x = x.reshape(x.shape[0], -1)
    for i, layer in enumerate(params["fc"]):
        x = _dense(x, layer["w"], precision) + layer["b"]
        if i < len(params["fc"]) - 1:
            x = jax.nn.relu(x)
    return x


def _loss_sum(params, cfg, images, labels, precision):
    logp = jax.nn.log_softmax(logits(params, cfg, images, precision), -1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


def train_rounds(cfg: dict, params0, rounds, *, precision: str = "highest",
                 block_rows: int = 800):
    """Run the reference over ``rounds``, a list of ``(images, labels)``
    host arrays, one per round.  Returns ``(losses, first_grad,
    params)``: each round's mean loss before its update, the first
    round's mean gradient, and the parameters after the last update,
    the two trees as host float32 arrays.  Rows go through in blocks of
    ``block_rows`` so that any round fits."""
    if precision not in PRECISIONS:
        raise ValueError(f"precision must be one of {PRECISIONS}, "
                         f"got {precision!r}")
    opt = cfg["optimizer"]
    lr, beta = opt["lr"], opt["beta"]
    grad_sum = jax.jit(jax.value_and_grad(
        lambda p, x, y: _loss_sum(p, cfg, x, y, precision)))

    @jax.jit
    def update(p, g, acc):
        acc = jax.tree_util.tree_map(lambda a, gi: a + gi * gi, acc, g)
        p = jax.tree_util.tree_map(
            lambda pi, gi, a: pi - lr * gi / jnp.sqrt(beta + a), p, g, acc)
        return p, acc

    params = params0
    acc = jax.tree_util.tree_map(jnp.zeros_like, params0)
    losses, first_grad = [], None
    for images, labels in rounds:
        n = len(labels)
        step = min(block_rows, n)
        if n % step:
            raise ValueError(f"{n} rows do not split into blocks of {step}")
        loss, grad = 0.0, None
        for lo in range(0, n, step):
            l, g = grad_sum(params, jnp.asarray(images[lo:lo + step]),
                            jnp.asarray(labels[lo:lo + step]))
            loss += float(l)
            grad = g if grad is None else jax.tree_util.tree_map(
                jnp.add, grad, g)
        grad = jax.tree_util.tree_map(lambda g: g / n, grad)
        losses.append(loss / n)
        if first_grad is None:
            first_grad = jax.device_get(grad)
        params, acc = update(params, grad, acc)
    return losses, first_grad, jax.device_get(params)


def as_host(tree):
    """``tree`` as host float32 numpy arrays."""
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a, np.float32), jax.device_get(tree))
