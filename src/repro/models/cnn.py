"""The paper's deep CNN (Sukiyaki): conv -> activation -> max-pool stacks and
a fully-connected softmax classifier (Figures 2/4 of the paper).

Exposed as two halves — ``conv_features`` (the "client" part under the
paper's distribution algorithm) and ``fc_logits`` (the "server" part) — so
``core/split_parallel.py`` can train them with the paper's concurrency.

Also exposed as **fabric ticket work**: :class:`CnnGradShard` is a
picklable task callable (registrable under a ``TaskDef``, shippable to
remote browser clients over the wire protocol) that computes the CNN's
loss + gradients for one row slice of a deterministic synthetic dataset
against the round's served weights — the payload that makes
``FederatedTrainingLoop`` rounds train the *paper's model* rather than a
toy regression (see ``benchmarks/federated_training.py``).
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.configs.paper_cnn import CNNConfig
from repro.obs import trace
from repro.sharding.spec import Param, param, shard_act


def init_cnn(key, ccfg):
    ks = jax.random.split(key, len(ccfg.convs) + 1)
    convs = []
    cin = ccfg.in_channels
    for i, spec in enumerate(ccfg.convs):
        convs.append({
            "w": param(ks[i], (spec.kernel, spec.kernel, cin,
                               spec.out_channels),
                       (None, None, None, "conv_out"),
                       scale=1.0 / math.sqrt(spec.kernel ** 2 * cin)),
            "b": Param(jnp.zeros((spec.out_channels,)), ("conv_out",)),
        })
        cin = spec.out_channels
    dims = [ccfg.feature_dim, *ccfg.fc_hidden, ccfg.num_classes]
    fck = jax.random.split(ks[-1], len(dims) - 1)
    fc = [{
        "w": param(fck[i], (dims[i], dims[i + 1]),
                   ("head_embed", "head_vocab"),
                   scale=1.0 / math.sqrt(dims[i])),
        "b": Param(jnp.zeros((dims[i + 1],)), ("head_vocab",)),
    } for i in range(len(dims) - 1)]
    return {"convs": convs, "fc": fc}


def conv_features(params, ccfg, images):
    """images: (B, H, W, C) -> flat features (B, feature_dim)."""
    x = images
    for spec, cp in zip(ccfg.convs, params["convs"]):
        x = jax.lax.conv_general_dilated(
            x, cp["w"].astype(x.dtype), window_strides=(1, 1),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))
        x = jax.nn.relu(x + cp["b"].astype(x.dtype))
        x = jax.lax.reduce_window(
            x, -jnp.inf, jax.lax.max, (1, spec.pool, spec.pool, 1),
            (1, spec.pool, spec.pool, 1), "VALID")
        x = shard_act(x, "batch", None, None, "conv_out")
    return x.reshape(x.shape[0], -1)


def fc_logits(params, ccfg, feats):
    """The server-side fully-connected classifier (optionally deep)."""
    x = feats
    layers_ = params["fc"]
    for i, lp in enumerate(layers_):
        x = x @ lp["w"].astype(x.dtype) + lp["b"].astype(x.dtype)
        if i < len(layers_) - 1:
            x = jax.nn.relu(x)
    return x


def forward(params, ccfg, images):
    return fc_logits(params, ccfg, conv_features(params, ccfg, images))


def nll_loss(logits, labels):
    """Mean softmax cross-entropy; labels: (B,) int."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))


def error_rate(logits, labels):
    return jnp.mean((jnp.argmax(logits, -1) != labels).astype(jnp.float32))


# ---------------------------------------------------------------------------
# The CNN as fabric ticket work
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def loss_and_grads(ccfg: CNNConfig):
    """Jitted ``(params, images, labels) -> (mean NLL, grad pytree)`` for
    plain (unboxed) params, cached per config so every shard of a round
    — and every round — reuses one compiled executable.  The program is
    named ``cnn_loss_and_grads`` on the profiler's timeline."""

    @jax.jit
    def cnn_loss_and_grads(params, images, labels):
        def loss_fn(p):
            return nll_loss(forward(p, ccfg, images), labels)
        with jax.named_scope("cnn_loss_and_grads"):
            return jax.value_and_grad(loss_fn)(params)

    return cnn_loss_and_grads


@functools.lru_cache(maxsize=None)
def shard_dataset(ccfg: CNNConfig, n_rows: int, seed: int):
    """The deterministic synthetic classification set the fabric shards
    by row slice (``repro.data.clustered_images`` — learnable, so the
    round loss actually converges).  Cached: every shard of every round
    slices the same arrays."""
    from repro.data import clustered_images
    return clustered_images(n_rows, image_size=ccfg.image_size,
                            channels=ccfg.in_channels, seed=seed)


@dataclass(frozen=True)
class CnnGradShard:
    """Picklable fabric task: paper-CNN loss + gradients of one row slice.

    ``args`` is a ``(lo, hi)`` row slice of :func:`shard_dataset`;
    ``static[weights_key]`` is the round's versioned weight publish
    ``{"round": t, "params": ...}``.  Returns the training-loop contract
    ``{"grad", "loss", "round"}`` with gradients device_get'ed to plain
    numpy so the result pickles over the v2 wire protocol.

    Where a tracer is current (``repro.obs.trace.use``), the copy of the
    params and rows to the device, the program until its gradients are
    ready, and their copy back run one after another, each in its own
    span (``grad.h2d``, ``grad.compute``, ``grad.d2h``); without one the
    call runs as a single dispatch.

    A frozen dataclass of hashable config rather than a closure: remote
    clients receive the task by pickle, and the jitted grad function is
    looked up per-process from the :func:`loss_and_grads` cache.
    """

    ccfg: CNNConfig
    n_rows: int = 512
    seed: int = 0
    weights_key: str = "weights"

    def __call__(self, args, static):
        lo, hi = args
        images, labels = shard_dataset(self.ccfg, self.n_rows, self.seed)
        served = static[self.weights_key]
        tr = trace.current()
        if tr is None:
            loss, grads = loss_and_grads(self.ccfg)(
                served["params"], jnp.asarray(images[lo:hi]),
                jnp.asarray(labels[lo:hi]))
            grads = jax.device_get(grads)
        else:
            loss, grads = _traced_loss_and_grads(
                tr, self.ccfg, served["params"], images[lo:hi],
                labels[lo:hi])
        return {"grad": grads, "loss": float(loss),
                "round": served.get("round", -1)}


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _traced_loss_and_grads(tr, ccfg, params, images, labels):
    """:func:`loss_and_grads` with its transfers split from its compute,
    each timed in a span of ``tr``; gradients returned on the host."""
    with tr.span("grad.h2d", cat="grad") as args:
        args["bytes"] = _nbytes((params, images, labels))
        operands = jax.block_until_ready(
            jax.device_put((params, images, labels)))
    with tr.span("grad.compute", cat="grad"):
        loss, grads = jax.block_until_ready(
            loss_and_grads(ccfg)(*operands))
    with tr.span("grad.d2h", cat="grad") as args:
        grads = jax.device_get(grads)
        args["bytes"] = _nbytes(grads)
    return loss, grads
