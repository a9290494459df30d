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
import itertools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

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


def _cnn_loss_and_grads(ccfg, params, images, labels):
    def loss_fn(p):
        return nll_loss(forward(p, ccfg, images), labels)
    with jax.named_scope("cnn_loss_and_grads"):
        return jax.value_and_grad(loss_fn)(params)


@functools.lru_cache(maxsize=None)
def loss_and_grads(ccfg: CNNConfig):
    """Jitted ``(params, images, labels) -> (mean NLL, grad pytree)`` for
    plain (unboxed) params, cached per config so every shard of a round
    — and every round — reuses one compiled executable.  The program is
    named ``cnn_loss_and_grads`` on the profiler's timeline."""

    @jax.jit
    def cnn_loss_and_grads(params, images, labels):
        return _cnn_loss_and_grads(ccfg, params, images, labels)

    return cnn_loss_and_grads


@functools.lru_cache(maxsize=None)
def packed_loss_and_grads(ccfg: CNNConfig, treedef, shapes: tuple,
                          images_shape: tuple, labels_shape: tuple):
    """:func:`loss_and_grads` on one packed operand: a flat ``uint32``
    buffer of the float32 param leaves (``treedef``'s leaf order, each of
    its ``shapes``), then the images, then the int32 labels, each as its
    own bits.  The program bitcasts and reshapes them on the device and
    returns the gradient leaves, flattened in the same order, and the
    loss last, as one float32 vector.  Cached per config and layout; the
    program has :func:`loss_and_grads`'s name."""
    shapes = (*shapes, images_shape, labels_shape)
    dtypes = [jnp.float32] * (len(shapes) - 1) + [jnp.int32]
    ends = list(itertools.accumulate(math.prod(s) for s in shapes))

    @jax.jit
    def cnn_loss_and_grads(words):
        *leaves, images, labels = [
            jax.lax.bitcast_convert_type(w, dtype).reshape(shape)
            for w, dtype, shape in zip(jnp.split(words, ends[:-1]), dtypes,
                                       shapes)]
        loss, grads = _cnn_loss_and_grads(
            ccfg, jax.tree_util.tree_unflatten(treedef, leaves), images,
            labels)
        return jnp.concatenate([g.ravel() for g in
                                jax.tree_util.tree_leaves(grads)]
                               + [loss[None]])

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
    ``{"grad", "loss", "round"}`` with gradients as plain numpy so the
    result pickles over the v2 wire protocol.

    Served params that are host float32 arrays (a remote client's,
    decoded from the wire) go to the device in one packed buffer with
    the rows (:func:`packed_loss_and_grads`), and the gradients come
    back in one buffer, split on the host into views with the leaves'
    shapes.  Params already on the device (an in-process client's) stay
    there: the task calls :func:`loss_and_grads` on them and the rows,
    and reads each gradient leaf back on its own.

    Where a tracer is current (``repro.obs.trace.use``), the copy of the
    params and rows to the device, the program until its gradients are
    ready, and their copy back run one after another, each in its own
    span (``grad.h2d``, ``grad.compute``, ``grad.d2h``; the copies carry
    their ``bytes`` and the number of ``arrays`` moved); without one the
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
        images, labels = images[lo:hi], labels[lo:hi]
        served = static[self.weights_key]
        params = served["params"]
        tr = trace.current()
        leaves, treedef = jax.tree_util.tree_flatten(params)
        if all(isinstance(x, np.ndarray) and x.dtype == np.float32
               for x in leaves):
            loss, grads = _packed_loss_and_grads(tr, self.ccfg, leaves,
                                                 treedef, images, labels)
        elif tr is None:
            loss, grads = loss_and_grads(self.ccfg)(
                params, jnp.asarray(images), jnp.asarray(labels))
            grads = jax.device_get(grads)
        else:
            loss, grads = _traced_loss_and_grads(tr, self.ccfg, params,
                                                 images, labels)
        return {"grad": grads, "loss": float(loss),
                "round": served.get("round", -1)}


def _nbytes(tree) -> int:
    return sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))


def _pack(*arrays) -> np.ndarray:
    """One ``uint32`` buffer of the 32-bit ``arrays``' bits, in order."""
    return np.concatenate([np.ravel(x).view(np.uint32) for x in arrays])


def _packed_loss_and_grads(tr, ccfg, leaves, treedef, images, labels):
    """The task on host leaves: one buffer to the device, one back.
    With a tracer ``tr``, each of the three steps in its span."""
    program = packed_loss_and_grads(
        ccfg, treedef, tuple(x.shape for x in leaves), images.shape,
        labels.shape)
    if tr is None:
        words = jax.device_put(_pack(*leaves, images, labels))
        return _unpack(jax.device_get(program(words)), leaves, treedef)
    with tr.span("grad.h2d", cat="grad") as args:
        words = _pack(*leaves, images, labels)
        args["bytes"], args["arrays"] = words.nbytes, 1
        words = jax.block_until_ready(jax.device_put(words))
    with tr.span("grad.compute", cat="grad"):
        out = jax.block_until_ready(program(words))
    with tr.span("grad.d2h", cat="grad") as args:
        loss, grads = _unpack(jax.device_get(out), leaves, treedef)
        args["bytes"], args["arrays"] = _nbytes(grads), 1
    return loss, grads


def _unpack(flat, leaves, treedef):
    """``(loss, grad tree)`` of the packed program's output on the host:
    views of ``flat`` with the shapes of ``leaves``."""
    *grads, loss = np.split(flat, list(itertools.accumulate(
        x.size for x in leaves)))
    return loss[0], jax.tree_util.tree_unflatten(
        treedef, [g.reshape(x.shape) for g, x in zip(grads, leaves)])


def _traced_loss_and_grads(tr, ccfg, params, images, labels):
    """:func:`loss_and_grads` with its transfers split from its compute,
    each timed in a span of ``tr``; gradients returned on the host."""
    with tr.span("grad.h2d", cat="grad") as args:
        args["bytes"] = _nbytes((params, images, labels))
        args["arrays"] = sum(not isinstance(x, jax.Array) for x in
                             jax.tree_util.tree_leaves(
                                 (params, images, labels)))
        operands = jax.block_until_ready(
            jax.device_put((params, images, labels)))
    with tr.span("grad.compute", cat="grad"):
        loss, grads = jax.block_until_ready(
            loss_and_grads(ccfg)(*operands))
    with tr.span("grad.d2h", cat="grad") as args:
        grads = jax.device_get(grads)
        args["bytes"] = _nbytes(grads)
        args["arrays"] = len(jax.tree_util.tree_leaves(grads))
    return loss, grads
