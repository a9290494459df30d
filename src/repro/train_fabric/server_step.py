"""The server-side round update as a first-class abstraction.

After a federated round's barrier closes, the server turns the arrived
per-member gradients into the next round's weights:

    clip each member's gradient  →  work-weighted mean  →  optimizer

:class:`ServerStep` names that hot path so
:class:`~repro.train_fabric.round_engine.FederatedTrainingLoop` can
delegate to interchangeable implementations:

  * :class:`TreeServerStep` — the reference: one fused ``tree_map``
    weighted mean (the old ``weighted_grad_mean`` rule, f32 accumulate)
    followed by the pure-pytree optimizer, the whole step under one
    ``jax.jit``.  Works with any :class:`~repro.optim.Optimizer`.
  * :class:`FusedServerStep` — the paper's modified-AdaGrad hot path as
    ONE kernel launch: every leaf is flattened into a single f32 buffer
    and ``repro.kernels.server_step`` performs clip-weighted mean +
    accumulator + update in one pass (Pallas on TPU, the jit-fused
    oracle off-TPU, the Pallas interpreter for the bit-equivalence
    tests).  With a ``mesh``, the buffer's rows are sharded across the
    data axis via ``shard_map``/``with_sharding_constraint``.

Both paths consume identical per-member coefficients from
:func:`member_coeffs` (clip scale × normalised work weight, computed
once per round on the unflattened trees), so the two implementations
are bit-equivalent by construction — asserted across dtypes in
``tests/test_train_fabric.py``.  One caveat: XLA scalarises leaves of
1-2 elements with FMA contraction the kernels don't replay, so the
bit-for-bit guarantee starts at 3-element leaves (smaller leaves still
agree to within one f32 ulp).
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.obs import trace
from repro.optim import Optimizer
from repro.optim.adagrad_math import adagrad_leaf_update

__all__ = ["ServerStep", "TreeServerStep", "FusedServerStep",
           "member_coeffs", "member_grad_norms", "param_count"]


def param_count(params) -> int:
    """Total scalar parameters in a pytree."""
    return int(sum(l.size for l in jax.tree_util.tree_leaves(params)))


def member_grad_norms(grads: Sequence) -> jnp.ndarray:
    """(M,) f32 global L2 norm of each member's gradient pytree.

    Per-leaf squared sums are accumulated left-to-right in flatten
    order — ONE canonical reduction order shared by every ServerStep
    implementation, so clip coefficients can never differ between the
    reference and the fused path.
    """
    norms = []
    for g in grads:
        s = None
        for leaf in jax.tree_util.tree_leaves(g):
            q = jnp.sum(jnp.square(jnp.asarray(leaf).astype(jnp.float32)))
            s = q if s is None else s + q
        norms.append(jnp.sqrt(s))
    return jnp.stack(norms)


@functools.lru_cache(maxsize=None)
def _coeffs_jit(clip_norm: Optional[float]):
    @jax.jit
    def member_coeffs(grads_tuple, works):   # names the program
        w = works / jnp.sum(works)
        if clip_norm is not None:
            norms = member_grad_norms(grads_tuple)
            w = w * jnp.minimum(1.0, clip_norm / jnp.maximum(norms, 1e-12))
        return w
    return member_coeffs


def member_coeffs(grads: Sequence, works: Sequence[float],
                  clip_norm: Optional[float] = None) -> jnp.ndarray:
    """(M,) f32 per-member coefficient: normalised work weight times the
    member's clip scale ``min(1, clip_norm / ‖g_m‖₂)``.  The weighted
    mean of clipped gradients is then simply ``Σ_m coeff_m · g_m``.

    Every ServerStep implementation calls this — the SAME cached
    compiled function — and feeds the resulting concrete array to its
    own step, so the coefficients are bitwise identical across
    implementations no matter how each one's jit fuses its math."""
    return _coeffs_jit(clip_norm)(
        tuple(grads), jnp.asarray(list(works), jnp.float32))


class ServerStep:
    """Interface: ``step(grads, works, params, opt_state)`` →
    ``(new_params, new_opt_state)``, where ``grads`` is the round's list
    of arrived per-member gradient pytrees and ``works`` their work
    weights (same order)."""

    name = "abstract"

    def step(self, grads: Sequence, works: Sequence[float], params,
             opt_state):
        raise NotImplementedError


class TreeServerStep(ServerStep):
    """Reference implementation: clip → fused ``tree_map`` weighted mean
    → ``opt.update``, jitted end to end.  The weighted mean accumulates
    in f32 left-to-right over members (each leaf reduced in one pass, no
    per-member scaled tree copies) — the same operation order the fused
    kernel replays, which is what makes bit-equivalence testable."""

    name = "tree_map"

    def __init__(self, opt: Optimizer, *, clip_norm: Optional[float] = None):
        self.opt = opt
        self.clip_norm = clip_norm

        def impl(grads_tuple, coeffs, params, opt_state):
            def fuse(*leaves):
                acc = coeffs[0] * leaves[0].astype(jnp.float32)
                for m in range(1, len(leaves)):
                    acc = acc + coeffs[m] * leaves[m].astype(jnp.float32)
                return acc

            gmean = jax.tree_util.tree_map(fuse, *grads_tuple)
            return self.opt.update(gmean, opt_state, params)

        self._jit = jax.jit(impl)

    def step(self, grads, works, params, opt_state):
        coeffs = member_coeffs(grads, works, self.clip_norm)
        return self._jit(tuple(grads), coeffs, params, opt_state)


class FusedServerStep(ServerStep):
    """The modified-AdaGrad server step as one fused kernel pass.

    Two instantiations of the same fusion, picked by ``mode``:

      * ``"pallas"`` / ``"interpret"`` — every leaf is flattened and
        concatenated into a single f32 buffer (per-leaf dtypes restored
        on the way out), the M member gradients stacked on a leading
        axis, and ``server_step_update`` performs clip-weighted mean +
        accumulator + parameter update in ONE kernel launch; with a
        multi-device ``mesh`` the flat rows are ``shard_map``-partitioned
        across ``data_axis``.
      * ``"xla"`` (the off-TPU default) — the identical math expressed
        leafwise under one ``jax.jit``: XLA fuses the whole step into
        one elementwise program per leaf with NO flatten/concat copies
        (on CPU those copies cost more than the unfused passes they
        replace).  Same elementwise op order as the flat kernel, so all
        three modes produce bit-identical results.

    Only the paper's optimizer is fused; constructing this against a
    non-adagrad optimizer raises.
    """

    name = "fused"

    def __init__(self, opt: Optimizer, *, lr: float, beta: float = 1.0,
                 weight_decay: float = 0.0,
                 clip_norm: Optional[float] = None,
                 mode: Optional[str] = None, mesh=None,
                 data_axis: str = "data"):
        if opt.name != "adagrad":
            raise ValueError(
                f"FusedServerStep fuses the paper's modified AdaGrad; "
                f"got optimizer {opt.name!r} (use TreeServerStep)")
        from repro.kernels.server_step.ops import (resolve_mode,
                                                   server_step_update)
        self.opt = opt
        self.lr, self.beta, self.weight_decay = lr, beta, weight_decay
        self.clip_norm = clip_norm
        self.mode = resolve_mode(mode)
        self.mesh = mesh
        self.data_axis = data_axis

        def leafwise(grads_tuple, coeffs, params, acc):
            def one(p, a, *gs):
                g = coeffs[0] * gs[0].astype(jnp.float32)
                for m in range(1, len(gs)):
                    g = g + coeffs[m] * gs[m].astype(jnp.float32)
                return adagrad_leaf_update(
                    p, g, a, lr=self.lr, beta=self.beta,
                    weight_decay=self.weight_decay)

            out = jax.tree_util.tree_map(one, params, acc, *grads_tuple)
            pick = lambda i: jax.tree_util.tree_map(
                lambda o: o[i], out,
                is_leaf=lambda x: isinstance(x, tuple))
            return pick(0), pick(1)

        def flat(grads_tuple, coeffs, params, acc):
            leaves_p, tdef = jax.tree_util.tree_flatten(params)
            leaves_a = tdef.flatten_up_to(acc)
            flat32 = lambda ls: jnp.concatenate(
                [jnp.asarray(l).astype(jnp.float32).reshape(-1)
                 for l in ls])
            pf = flat32(leaves_p)
            af = flat32(leaves_a)
            gf = jnp.stack([flat32(tdef.flatten_up_to(g))
                            for g in grads_tuple])
            po, ao = server_step_update(
                pf, gf, af, coeffs, lr=self.lr, beta=self.beta,
                weight_decay=self.weight_decay, mode=self.mode,
                mesh=self.mesh, data_axis=self.data_axis)
            new_p, new_a, off = [], [], 0
            for leaf in leaves_p:
                sz = leaf.size
                new_p.append(po[off:off + sz].reshape(leaf.shape)
                             .astype(leaf.dtype))
                new_a.append(ao[off:off + sz].reshape(leaf.shape))
                off += sz
            return (jax.tree_util.tree_unflatten(tdef, new_p),
                    jax.tree_util.tree_unflatten(tdef, new_a))

        # leafwise only without a mesh: the sharded paths (GSPMD / the
        # shard_map'd kernel) need the flat row-partitioned buffer
        body = leafwise if self.mode == "xla" and mesh is None else flat

        def fused_server_step(grads_tuple, coeffs, params, acc):
            return body(grads_tuple, coeffs, params, acc)

        self._jit = jax.jit(fused_server_step)

    def step(self, grads, works, params, opt_state):
        tr = trace.current()
        if tr is None:
            coeffs = member_coeffs(grads, works, self.clip_norm)
            new_params, new_acc = self._jit(tuple(grads), coeffs, params,
                                            opt_state["acc"])
            return new_params, {"acc": new_acc}
        # traced: the coefficients, the copy of the M host gradients to
        # the device and the fused program, each until ready in its span
        with tr.span("server_step.coeffs", cat="server_step",
                     args={"M": len(grads)}):
            coeffs = jax.block_until_ready(
                member_coeffs(grads, works, self.clip_norm))
        with tr.span("server_step.h2d", cat="server_step") as args:
            args["bytes"] = sum(x.nbytes for x in
                                jax.tree_util.tree_leaves(grads))
            dev = jax.block_until_ready(jax.device_put(tuple(grads)))
        with tr.span("server_step.compute", cat="server_step"):
            new_params, new_acc = jax.block_until_ready(
                self._jit(dev, coeffs, params, opt_state["acc"]))
        return new_params, {"acc": new_acc}
