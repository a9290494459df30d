"""Jit'd public wrappers for the fused federated server-step kernel.

Three execution modes behind one call:

  * ``"pallas"``    — the real TPU kernel (``interpret=False``);
  * ``"interpret"`` — the same kernel through the Pallas interpreter —
                      the CPU path the bit-equivalence tests pin;
  * ``"xla"``       — the oracle (``ref.server_step_ref``) under
                      ``jax.jit``: identical math, XLA-fused.  The fast
                      off-TPU path — one fused elementwise computation
                      over the flat buffer instead of the interpreter's
                      per-block Python loop.

Default mode is ``"pallas"`` on TPU, ``"xla"`` elsewhere.

Sharding (the olmax ``pjit``/``with_sharding_constraint`` idiom, resolved
through ``repro.sharding.spec.to_pspec``): pass a ``mesh`` and the padded
row dimension of the flat buffer is partitioned across ``data_axis`` —
``shard_map`` hands each device its own whole-block row slice for the
kernel modes, and GSPMD partitions the constrained oracle in ``"xla"``
mode.  Row padding is raised to ``devices × BLOCK_ROWS`` so every device
slice is itself whole VPU tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.kernels.server_step.kernel import (BLOCK_ROWS, pad_to_blocks,
                                              padded_size,
                                              server_step_blocks)
from repro.kernels.server_step.ref import server_step_ref
from repro.sharding.spec import to_pspec

MODES = ("pallas", "interpret", "xla")

def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_mode(mode: str | None) -> str:
    if mode is None:
        return "pallas" if _on_tpu() else "xla"
    if mode not in MODES:
        raise KeyError(f"server-step mode must be one of {MODES}, "
                       f"got {mode!r}")
    return mode


def _row_specs(data_axis: str):
    """PartitionSpecs for the padded buffers, resolved through the
    sharding layer's logical-axis machinery: the flat buffer's row dim
    is the one sharded ('flat_rows' -> the mesh's data axis)."""
    rules = {"flat_rows": data_axis}
    return (to_pspec(("flat_rows", None), rules),           # p2 / acc2
            to_pspec((None, "flat_rows", None), rules),     # g3
            to_pspec((), rules))                            # coeffs


@functools.lru_cache(maxsize=None)
def _jit_impl(lr: float, beta: float, weight_decay: float, mode: str,
              mesh, data_axis: str):
    """One compiled callable per (hyperparams, mode, mesh) combination."""
    ndev = 1 if mesh is None else mesh.shape[data_axis]
    ps2, ps3, psc = _row_specs(data_axis)

    def impl(p, g_stack, acc, coeffs):
        shape, n = p.shape, p.size
        n_p = padded_size(n, BLOCK_ROWS * ndev)
        p2 = pad_to_blocks(p.astype(jnp.float32), n_p)
        acc2 = pad_to_blocks(acc.astype(jnp.float32), n_p)
        g3 = jnp.stack([pad_to_blocks(g.astype(jnp.float32), n_p)
                        for g in g_stack])
        coeffs_f = jnp.asarray(coeffs, jnp.float32)
        kw = dict(lr=lr, beta=beta, weight_decay=weight_decay)
        if mesh is not None and ndev > 1:
            # olmax idiom: constrain, then run the sharded computation
            p2 = jax.lax.with_sharding_constraint(
                p2, NamedSharding(mesh, ps2))
            acc2 = jax.lax.with_sharding_constraint(
                acc2, NamedSharding(mesh, ps2))
            g3 = jax.lax.with_sharding_constraint(
                g3, NamedSharding(mesh, ps3))
        if mode == "xla":
            po, ao = server_step_ref(p2, g3, acc2, coeffs_f, **kw)
        elif mesh is not None and ndev > 1:
            body = functools.partial(server_step_blocks,
                                     interpret=(mode == "interpret"), **kw)
            po, ao = jax.shard_map(
                lambda pp, gg, aa, cc: body(pp, gg, aa, cc),
                mesh=mesh, in_specs=(ps2, ps3, ps2, psc),
                out_specs=(ps2, ps2), check_vma=False)(p2, g3, acc2, coeffs_f)
        else:
            po, ao = server_step_blocks(p2, g3, acc2, coeffs_f,
                                        interpret=(mode == "interpret"),
                                        **kw)
        return (po.reshape(-1)[:n].reshape(shape),
                ao.reshape(-1)[:n].reshape(shape))

    return jax.jit(impl)


def server_step_update(p, g_stack, acc, coeffs, *, lr: float,
                       beta: float = 1.0, weight_decay: float = 0.0,
                       mode: str | None = None, mesh=None,
                       data_axis: str = "data"):
    """Fused clip×weight mean + modified-AdaGrad update.

    ``p``/``acc``: any shape (``acc`` f32); ``g_stack``: (M, *p.shape);
    ``coeffs``: (M,) f32 — each member's clip scale × normalised work
    weight.  Returns ``(p', acc')`` f32 in ``p``'s shape.
    """
    mode = resolve_mode(mode)
    fn = _jit_impl(float(lr), float(beta), float(weight_decay), mode,
                   mesh, data_axis)
    return fn(p, g_stack, acc, coeffs)
