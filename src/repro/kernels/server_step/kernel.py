"""Fused federated server-step TPU kernel (Pallas).

One pass over the round's flattened parameter buffer performs the whole
server-side hot path of a federated training round:

    g     = Σ_m  coeff_m · g_m        (per-member clip × work weight,
                                       folded into one f32 coefficient)
    acc  += g²                        (modified-AdaGrad accumulator)
    θ    −= α · g / sqrt(β + acc)

i.e. per-member gradient clipping, the work-weighted mean, and the
paper's modified-AdaGrad update in a single kernel launch — (M + 2)
reads + 2 writes per element instead of the ~(3M + 7) transfers of the
unfused clip → ``weighted_grad_mean`` → optimizer chain.

Layout follows the adagrad kernel template: the caller flattens and
concatenates every leaf into one f32 buffer, pads it to (rows, 1024)
VPU tiles, and stacks the M member gradients on a leading axis.  The
member loop is a static Python loop, so the f32 accumulation order is
exactly the reference's left-to-right order — interpret mode is
bit-equal to ``repro.kernels.server_step.ref.server_step_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_ROWS = 8
BLOCK_COLS = 1024

# A grid step holds one f32 block of p, acc, p' and acc' plus one block of
# every member's gradient, each double-buffered, so the kernel's VMEM
# grows by two blocks (64 KiB) per member.  Mosaic's default scoped limit
# (16 MiB) stops at about 250 members; the limit is raised from these
# bytes up to a budget kept under the 128 MiB of VMEM of a v5e core.
_BLOCK_BYTES = BLOCK_ROWS * BLOCK_COLS * 4
_DEFAULT_SCOPED_VMEM = 16 * 2**20
_VMEM_HEADROOM = 2 * 2**20
VMEM_BUDGET = 96 * 2**20


def vmem_bytes(members: int) -> int:
    """Scoped VMEM one kernel call asks for with ``members`` gradients."""
    return 2 * (members + 4) * _BLOCK_BYTES + _VMEM_HEADROOM


def max_members() -> int:
    """The largest cohort one kernel call takes within ``VMEM_BUDGET``."""
    return (VMEM_BUDGET - _VMEM_HEADROOM) // (2 * _BLOCK_BYTES) - 4


def _server_step_kernel(c_ref, p_ref, g_ref, a_ref, po_ref, ao_ref, *,
                        lr: float, beta: float, weight_decay: float,
                        members: int):
    # static member loop: left-to-right f32 accumulate, same order as the
    # tree_map reference (bit-equivalence contract)
    g = c_ref[0] * g_ref[0].astype(jnp.float32)
    for m in range(1, members):
        g = g + c_ref[m] * g_ref[m].astype(jnp.float32)
    p = p_ref[...].astype(jnp.float32)
    if weight_decay:
        g = g + weight_decay * p
    a = a_ref[...] + jnp.square(g)
    step = lr * g * jax.lax.rsqrt(beta + a)
    po_ref[...] = (p - step).astype(po_ref.dtype)
    ao_ref[...] = a


def pad_to_blocks(x, n_padded: int):
    """Flatten ``x`` and zero-pad to the (rows, BLOCK_COLS) tile grid."""
    flat = x.reshape(-1)
    return jnp.pad(flat, (0, n_padded - flat.shape[0])).reshape(
        n_padded // BLOCK_COLS, BLOCK_COLS)


def padded_size(n: int, row_multiple: int = BLOCK_ROWS) -> int:
    """Elements after padding ``n`` up to whole (row_multiple, 1024)
    blocks — ``row_multiple`` is raised by the sharded path so every
    device slice is itself whole blocks."""
    block = row_multiple * BLOCK_COLS
    return (n + block - 1) // block * block


def server_step_blocks(p2, g3, acc2, coeffs, *, lr: float, beta: float = 1.0,
                       weight_decay: float = 0.0, interpret: bool = True):
    """The raw kernel over pre-tiled buffers.

    ``p2``/``acc2``: (R, 1024) f32 with R a multiple of BLOCK_ROWS;
    ``g3``: (M, R, 1024) f32; ``coeffs``: (M,) f32 (clip scale × work
    weight per member).  Returns (p2', acc2') f32.
    """
    m, rows = g3.shape[0], p2.shape[0]
    if vmem_bytes(m) > VMEM_BUDGET:
        raise ValueError(
            f"server-step kernel: {m} member gradients need "
            f"{vmem_bytes(m) / 2**20:.2f} MiB of VMEM, over the "
            f"{VMEM_BUDGET // 2**20} MiB budget; at most {max_members()} "
            f"members fit in one call")
    grid = (rows // BLOCK_ROWS,)
    spec2 = pl.BlockSpec((BLOCK_ROWS, BLOCK_COLS), lambda i: (i, 0))
    spec3 = pl.BlockSpec((m, BLOCK_ROWS, BLOCK_COLS), lambda i: (0, i, 0))
    cspec = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_server_step_kernel, lr=lr, beta=beta,
                          weight_decay=weight_decay, members=m),
        grid=grid,
        in_specs=[cspec, spec2, spec3, spec2],
        out_specs=[spec2, spec2],
        out_shape=[
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
            jax.ShapeDtypeStruct(p2.shape, jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem_bytes(m), _DEFAULT_SCOPED_VMEM)),
        interpret=interpret,
        name="server_step_update",
    )(coeffs, p2, g3, acc2)


def server_step_kernel(p, g_stack, acc, coeffs, *, lr: float,
                       beta: float = 1.0, weight_decay: float = 0.0,
                       interpret: bool = True):
    """Convenience single-array form: ``p``/``acc`` any shape, ``g_stack``
    (M, *p.shape).  Pads, tiles, runs the kernel, un-pads.  Returns
    (p', acc') f32 in ``p``'s shape."""
    shape, n = p.shape, p.size
    n_p = padded_size(n)
    p2 = pad_to_blocks(p.astype(jnp.float32), n_p)
    acc2 = pad_to_blocks(acc.astype(jnp.float32), n_p)
    g3 = jnp.stack([pad_to_blocks(g.astype(jnp.float32), n_p)
                    for g in g_stack])
    po, ao = server_step_blocks(p2, g3, acc2,
                                jnp.asarray(coeffs, jnp.float32),
                                lr=lr, beta=beta,
                                weight_decay=weight_decay,
                                interpret=interpret)
    return (po.reshape(-1)[:n].reshape(shape),
            ao.reshape(-1)[:n].reshape(shape))
