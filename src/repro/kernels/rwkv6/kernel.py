"""RWKV-6 WKV recurrence TPU kernel (Pallas).

    y_t = r_t · (S_{t-1} + diag(u·k_t) v_tᵀ)
    S_t = diag(w_t) S_{t-1} + k_t v_tᵀ

Grid: (batch, heads, num_time_blocks) — time is the sequential innermost
dimension; the (hd, hd) state matrix lives in VMEM scratch and is carried
across time blocks.  Inside a block the recurrence is a ``fori_loop`` over
single steps (rank-1 update + matvec on an (hd, hd) tile; hd=64 keeps the
tile lane-aligned).  Outputs: per-token y and the final state (for the
prefill→decode handoff).  VMEM per step ≈ 4·BT·hd inputs + hd² state ≈
0.15 MB at BT=128, hd=64.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 128


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, y_ref, sT_ref,
                state_ref, *, bt: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = s0_ref[...][0, 0].astype(jnp.float32)

    u = u_ref[...][0].astype(jnp.float32)                # (hd,)

    def step(t, _):
        tt = pl.ds(t, 1)
        r_t = r_ref[0, tt, 0, :][0].astype(jnp.float32)
        k_t = k_ref[0, tt, 0, :][0].astype(jnp.float32)
        v_t = v_ref[0, tt, 0, :][0].astype(jnp.float32)
        w_t = w_ref[0, tt, 0, :][0].astype(jnp.float32)
        s = state_ref[...]                               # (hd_k, hd_v)
        kv = k_t[:, None] * v_t[None, :]
        att = s + (u * k_t)[:, None] * v_t[None, :]
        y = jnp.einsum("k,kv->v", r_t, att)
        y_ref[0, tt, 0, :] = y[None].astype(y_ref.dtype)
        state_ref[...] = w_t[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(it == nt - 1)
    def _writeout():
        sT_ref[...] = state_ref[...][None, None].astype(sT_ref.dtype)


def wkv_kernel(r, k, v, w, u, s0, *, block_t: int = DEFAULT_BT,
               interpret: bool = True):
    """r/k/v/w: (B, T, H, hd); u: (H, hd); s0: (B, H, hd, hd) f32.

    Returns (y (B, T, H, hd) f32-cast-to-input-dtype, sT (B, H, hd, hd) f32).
    """
    b, t, h, hd = r.shape
    bt = min(block_t, t)
    t_p = (t + bt - 1) // bt * bt
    if t_p != t:
        pad = ((0, 0), (0, t_p - t), (0, 0), (0, 0))
        r = jnp.pad(r, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        w = jnp.pad(w, pad, constant_values=1.0)  # identity decay on pad

    grid = (b, h, t_p // bt)
    seq_spec = pl.BlockSpec((1, bt, 1, hd), lambda b_, h_, i: (b_, i, h_, 0))
    y, sT = pl.pallas_call(
        functools.partial(_wkv_kernel, bt=bt),
        grid=grid,
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, hd), lambda b_, h_, i: (h_, 0)),
            pl.BlockSpec((1, 1, hd, hd), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_specs=[
            seq_spec,
            pl.BlockSpec((1, 1, hd, hd), lambda b_, h_, i: (b_, h_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_p, h, hd), r.dtype),
            jax.ShapeDtypeStruct((b, h, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y[:, :t], sT
