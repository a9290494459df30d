"""Selective-SSM (Mamba) scan TPU kernel (Pallas).

    h_t = exp(Δ_t ⊙ A) h_{t-1} + (Δ_t ⊙ x_t) B_tᵀ
    y_t = h_t C_t + D x_t   (D-skip applied in the wrapper)

Grid: (batch, channel_blocks, time_blocks) — time is the sequential
innermost dimension; the (BC, d_state) state tile is carried in VMEM
scratch.  Channels (d_inner) are blocked at 512 lanes; B_t/C_t (d_state
columns) are shared across channel blocks via their index map.  VMEM per
step ≈ BT·BC (dt, x) + 2·BT·ds (B, C) + BC·ds state ≈ 0.6 MB at
BT=64, BC=512, ds=16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BT = 64
DEFAULT_BC = 512


def _mamba_kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, h0_ref, y_ref, hT_ref,
                  state_ref, *, bt: int):
    it = pl.program_id(2)
    nt = pl.num_programs(2)

    @pl.when(it == 0)
    def _init():
        state_ref[...] = h0_ref[...][0].astype(jnp.float32)

    a = a_ref[...].astype(jnp.float32)                    # (BC, ds)

    def step(t, _):
        tt = pl.ds(t, 1)
        dt_t = dt_ref[0, tt, :][0].astype(jnp.float32)
        x_t = x_ref[0, tt, :][0].astype(jnp.float32)
        b_t = b_ref[0, tt, :][0].astype(jnp.float32)
        c_t = c_ref[0, tt, :][0].astype(jnp.float32)
        h = state_ref[...]                                # (BC, ds)
        da = jnp.exp(dt_t[:, None] * a)
        h = da * h + (dt_t * x_t)[:, None] * b_t[None, :]
        y = jnp.einsum("cs,s->c", h, c_t)
        y_ref[0, tt, :] = y[None].astype(y_ref.dtype)
        state_ref[...] = h
        return 0

    jax.lax.fori_loop(0, bt, step, 0)

    @pl.when(it == nt - 1)
    def _writeout():
        hT_ref[...] = state_ref[...][None].astype(hT_ref.dtype)


def mamba_scan_kernel(dt, x, b_t, c_t, a, h0, *, block_t: int = DEFAULT_BT,
                      block_c: int = DEFAULT_BC, interpret: bool = True):
    """dt/x: (B, T, DI); b_t/c_t: (B, T, ds); a: (DI, ds);
    h0: (B, DI, ds) f32.  Returns (y (B,T,DI) f32, hT (B, DI, ds) f32).
    """
    b, t, di = dt.shape
    ds = b_t.shape[-1]
    bc = min(block_c, di)
    bt = min(block_t, t)
    assert di % bc == 0, (di, bc)
    t_p = (t + bt - 1) // bt * bt
    if t_p != t:
        pad3 = ((0, 0), (0, t_p - t), (0, 0))
        dt = jnp.pad(dt, pad3)
        x = jnp.pad(x, pad3)
        b_t = jnp.pad(b_t, pad3)
        c_t = jnp.pad(c_t, pad3)

    grid = (b, di // bc, t_p // bt)
    chan_spec = pl.BlockSpec((1, bt, bc), lambda b_, c, i: (b_, i, c))
    state_spec = pl.BlockSpec((1, bt, ds), lambda b_, c, i: (b_, i, 0))
    y, hT = pl.pallas_call(
        functools.partial(_mamba_kernel, bt=bt),
        grid=grid,
        in_specs=[
            chan_spec, chan_spec, state_spec, state_spec,
            pl.BlockSpec((bc, ds), lambda b_, c, i: (c, 0)),
            pl.BlockSpec((1, bc, ds), lambda b_, c, i: (b_, c, 0)),
        ],
        out_specs=[
            chan_spec,
            pl.BlockSpec((1, bc, ds), lambda b_, c, i: (b_, c, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, t_p, di), jnp.float32),
            jax.ShapeDtypeStruct((b, di, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bc, ds), jnp.float32)],
        interpret=interpret,
    )(dt, x, b_t, c_t, a, h0)
    return y[:, :t], hT
