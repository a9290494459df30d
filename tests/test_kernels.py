"""Pallas kernel sweeps: shapes x dtypes, assert_allclose vs the pure-jnp
ref.py oracles (interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.adagrad.ops import adagrad_update
from repro.kernels.adagrad.ref import adagrad_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.mamba.ops import mamba_scan
from repro.kernels.mamba.ref import mamba_scan_ref
from repro.kernels.rwkv6.ops import wkv
from repro.kernels.rwkv6.ref import wkv_ref
from repro.kernels.server_step.ops import server_step_update
from repro.kernels.server_step.ref import server_step_ref

RNG = np.random.default_rng(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=3e-5, rtol=1e-4)


@pytest.mark.parametrize("b,s,hq,hkv,hd", [
    (1, 128, 4, 4, 64),     # MHA
    (2, 256, 8, 2, 64),     # GQA 4:1
    (1, 512, 4, 1, 128),    # MQA
    (2, 192, 6, 2, 32),     # non-power-of-two seq (padding path)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention_sweep(b, s, hq, hkv, hd, dtype, causal, window):
    q = jnp.asarray(RNG.normal(size=(b, s, hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(b, s, hkv, hd)), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window)
    ref = attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


@pytest.mark.parametrize("b,t,h,hd", [(1, 64, 2, 64), (2, 200, 4, 64),
                                      (1, 128, 8, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rwkv6_wkv_sweep(b, t, h, hd, dtype):
    r = jnp.asarray(RNG.normal(size=(b, t, h, hd)) * 0.5, dtype)
    k = jnp.asarray(RNG.normal(size=(b, t, h, hd)) * 0.5, dtype)
    v = jnp.asarray(RNG.normal(size=(b, t, h, hd)) * 0.5, dtype)
    w = jnp.asarray(RNG.uniform(0.7, 0.999, size=(b, t, h, hd)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, hd)) * 0.1, jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(b, h, hd, hd)) * 0.1, jnp.float32)
    y1, sT1 = wkv(r, k, v, w, u, s0)
    y2, sT2 = wkv_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(sT1), np.asarray(sT2),
                               atol=1e-3, rtol=1e-3)


def test_rwkv6_state_chaining_equals_one_shot():
    """Running two chunks with carried state == one long sequence."""
    b, t, h, hd = 1, 64, 2, 64
    r, k, v = (jnp.asarray(RNG.normal(size=(b, t, h, hd)), jnp.float32) * 0.5
               for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.7, 0.99, size=(b, t, h, hd)), jnp.float32)
    u = jnp.asarray(RNG.normal(size=(h, hd)) * 0.1, jnp.float32)
    s0 = jnp.zeros((b, h, hd, hd), jnp.float32)
    y_full, sT_full = wkv(r, k, v, w, u, s0)
    y1, s1 = wkv(r[:, :32], k[:, :32], v[:, :32], w[:, :32], u, s0)
    y2, s2 = wkv(r[:, 32:], k[:, 32:], v[:, 32:], w[:, 32:], u, s1)
    np.testing.assert_allclose(np.asarray(y_full),
                               np.asarray(jnp.concatenate([y1, y2], 1)),
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(sT_full), np.asarray(s2),
                               atol=1e-4)


@pytest.mark.parametrize("b,t,di,ds", [(1, 64, 512, 16), (2, 96, 1024, 8),
                                       (1, 64, 512, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mamba_scan_sweep(b, t, di, ds, dtype):
    dt = jnp.asarray(RNG.uniform(0.001, 0.1, size=(b, t, di)), jnp.float32)
    x = jnp.asarray(RNG.normal(size=(b, t, di)), dtype)
    b_t = jnp.asarray(RNG.normal(size=(b, t, ds)), dtype)
    c_t = jnp.asarray(RNG.normal(size=(b, t, ds)), dtype)
    a = -jnp.asarray(RNG.uniform(0.5, 4.0, size=(di, ds)), jnp.float32)
    h0 = jnp.asarray(RNG.normal(size=(b, di, ds)) * 0.1, jnp.float32)
    y1, h1 = mamba_scan(dt, x, b_t, c_t, a, h0)
    y2, h2 = mamba_scan_ref(dt, x, b_t, c_t, a, h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("shape", [(127,), (8, 1024), (33, 77), (3, 5, 7)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("wd", [0.0, 0.01])
def test_adagrad_kernel_sweep(shape, dtype, wd):
    p = jnp.asarray(RNG.normal(size=shape), dtype)
    g = jnp.asarray(RNG.normal(size=shape), dtype)
    acc = jnp.asarray(np.abs(RNG.normal(size=shape)), jnp.float32)
    p1, a1 = adagrad_update(p, g, acc, lr=0.05, beta=1.5, weight_decay=wd)
    p2, a2 = adagrad_ref(p, g, acc, lr=0.05, beta=1.5, weight_decay=wd)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p2, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a2),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", [
    (1,),        # pads to a single block
    (127,),      # sub-tile remainder
    (8192,),     # exactly BLOCK_ROWS x BLOCK_COLS, zero padding
    (33, 77),    # odd 2-d leaf
    (3, 5, 7),   # 3-d leaf
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("members,wd", [(1, 0.0), (5, 0.01)])
def test_server_step_kernel_sweep(shape, dtype, members, wd):
    """The interpret-mode fused server-step kernel is BIT-equal — not
    allclose — to the XLA-jitted oracle over the same padded program
    (``mode="xla"``): the federated loop's fused and reference paths
    must be interchangeable without drifting the trajectory.  A plain
    allclose against the unpadded oracle guards the math itself (the
    bit comparison can't see a shared bug in the padded pipeline)."""
    import functools
    p = jnp.asarray(RNG.normal(size=shape), dtype)
    acc = jnp.asarray(np.abs(RNG.normal(size=shape)), jnp.float32)
    g = jnp.asarray(RNG.normal(size=(members,) + shape), dtype)
    coeffs = jnp.asarray(RNG.uniform(0.1, 1.0, size=members), jnp.float32)
    kw = dict(lr=0.05, beta=1.5, weight_decay=wd)
    p1, a1 = server_step_update(p, g, acc, coeffs, mode="interpret", **kw)
    p2, a2 = server_step_update(p, g, acc, coeffs, mode="xla", **kw)
    assert np.array_equal(np.asarray(p1), np.asarray(p2))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    p3, a3 = jax.jit(functools.partial(server_step_ref, **kw))(
        p, g, acc, coeffs)
    np.testing.assert_allclose(np.asarray(p1, np.float32),
                               np.asarray(p3, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a3),
                               atol=1e-5, rtol=1e-5)


def test_server_step_kernel_refuses_a_cohort_over_its_vmem_budget():
    """Every member's gradient block sits in VMEM at once, so one call
    takes at most ``max_members()`` gradients; one more is refused with
    that number before anything is lowered."""
    from repro.kernels.server_step.kernel import (max_members,
                                                  server_step_blocks)
    m = max_members() + 1
    p2 = jax.ShapeDtypeStruct((8, 1024), jnp.float32)
    g3 = jax.ShapeDtypeStruct((m, 8, 1024), jnp.float32)
    coeffs = jax.ShapeDtypeStruct((m,), jnp.float32)
    with pytest.raises(ValueError, match=f"at most {m - 1} members"):
        server_step_blocks(p2, g3, p2, coeffs, lr=0.05, interpret=False)


def test_flash_attention_matches_model_attention_layer():
    """The kernel agrees with the XLA attention path used by the models."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.models import layers as L
    from repro.sharding.spec import values_tree

    cfg = get_smoke_config("qwen3-4b")
    p = values_tree(L.init_attention(jax.random.PRNGKey(0), cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    pos = jnp.arange(64)
    y_model, (k, v) = L.attention(p, cfg, x, positions=pos)
    # rebuild q/k/v exactly as the layer does, then run the kernel
    q, k2, v2 = L._proj_qkv(p, cfg, x, x)
    cos, sin = L.rope_cos_sin(pos, cfg.resolved_head_dim, cfg.rope_theta)
    q = L.apply_rope(q, cos, sin)
    k2 = L.apply_rope(k2, cos, sin)
    out = flash_attention(q, k2, v2, causal=True)
    y_kernel = jnp.einsum("bqhe,hed->bqd", out, p["wo"])
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel),
                               atol=2e-4, rtol=1e-3)
