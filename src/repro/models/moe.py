"""Mixture-of-Experts FFN: top-k token-choice routing with capacity-bounded
sort-based dispatch (no (tokens, E, C) one-hot blow-up).

Dispatch: assignments (token, k) are ranked within their expert via an
argsort + searchsorted trick, scattered into an (E, C, D) buffer (sharded
expert-parallel over 'model'), batched expert matmuls run as one einsum,
and results are gathered back and combined with the normalised router
weights.  Tokens beyond an expert's capacity are dropped (standard
token-choice behaviour); ``tests/test_moe.py`` checks exactness against a
dense per-token oracle when capacity is ample.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.sharding.spec import current_ctx, param, shard_act, to_pspec
from repro.models.layers import mlp_act


def init_moe(key, cfg, d_ff: int | None = None):
    d_ff = d_ff or cfg.d_ff
    e = cfg.moe.num_experts
    ks = jax.random.split(key, 4)
    p = {
        "router": param(ks[0], (cfg.d_model, e), ("embed", None), scale=0.02),
        "w_up": param(ks[1], (e, cfg.d_model, d_ff),
                      ("expert", "embed", "mlp"),
                      scale=1.0 / math.sqrt(cfg.d_model)),
        "w_down": param(ks[2], (e, d_ff, cfg.d_model),
                        ("expert", "mlp", "embed"),
                        scale=1.0 / math.sqrt(d_ff)),
    }
    if cfg.act == "swiglu":
        p["w_gate"] = param(ks[3], (e, cfg.d_model, d_ff),
                            ("expert", "embed", "mlp"),
                            scale=1.0 / math.sqrt(cfg.d_model))
    return p


def router_topk(logits, k: int):
    """fp32 softmax over experts, take top-k, renormalise. -> (weights, idx)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, idx = jax.lax.top_k(probs, k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, -1, keepdims=True), 1e-9)
    return probs, weights, idx


def _positions_within_expert(e_flat, num_experts: int):
    """Rank of each assignment within its expert (stable arrival order)."""
    nk = e_flat.shape[0]
    order = jnp.argsort(e_flat, stable=True)
    sorted_e = e_flat[order]
    seg_start = jnp.searchsorted(sorted_e, jnp.arange(num_experts))
    pos_sorted = jnp.arange(nk) - seg_start[sorted_e]
    return jnp.zeros((nk,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))


def apply_moe(p, cfg, x, *, capacity_factor: float | None = None):
    """x: (B, S, D) -> (y, aux_loss).

    Two dispatch paths:
      * expert-parallel ``shard_map`` (default on a mesh with experts
        sharded): local scatter into per-shard (E, C_loc, D) buffers, each
        model shard computes only its experts, partial outputs psum over
        'model'.  No cross-shard scatter/gather — GSPMD's generic scatter
        handling replicates the dispatch buffers (measured +450 GiB/device
        on dbrx-132b train_4k, see EXPERIMENTS.md §Perf).
      * local XLA scatter (single device / replicated experts).
    """
    ctx = current_ctx()
    if ctx is not None and ctx.mesh is not None:
        expert_ax = ctx.rules.get("expert")
        if expert_ax is not None and cfg.moe.num_experts % \
                ctx.mesh.shape[expert_ax] == 0:
            return _apply_moe_sharded(p, cfg, x, ctx,
                                      capacity_factor=capacity_factor)
    return _apply_moe_local(p, cfg, x, capacity_factor=capacity_factor)


def _apply_moe_local(p, cfg, x, *, capacity_factor: float | None = None):
    """Single-shard dispatch (reference semantics)."""
    moe = cfg.moe
    e, k = moe.num_experts, moe.num_experts_per_tok
    b, s, d = x.shape
    n = b * s
    capf = capacity_factor or moe.capacity_factor
    cap = max(int(math.ceil(n * k / e * capf)), 2 * k)
    # round to a lane-friendly multiple
    cap = (cap + 7) // 8 * 8

    xf = x.reshape(n, d)
    logits = jnp.einsum("nd,de->ne", xf, p["router"].astype(x.dtype))
    probs, weights, idx = router_topk(logits, k)          # (n,e),(n,k),(n,k)

    e_flat = idx.reshape(-1)                               # (n*k,)
    tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)    # (n*k,)
    pos = _positions_within_expert(e_flat, e)              # (n*k,)
    keep = pos < cap
    cpos = jnp.minimum(pos, cap - 1)

    # dispatch: (E, C, D) expert-parallel buffer
    vals = xf[tok] * keep[:, None].astype(x.dtype)
    buf = jnp.zeros((e, cap, d), x.dtype).at[e_flat, cpos].add(vals)
    buf = shard_act(buf, "expert", "capacity", None)

    h = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(x.dtype))
    g = (jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(x.dtype))
         if cfg.act == "swiglu" else None)
    h = mlp_act(cfg, h, g)
    h = shard_act(h, "expert", "capacity", "mlp")
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["w_down"].astype(x.dtype))
    out_buf = shard_act(out_buf, "expert", "capacity", None)

    # combine
    contrib = out_buf[e_flat, cpos] * (
        weights.reshape(-1)[:, None] * keep[:, None]).astype(x.dtype)
    y = contrib.reshape(n, k, d).sum(axis=1).reshape(b, s, d)
    y = shard_act(y, "batch", "seq", None)

    # load-balance auxiliary loss (Switch-style)
    counts = jnp.zeros((e,), jnp.float32).at[e_flat].add(
        keep.astype(jnp.float32))
    frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
    frac_probs = probs.mean(axis=0)
    aux = e * jnp.sum(frac_tokens * frac_probs) * moe.router_aux_loss_coef
    return y, aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map dispatch
# ---------------------------------------------------------------------------


def _flat_axes(ax):
    if ax is None:
        return ()
    return ax if isinstance(ax, tuple) else (ax,)


def _apply_moe_sharded(p, cfg, x, ctx, *, capacity_factor=None):
    """Expert-parallel MoE: scatter locally per data shard, compute each
    expert only on its 'model' shard, psum partial outputs.

    Collectives per layer: all-gather of expert weights over the FSDP axis
    (+ psum of (tokens_local, D) outputs over 'model') — no distributed
    scatter/gather at all.
    """
    mesh = ctx.mesh
    rules = ctx.rules
    moe = cfg.moe
    e, k = moe.num_experts, moe.num_experts_per_tok
    b, s, d = x.shape
    capf = capacity_factor or moe.capacity_factor

    model_ax = rules.get("expert")
    batch_axes = tuple(a for a in _flat_axes(rules.get("batch"))
                       if b % max(mesh.shape[a], 1) == 0)
    # weight FSDP axis: embed rule, minus axes used elsewhere here
    fsdp_axes = tuple(a for a in _flat_axes(rules.get("embed"))
                      if a != model_ax)

    x_spec = to_pspec(("batch", None, None),
                      dict(rules) | {"batch": batch_axes or None},
                      mesh=mesh, shape=x.shape)
    w3 = ("expert", "embed", "mlp")
    specs = {
        "router": to_pspec(("embed", None), rules, mesh=mesh,
                           shape=p["router"].shape),
        "w_up": to_pspec(w3, rules, mesh=mesh, shape=p["w_up"].shape),
        "w_down": to_pspec(("expert", "mlp", "embed"), rules, mesh=mesh,
                           shape=p["w_down"].shape),
    }
    if "w_gate" in p:
        specs["w_gate"] = specs["w_up"]

    n_model = mesh.shape[model_ax]
    e_loc = e // n_model

    n_fsdp = 1
    for a in fsdp_axes:
        n_fsdp *= mesh.shape[a]

    def body(x_loc, p_loc):
        bl, sl, _ = x_loc.shape
        n = bl * sl
        cap = max(int(math.ceil(n * k / e * capf)), 2 * k)
        cap = (cap + 7) // 8 * 8

        # reassemble FSDP-sharded weights
        def gather(w, axis):
            for a in fsdp_axes:
                w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
            return w

        router = gather(p_loc["router"], 0)
        xf = x_loc.reshape(n, d)
        logits = jnp.einsum("nd,de->ne", xf, router.astype(x.dtype))
        probs, weights, idx = router_topk(logits, k)

        e_flat = idx.reshape(-1)
        tok = jnp.repeat(jnp.arange(n, dtype=jnp.int32), k)
        pos = _positions_within_expert(e_flat, e)
        keep = pos < cap
        cpos = jnp.minimum(pos, cap - 1)

        vals = xf[tok] * keep[:, None].astype(x.dtype)
        buf = jnp.zeros((e, cap, d), x.dtype).at[e_flat, cpos].add(vals)

        # this model shard computes only its own experts
        e0 = jax.lax.axis_index(model_ax) * e_loc
        buf_loc = jax.lax.dynamic_slice_in_dim(buf, e0, e_loc, axis=0)

        # Two expert-matmul schedules (see EXPERIMENTS.md §Perf, jamba
        # decode iteration):
        #  * weight-gather (training): all-gather the FSDP shard of the
        #    expert weights once; right when C >> D (dispatch buffers big).
        #  * partial-sum (decode): weights stay RESIDENT; each FSDP shard
        #    multiplies its D-slice of the dispatch buffer and the partial
        #    results are psum'd / gathered — comm ∝ C·(F+D) instead of
        #    3·D·F.  Right when C << D (a handful of tokens per step).
        #    VALID ONLY when the batch is replicated over the FSDP axis
        #    (otherwise different shards hold different tokens and the
        #    psum would mix them) — the replicated-batch decode layout.
        batch_uses_fsdp = any(a in batch_axes for a in fsdp_axes)
        use_partial = (bool(fsdp_axes) and not batch_uses_fsdp
                       and cap * n_fsdp < d)
        if use_partial:
            d_loc = d // n_fsdp
            di = jax.lax.axis_index(fsdp_axes[0])
            buf_slice = jax.lax.dynamic_slice_in_dim(
                buf_loc, di * d_loc, d_loc, axis=2)
            h = jnp.einsum("ecd,edf->ecf", buf_slice,
                           p_loc["w_up"].astype(x.dtype))
            if "w_gate" in p_loc:
                g = jnp.einsum("ecd,edf->ecf", buf_slice,
                               p_loc["w_gate"].astype(x.dtype))
                h, g = jax.lax.psum((h, g), fsdp_axes)
            else:
                h = jax.lax.psum(h, fsdp_axes)
                g = None
            h = mlp_act(cfg, h, g)
            out_part = jnp.einsum("ecf,efd->ecd", h,
                                  p_loc["w_down"].astype(x.dtype))
            out_buf = out_part
            for a in fsdp_axes:
                out_buf = jax.lax.all_gather(out_buf, a, axis=2, tiled=True)
        else:
            w_up = gather(p_loc["w_up"], 1)
            w_down = gather(p_loc["w_down"], 2)
            h = jnp.einsum("ecd,edf->ecf", buf_loc, w_up.astype(x.dtype))
            if "w_gate" in p_loc:
                g = jnp.einsum("ecd,edf->ecf", buf_loc,
                               gather(p_loc["w_gate"], 1).astype(x.dtype))
            else:
                g = None
            h = mlp_act(cfg, h, g)
            out_buf = jnp.einsum("ecf,efd->ecd", h, w_down.astype(x.dtype))

        # combine local experts' contributions, psum across expert shards
        le = e_flat - e0
        mine = (le >= 0) & (le < e_loc) & keep
        contrib = out_buf[jnp.clip(le, 0, e_loc - 1), cpos]
        contrib = contrib * (weights.reshape(-1)[:, None]
                             * mine[:, None]).astype(x.dtype)
        y = contrib.reshape(n, k, d).sum(axis=1)
        y = jax.lax.psum(y, model_ax)
        y = y.reshape(bl, sl, d)

        counts = jnp.zeros((e,), jnp.float32).at[e_flat].add(
            keep.astype(jnp.float32))
        frac_tokens = counts / jnp.maximum(counts.sum(), 1.0)
        aux = e * jnp.sum(frac_tokens * probs.mean(axis=0)) \
            * moe.router_aux_loss_coef
        # make the scalar identical on every shard so out_spec=P() holds
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y, aux

    p_vals = {k2: p[k2] for k2 in specs}
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, {k2: specs[k2] for k2 in p_vals}),
        out_specs=(x_spec, jax.sharding.PartitionSpec()),
        check_vma=False)
    y, aux = f(x, p_vals)
    return y, aux
