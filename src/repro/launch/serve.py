"""Serving driver: batched prefill + decode with the KV-cache/state path.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-1.6b --smoke \
      --batch 4 --prompt-len 64 --gen 32
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_arch_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models.model import build_model


def generate(api, params, prompts, *, gen: int, extra_inputs=None):
    """Greedy decode ``gen`` tokens after batched prefill.

    prompts: (B, S) int32.  Returns (B, gen) int32.
    """
    cfg = api.cfg
    b, s = prompts.shape
    batch = {"tokens": prompts}
    if extra_inputs:
        batch.update(extra_inputs)
    total = s + gen
    logits, cache = jax.jit(
        lambda p, bt: api.prefill(p, bt, cache_len=total))(params, batch)

    jstep = jax.jit(api.decode_step)
    out = []
    tok = jnp.argmax(logits[:, :, :cfg.vocab_size], axis=-1).astype(jnp.int32)
    for i in range(gen):
        out.append(tok[:, 0])
        logits, cache = jstep(params, cache, tok, jnp.int32(s + i))
        tok = jnp.argmax(logits[:, :, :cfg.vocab_size],
                         axis=-1).astype(jnp.int32)
    return jnp.stack(out, axis=1)


def main():
    """CLI entry: serve a model (prefill+decode loop) from a config id."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    args = ap.parse_args()

    use_compile_cache()
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_arch_config(args.arch))
    api = build_model(cfg, compute_dtype=jnp.float32, remat=False)
    from repro.sharding.spec import values_tree
    params = values_tree(api.init(jax.random.PRNGKey(0)))
    rng = np.random.default_rng(0)
    s_text = args.prompt_len - (cfg.num_patches if cfg.family == "vlm" else 0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, s_text)), jnp.int32)
    extra = {}
    if cfg.family == "vlm":
        extra["patches"] = jnp.asarray(
            rng.normal(0, 0.02, (args.batch, cfg.num_patches, cfg.d_model)),
            jnp.float32)
    if cfg.family == "encdec":
        extra["frames"] = jnp.asarray(
            rng.normal(0, 0.02,
                       (args.batch, cfg.encoder_seq_len, cfg.d_model)),
            jnp.float32)
    t0 = time.time()
    toks = generate(api, params, prompts, gen=args.gen, extra_inputs=extra)
    dt = time.time() - t0
    print(f"generated {toks.shape} tokens in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s)")
    print("sample:", np.asarray(toks[0][:16]))


if __name__ == "__main__":
    main()
