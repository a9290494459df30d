"""Where the entry points keep JAX's persistent compilation cache.

A compiled program is found again only under the same directory, so the
directory is either the one ``JAX_COMPILATION_CACHE_DIR`` names (JAX
reads that variable itself) or a fixed ``.jax_cache/`` at the checkout
root, never a temporary or per-run name.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory.  Call before the first compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
