"""JAX's persistent compilation cache, placed where a later run finds it.

The cache key includes the cache path, so the directory must not move
between runs: it is `$JAX_COMPILATION_CACHE_DIR` when that is set (JAX
reads the variable itself), and otherwise `<checkout>/.jax_cache`, which
`.gitignore` lists.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
