"""JAX's persistent compilation cache, kept where every run finds it.

The cache key includes the directory, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads the
variable itself, and no other directory is set), otherwise the fixed,
git-ignored ``.jax_cache/`` at the root of this checkout.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: the checkout root (``src/repro/launch/compile_cache.py`` -> three up)
CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
