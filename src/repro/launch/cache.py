"""JAX's persistent compilation cache, at one fixed place per checkout.

The cache key includes the directory, so a path that moves between runs
never hits.  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting
and wins; otherwise the cache lives in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
