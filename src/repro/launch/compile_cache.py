"""Where JAX's persistent compilation cache lives.

The cache key includes its directory, so the directory must not move
between runs: a path built from a temp name, a pid or the time never hits.
`JAX_COMPILATION_CACHE_DIR`, when set, is read by JAX itself and wins;
otherwise the cache sits at the fixed `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Point the persistent compilation cache at its one place; returns
    the directory. Call before the first compile."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE))
    return str(REPO_CACHE)
