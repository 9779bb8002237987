"""JAX's persistent compilation cache for the launchers.

A cache key includes the cache's own path, so the directory must not move
between runs: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it,
otherwise one fixed directory inside the checkout (``<repo>/.jax_cache``,
ignored by git).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at ``$JAX_COMPILATION_CACHE_DIR``
    or, when that is unset, at :data:`REPO_CACHE_DIR`. Returns the directory.
    Call before the first compile."""
    path = os.environ.get(ENV_VAR) or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
