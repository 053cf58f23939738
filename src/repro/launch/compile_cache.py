"""JAX's persistent compilation cache at one fixed place.

Entry points call :func:`enable_compile_cache` before their first compile,
so a second run of the same programs loads them instead of compiling again.
The cache key includes the directory, so it never moves: the directory named
by ``JAX_COMPILATION_CACHE_DIR`` when that is set, otherwise ``.jax_cache/``
at the repository root (git ignores it).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its fixed directory and
    return that directory."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(REPO_CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    return path
