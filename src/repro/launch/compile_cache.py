"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`enable_compile_cache` once, before their first
compile; importing this module changes nothing.  The cache key includes
the directory, so the path is fixed: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (JAX reads that itself), otherwise ``.jax_cache/`` at
the root of the checkout (git-ignored).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on at its fixed place; returns the
    directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
