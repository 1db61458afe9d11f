"""JAX's persistent compilation cache for the launchers.

The cache key includes its directory, so the directory is fixed: the one
``JAX_COMPILATION_CACHE_DIR`` names (JAX reads that variable itself), else
``<repo>/.jax_cache``.  Called from entry points only, never at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
