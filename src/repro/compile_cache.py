"""JAX's persistent compilation cache for the repository's entry points.

Call ``enable_compile_cache()`` from a script's entry point, never at import:
the tests compile with the cache off. Where ``JAX_COMPILATION_CACHE_DIR`` is
set, JAX reads it on its own and this sets no other directory; otherwise the
cache lives in ``.jax_cache/`` at the repository root. The path is part of
the cache key, so it is fixed rather than temporary.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory. Every program
    is cached, however quickly it compiled: on a TPU the float64 solver
    programs of this repository take seconds each."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
