"""Persistent compilation cache for the entry points.

A full-width serving run compiles every paged step before it answers a
request; JAX's persistent cache lets the next run load those programs
instead.  The entry points (``chip_smoke.py``, the serving benchmark and
examples) call :func:`enable_compile_cache` first thing; library imports
never do, so the test suite stays cache-free.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed in-checkout cache path (listed in .gitignore).  The path is part
#: of the cache key, so it never carries a temp name, a pid or a time.
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Use ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads it itself and
    nothing else is configured); otherwise point JAX at
    :data:`REPO_CACHE_DIR`.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
