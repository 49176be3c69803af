"""JAX's persistent compilation cache, placed from outside or at a fixed path.

The entry points that compile the big programs (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` once, before the
first compile.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and nothing is set here.  Otherwise the cache goes to
``<repo>/.jax_cache``: a fixed path, because the directory is part of what
a later process must find again (never a temporary name, a process id or a
time).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = Path(__file__).resolve().parents[2]
DEFAULT_DIR = REPO_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    placed = os.environ.get(ENV, "").strip()
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
