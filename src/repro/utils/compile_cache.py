"""Persistent XLA compilation cache placement for the entry points.

Every new (mode, signature, capacity bucket) plan is its own executable, so
a cold process pays many small compiles.  JAX's persistent cache keeps them
on disk across processes.  Entry points (``chip_smoke.py``,
``launch/serve.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once at start-up; importing the library never
touches the cache.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: Used when ``JAX_COMPILATION_CACHE_DIR`` is not set: a fixed directory
#: inside the checkout (gitignored).  The path is part of the cache key, so
#: it must not move between runs.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already reads it, and
    the cache stays there.  Otherwise it goes to :data:`DEFAULT_CACHE_DIR`.
    Every executable is cached, however quick its compile.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
