"""JAX's persistent compilation cache for the entry points.

The jit backend compiles one program per (template, batch shape,
capacity vector), and every overflow retry may compile again; a cold
process pays all of that.  Entry points (``chip_smoke.py``,
``repro.launch.serve``, ``benchmarks/run.py``,
``examples/serve_sparql.py``) call :func:`enable_compile_cache` once at
start-up, so a second run in the same checkout reads its programs back.
The library never turns the cache on by itself.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: fixed in-checkout cache directory (``.gitignore`` lists it); the path
#: is part of the cache key, so it never depends on a temp name, pid or time
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone; otherwise the cache lives in :data:`CACHE_DIR`.  Every
    program is cached, however fast it compiled: the per-template
    programs are many and small."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
