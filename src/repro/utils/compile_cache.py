"""JAX's persistent compilation cache, placed for the entry points.

The fused superstep takes tens of seconds to compile, and a fresh
process compiles it again unless the program is found in a persistent
cache. Entry points (``chip_smoke.py``, ``examples/
surface_reconstruction.py``, ``benchmarks/run.py``) call
:func:`enable_compile_cache` first thing; importing the library never
touches the cache.
"""
from __future__ import annotations

import os

import jax

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the
    cache there and nothing else is set. Otherwise the cache goes to
    the fixed ``<repo>/.jax_cache``: a directory that moved between
    runs would never be found again. Call before the first compile —
    JAX decides once per process whether the cache is in use.
    """
    path = os.environ.get(ENV_DIR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
