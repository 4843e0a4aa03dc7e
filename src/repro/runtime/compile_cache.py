"""JAX's persistent compilation cache at a place the caller can predict.

Entry-point scripts (``chip_smoke.py``, ``benchmarks/run.py``) call
``enable_compile_cache`` before their first compile; library code never
does, so importing ``repro`` leaves the cache as JAX configured it.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
other directory is set here.  Otherwise the cache lives at the fixed
``<checkout>/.jax_cache``: the path is part of the cache key, so it never
comes from a temporary name, a process id or the time.
"""
from __future__ import annotations

import os

import jax

CACHE_DIRNAME = ".jax_cache"


def enable_compile_cache(checkout: str) -> str:
    """Turn the persistent cache on for every compile and return its
    directory: ``$JAX_COMPILATION_CACHE_DIR`` or ``<checkout>/.jax_cache``."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(os.path.abspath(checkout), CACHE_DIRNAME)
        jax.config.update("jax_compilation_cache_dir", path)
    # kernels compile in well under JAX's default 1 s threshold, yet each
    # one is a cold compile on a fresh machine: keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    return path
