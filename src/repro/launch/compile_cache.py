"""JAX's persistent compilation cache, kept at one fixed place.

Entry points (``chip_smoke.py``, the examples, the benchmarks) call
:func:`use_compile_cache` from their ``main()``; importing a module never
turns the cache on. The cache directory is part of the cache key, so it is
a fixed path inside the checkout, never one derived from a temporary
directory, a pid or the time.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["CACHE_DIR", "use_compile_cache"]

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...)
CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_compile_cache() -> str:
    """Turn on the persistent compile cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here; otherwise the cache goes to :data:`CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
