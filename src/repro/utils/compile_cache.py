"""JAX's persistent compilation cache, at one fixed place per checkout.

Entry points that compile at deployment size (``chip_smoke.py``,
``benchmarks/run.py``) call :func:`enable_compile_cache` before their first
compile. Importing ``repro`` never does: a library must not choose a
process-wide cache for its caller.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["enable_compile_cache"]

# <checkout>/src/repro/utils/compile_cache.py -> <checkout>/.jax_cache
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory
    itself and no other is set here. Otherwise the cache is
    ``<checkout>/.jax_cache``: a fixed path, never one made from a temp
    name, a PID or the time, so a later process of the same checkout
    finds what an earlier one compiled.
    """
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT_CACHE)
        jax.config.update("jax_compilation_cache_dir", path)
    return path
