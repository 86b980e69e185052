"""JAX persistent compilation cache for the repo's entry points.

Entry points (``main()`` of the server, the serve driver, the benchmarks
and ``chip_smoke.py``) call ``enable_compile_cache()`` once, before they
compile anything; importing a module never turns the cache on, and tests
do not call it.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

# one fixed directory inside the checkout (listed in .gitignore): the
# cache key includes the path, so a directory that moves never hits
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads the cache
    from there and nothing is changed; otherwise the cache goes to
    ``CACHE_DIR``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
