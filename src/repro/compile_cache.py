"""JAX's persistent compilation cache, set up in one place.

The entry points (``chip_smoke.py``, ``benchmarks/run.py`` and
``scripts/run_benchmarks.py``) call :func:`enable_compile_cache` from their
``main()``.  Nothing calls it at import time, so tests run without a
persistent cache.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

# Fixed, at the checkout root, and listed in .gitignore: a later run finds
# what an earlier run of the same checkout wrote.
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache: JAX reads the
    variable itself and no other directory is set here.  Otherwise the
    cache is ``.jax_cache/`` at the checkout root.  Every program is
    cached, however quickly it compiled: the serving path builds many
    small programs, and each one costs a chip run its compile again."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
