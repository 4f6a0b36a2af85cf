"""JAX's persistent compilation cache for this repo's entry points.

Scripts (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile, so a
second run reuses the compiled programs of the first.  Library modules
never call it, and the tests leave the cache off.

The directory is fixed: JAX keys cache entries by the program, and a
directory that moved between runs would never hit.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed; otherwise the cache goes to ``.jax_cache`` at
    the root of the checkout (listed in ``.gitignore``)."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)
