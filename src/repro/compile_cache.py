"""Compiler settings for this repo's entry points.

Scripts (``chip_smoke.py``, the examples, ``benchmarks/run.py``) call
:func:`enable_compile_cache` once, before their first compile, so a
second run reuses the compiled programs of the first.  Library modules
never call it, and the tests leave the cache off.

The directory is fixed: JAX keys cache entries by the program, and a
directory that moved between runs would never hit.

Everything that runs gate netlists on the CPU (``tests/conftest.py``,
the examples, the ``benchmarks/`` CLIs) calls
:func:`use_classic_cpu_emitters` before JAX starts a backend, so every
CPU run compiles with the same code generator.
"""
from __future__ import annotations

import os
import pathlib

import jax
from jax._src import xla_bridge

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"
CPU_CODEGEN_FLAG = "--xla_cpu_use_fusion_emitters=false"


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


def use_classic_cpu_emitters() -> None:
    """Compile CPU programs with XLA's classic emitters.

    With XLA:CPU's MLIR fusion emitters some gate netlists (thousands of
    chained bitwise ops) compile about three times slower and then run
    for minutes: a 4x8x32 hobflops9e matmul (4-deep MAC chain) compiles
    in 47 s and runs for more than 5 minutes.  The classic emitters
    compile it in 14 s and run it in milliseconds, to the same bits.
    The flag touches only the CPU backend.

    XLA reads ``XLA_FLAGS`` when the first backend starts, so this must
    run before that; afterwards it raises rather than do nothing."""
    flags = os.environ.get("XLA_FLAGS", "")
    if CPU_CODEGEN_FLAG in flags.split():
        return
    if xla_bridge.backends_are_initialized():
        raise RuntimeError("use_classic_cpu_emitters() must run before JAX "
                           "starts a backend")
    os.environ["XLA_FLAGS"] = f"{flags} {CPU_CODEGEN_FLAG}".strip()
