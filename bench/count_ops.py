#!/usr/bin/env python3
"""Bitwise operations per multiply-accumulate of the fused MAC kernel.

Counts the int32 vector operations (``and``, ``or``, ``xor``, ``not``)
that one chain step of the program's lowered MAC netlist emits, for the
chain depth the kernel uses, divided by the depth.  The output bus is
assembled by a plain stack here, so only gate operations count.  The
configurations keep the result, measured once, as ``ops_per_mac``: the
roofline of the kernel is taken against that fixed work, so a later
netlist with fewer gates reads as a faster kernel.

    python bench/count_ops.py hobflops9 hobflops16
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

BITWISE = ("and", "or", "xor", "not")


def jaxpr_ops(jaxpr) -> int:
    n = 0
    for e in jaxpr.eqns:
        if e.primitive.name in BITWISE:
            n += 1
        for p in e.params.values():
            inner = getattr(p, "jaxpr", None)
            if inner is not None:
                n += jaxpr_ops(getattr(inner, "jaxpr", inner))
    return n


def lowered_ops(lowered, nin: int, nout: int, k: int) -> int:
    """Bitwise operations one call of a lowered chain netlist emits."""
    import jax
    import jax.numpy as jnp
    args = {"acc": jnp.zeros((nout, 1, 1), jnp.int32)}
    for j in range(k):
        args[f"x{j}"] = jnp.zeros((nin, 1, 1), jnp.int32)
        args[f"y{j}"] = jnp.zeros((nin, 1, 1), jnp.int32)
    return jaxpr_ops(jax.make_jaxpr(lambda a: lowered(**a))(args).jaxpr)


def ops_per_mac(fmt_name: str) -> tuple[float, int]:
    """(operations per MAC, chain depth) of the kernel in ``fmt_name``."""
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from repro.core.pallas_backend import fused_chain_k, fused_chain_lowered
    from repro.kernels.conv2d_bitslice.ops import derive_blocks
    fmt = HOBFLOPS_FORMATS[fmt_name]
    k = fused_chain_k(fmt, False, derive_blocks(8, 64, 32)["c_unroll"])
    low = fused_chain_lowered(fmt, k, False, "rne", stack_max=1 << 30)
    n = lowered_ops(low, fmt.nbits, fmt.mult_out().nbits, k)
    return n / k, k


if __name__ == "__main__":
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    for name in sys.argv[1:] or ["hobflops9", "hobflops16"]:
        per, k = ops_per_mac(name)
        print(f"{name}: chain depth {k}, {per} bitwise ops per MAC")
