"""Fused Pallas netlist compiler backend (DESIGN.md §12).

Lowers a whole optimized netlist — the K-step MAC chain plus its
round/relu epilogue — into a *single* Pallas kernel body: the
``_slot_schedule`` register allocation becomes an explicit in-kernel
register file of lane-word temporaries, every gate becomes one
straight-line vector bitwise op over one ``(8, 128)`` vreg tile of
(output-channel word, output pixel) pairs, and bus I/O maps onto the
kernel's block-specced refs.  ``tile_plan`` gives the tile of a
``[P, Mw]`` output plane and the share of its words that are real.

Selected as ``backend="pallas_fused"`` in ``hobflops_matmul`` /
``conv_core`` / ``NetworkGraph`` / ``ConvServeEngine``; bit-identical
to the gate-interpreter backends and the softfloat oracle.
"""
from .emitter import (STACK_MAX_DEFAULT, LoweredNetlist,
                      RegisterFileOverflow, lower_netlist)
from .kernel import (TilePlan, fused_chain_k, fused_chain_lowered,
                     fused_mac_pallas, tile_plan)

__all__ = [
    "STACK_MAX_DEFAULT", "LoweredNetlist", "RegisterFileOverflow",
    "lower_netlist", "fused_chain_lowered", "fused_mac_pallas",
    "fused_chain_k", "TilePlan", "tile_plan",
]
