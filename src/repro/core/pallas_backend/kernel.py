"""The fused MAC-chain Pallas kernel.

One ``pl.pallas_call`` carries the whole layer: the K-step MAC chain
netlist (lowered by :mod:`.emitter` into a straight-line register-file
program), the channel reduction as an in-kernel ``fori_loop`` over
ref slices, and the ReLU epilogue as two in-kernel ops on the final
C-step — so a fused conv emits exactly one kernel in its jaxpr where
the gate-interpreter backends emit hundreds of elementwise HLO ops.

Tiles (DESIGN.md §5): every netlist value is one full ``(8, 128)``
int32 vreg of (output-channel word, output pixel) pairs, with pixels on
the 128 lanes.  :func:`tile_plan` picks the tile from ``(P, Mw)`` alone:
with ``Mw >= 8`` a tile holds 8 channel words of 128 pixels; with fewer
words, ``8 / Mw`` groups of 128 pixels are folded onto the sublanes as
well.  The C reduction is the innermost grid axis with output-block
revisiting; ``c_block`` and ``c_unroll`` are the only launch knobs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.fpcore import build_mac_chain
from repro.core.fpformat import RNE, FPFormat
from repro.core.opt import optimize_mapped

from .emitter import STACK_MAX_DEFAULT, LoweredNetlist, lower_netlist


@functools.lru_cache(maxsize=None)
def fused_chain_lowered(fmt: FPFormat, k: int, extended: bool,
                        rounding: str, lib: str = "tpu_vpu",
                        stack_max: int = STACK_MAX_DEFAULT
                        ) -> LoweredNetlist:
    """The optimized ``lib``-mapped K-step MAC chain, lowered once per
    (format, chain depth, rounding, policy) to a fused kernel body."""
    mapped = optimize_mapped(build_mac_chain(fmt, k, extended, rounding),
                             lib)
    return lower_netlist(mapped, stack_max=stack_max)


def fused_chain_k(fmt: FPFormat, extended: bool = False,
                  requested: int = 4,
                  stack_max: int = STACK_MAX_DEFAULT) -> int:
    """Chain depth the fused backend actually uses.

    Wide-accumulator formats (out bus past ``stack_max``, e.g.
    hobflops16's 19 planes) keep ``k=1``: their chain bodies grow the
    XLA compile time superlinearly (minutes at k=4) while the one-hot
    bus assembly already removes the cone-duplication that chaining
    would otherwise amortize.  Narrow formats keep the requested depth.
    """
    nout = fmt.mult_out(extended).nbits
    return 1 if nout > stack_max else max(1, requested)


ROWS = 8        # sublanes of an int32 vreg
LANES = 128     # lanes of an int32 vreg


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """How :func:`fused_mac_pallas` lays an output bit plane
    ``[P, Mw]`` onto ``(ROWS, LANES)`` int32 vregs.

    A tile's row ``r = g * words + m`` holds channel word ``m`` (of the
    tile's ``words``) of pixels ``g * lanes + l``, ``l < lanes``: the
    ``groups = ROWS // words`` pixel groups are folded onto the
    sublanes.  ``lanes`` is 128, or the whole padded P where it fits in
    one tile (a vreg still spans 128 lanes then)."""
    p: int
    mw: int
    words: int
    lanes: int

    rows = ROWS

    @property
    def groups(self) -> int:
        return ROWS // self.words

    @property
    def p_tile(self) -> int:
        """Pixels one tile holds."""
        return self.groups * self.lanes

    @property
    def n_p(self) -> int:
        return -(-self.p // self.p_tile)

    @property
    def n_m(self) -> int:
        return -(-self.mw // self.words)

    @property
    def p_pad(self) -> int:
        return self.n_p * self.p_tile

    @property
    def mw_pad(self) -> int:
        return self.n_m * self.words

    @property
    def fill(self) -> float:
        """Share of the vreg words of the plane's tiles that carry a
        real (pixel, channel word) pair."""
        return self.p * self.mw / (self.n_p * self.n_m * ROWS * LANES)


def tile_plan(P: int, Mw: int) -> TilePlan:
    """The tile of a ``[P, Mw]`` output plane: 8 channel words a tile
    where there are at least 8, else ``Mw`` rounded up to 1, 2 or 4
    words with the rest of the 8 sublanes taken by pixel groups."""
    words = ROWS if Mw >= ROWS else 1 << (Mw - 1).bit_length()
    groups = ROWS // words
    lanes = LANES if P > groups * LANES else -(-P // groups)
    return TilePlan(P, Mw, words, lanes)


def _fused_mac_kernel(i_ref, w_ref, o_ref, *, c_block: int,
                      c_unroll: int, nout: int, n_c: int, sign_off: int,
                      relu: bool, fmt: FPFormat, extended: bool,
                      rounding: str, stack_max: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        # +0.0 in FloPoCo encoding is the all-zero code word.
        o_ref[...] = jnp.zeros_like(o_ref)

    lowered = fused_chain_lowered(fmt, c_unroll, extended, rounding,
                                  stack_max=stack_max)
    acc_shape = o_ref.shape            # (NOUT, ROWS, lanes)
    assert acc_shape[0] == nout, (acc_shape, nout)
    assert c_block % c_unroll == 0, (c_block, c_unroll)
    nin = w_ref.shape[1]
    tile = (nin,) + acc_shape[1:]

    def step(s, acc):
        base = s * c_unroll
        xw = w_ref[pl.ds(base, c_unroll)]     # [c_unroll, NIN, ROWS]
        yb = i_ref[pl.ds(base, c_unroll)]     # [c_unroll, NIN, (ROWS,) L]
        kwargs = {"acc": acc}
        for j in range(c_unroll):
            # Weight words down the sublanes, mask bits along the
            # lanes: both arrive as full tiles, one vreg per bit.
            kwargs[f"x{j}"] = jnp.broadcast_to(xw[j][:, :, None], tile)
            y = yb[j]
            kwargs[f"y{j}"] = jnp.broadcast_to(
                y[:, None, :] if y.ndim == 2 else y, tile)
        out = lowered(**kwargs)["out"]
        return jnp.broadcast_to(out, acc_shape)

    o_ref[...] = jax.lax.fori_loop(0, c_block // c_unroll, step,
                                   o_ref[...])

    if relu:
        # In-kernel epilogue, only once the C reduction is complete:
        # clear every plane where the sign plane is set (the
        # hobflops_relu_planes semantics, DESIGN.md §8).
        @pl.when(ci == n_c - 1)
        def _epilogue():
            acc = o_ref[...]
            o_ref[...] = acc & ~acc[sign_off][None]


def fused_mac_pallas(i_masks, w_planes, *, fmt: FPFormat,
                     extended: bool = False, rounding: str = RNE,
                     c_block: int = 64, c_unroll: int = 4,
                     relu: bool = False, interpret: bool = False,
                     stack_max: int = STACK_MAX_DEFAULT):
    """Launch the fused MAC-chain kernel.

    Same contract as ``bitslice_mac_pallas`` (i_masks [P, C, NIN] in
    {0, -1}, w_planes [C, NIN, Mw], returns OFM planes [NOUT, P, Mw])
    plus the fused ReLU epilogue; ``c_unroll`` is additionally clamped
    through :func:`fused_chain_k`.  Bit-identical to the interpreter
    backends for every format x rounding (tests pin this), and the
    whole layer is one ``pallas_call``.

    The tile comes from ``(P, Mw)`` alone (:func:`tile_plan`).  Here the
    operands are laid out for it, P-minor: weights ``[n_m, C, NIN, 8]``
    (each tile's 8 rows of channel words), masks ``[C, NIN, P]`` (one
    row of pixels, broadcast down the sublanes in the kernel) or, with
    pixel groups folded onto the sublanes, ``[C, NIN, n_p * 8, lanes]``
    (each group's row repeated once per channel word).  The result
    tiles are put back in ``[NOUT, P, Mw]`` order.
    """
    P, C, nin = i_masks.shape
    C2, nin2, Mw = w_planes.shape
    assert (C, nin) == (C2, nin2), (i_masks.shape, w_planes.shape)
    assert nin == fmt.nbits
    fmt_out = fmt.mult_out(extended)
    nout = fmt_out.nbits
    c_block = min(c_block, C)
    c_pad = -(-C // c_block) * c_block    # +0 codes: the MAC identity
    c_unroll = fused_chain_k(fmt, extended,
                             max(1, min(c_unroll, c_block)), stack_max)
    while c_block % c_unroll:
        c_unroll -= 1

    t = tile_plan(P, Mw)
    G, L = t.groups, t.lanes
    w = jnp.pad(w_planes, ((0, c_pad - C), (0, 0), (0, t.mw_pad - Mw)))
    w = jnp.tile(w.reshape(c_pad, nin, t.n_m, t.words), (1, 1, 1, G))
    w = jnp.transpose(w, (2, 0, 1, 3))                # [n_m, C, NIN, 8]
    y = jnp.transpose(i_masks, (1, 2, 0))             # [C, NIN, P]
    y = jnp.pad(y, ((0, 0), (0, 0), (0, t.p_pad - P)))
    if G == 1:
        y_spec = pl.BlockSpec((c_block, nin, L),
                              lambda pi, mi, ci: (ci, 0, pi))
    else:
        y = jnp.broadcast_to(
            y.reshape(C, nin, t.n_p, G, 1, L),
            (C, nin, t.n_p, G, t.words, L)).reshape(C, nin, -1, L)
        y_spec = pl.BlockSpec((c_block, nin, ROWS, L),
                              lambda pi, mi, ci: (ci, 0, pi, 0))
    # C pads last, in the kernel's own layout: XLA may give a pad of
    # the [P, C, NIN] masks a layout with NIN on the lanes.
    y = jnp.pad(y, ((0, c_pad - C),) + ((0, 0),) * (y.ndim - 1))

    n_c = c_pad // c_block
    n_m = t.n_m
    kernel = functools.partial(
        _fused_mac_kernel, c_block=c_block, c_unroll=c_unroll,
        nout=nout, n_c=n_c, sign_off=fmt_out.sign_off, relu=relu,
        fmt=fmt, extended=extended, rounding=rounding,
        stack_max=stack_max)
    out = pl.pallas_call(
        kernel,
        grid=(t.n_p, n_m, n_c),
        in_specs=[
            y_spec,
            pl.BlockSpec((None, c_block, nin, ROWS),
                         lambda pi, mi, ci: (mi, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((nout, ROWS, L),
                               lambda pi, mi, ci: (0, pi * n_m + mi, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (nout, t.n_p * n_m * ROWS, L), jnp.int32),
        interpret=interpret,
    )(y, w)
    out = out.reshape(nout, t.n_p, n_m, G, t.words, L)
    out = jnp.transpose(out, (0, 1, 3, 5, 2, 4))
    return out.reshape(nout, t.p_pad, t.mw_pad)[:, :P, :Mw]
