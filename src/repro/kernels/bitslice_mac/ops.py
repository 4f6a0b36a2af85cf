"""Public jit'd API for the HOBFLOPS bitslice MAC.

``hobflops_matmul``: float32 in / float32 out GEMM whose arithmetic is
custom-precision HOBFLOPS FP executed bitslice-parallel.  Two backends:

* ``backend="pallas"``       — the TPU kernel (``interpret=True`` on
                               CPU); the netlist is traced per grid
                               step by the gate interpreter.
* ``backend="jnp"``          — the same synthesized netlist traced as
                               plain XLA elementwise ops over full
                               arrays; used for CPU benchmarking and as
                               a portability fallback.
* ``backend="pallas_fused"`` — the fused compiler backend
                               (``repro.core.pallas_backend``,
                               DESIGN.md §12): the whole MAC chain
                               lowered to a single-``pallas_call``
                               register-file kernel with the
                               fusion-shaped bus assembly.

All produce bit-identical results; tests cross-check them and the
pure softfloat oracle in ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import softfloat as sf
from repro.core.bitslice import pack_planes, unpack_planes
from repro.core.fpformat import RNE, FPFormat
from repro.core.pallas_backend import fused_mac_pallas

from .kernel import bitslice_mac_pallas, mac_chain_netlist_fn

LANE = 32


def _pad_to(x, mult, axis):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def encode_input_masks(i_f32, fmt: FPFormat, rounding: str = RNE,
                       p_block: int = 8, c_block: int = 64):
    """float32 [P,C] -> i_masks [P',C',NIN] int32 in {0,-1} (bit
    broadcast masks), P/C zero-padded to the block multiples."""
    ic = sf.encode_jnp(i_f32, fmt, rounding)        # [P, C] int32
    ic = _pad_to(_pad_to(ic, p_block, 0), c_block, 1)
    bits = (ic[..., None] >> jnp.arange(fmt.nbits, dtype=jnp.int32)) & 1
    return -bits.astype(jnp.int32)                   # 0 / -1 masks


def encode_weight_planes(w_f32, fmt: FPFormat, rounding: str = RNE,
                         c_block: int = 1, m_block: int = 1):
    """float32 [C,M] -> w_planes [C',NIN,Mw] int32 bit planes (M packed
    along int32 lanes).  Static inference weights should be encoded
    once through this and passed to ``hobflops_matmul(w_planes=...)`` /
    ``conv2d_bitslice.encode_conv_weights`` instead of re-encoding f32
    kernels on every call.  Defaults carry minimal padding (M to the
    next lane word only) so one encoding serves any launch block
    configuration; launch-time padding happens at the call site."""
    wc = sf.encode_jnp(w_f32, fmt, rounding)        # [C, M] int32
    wc = _pad_to(_pad_to(wc, c_block, 0), m_block * LANE, 1)
    return jnp.moveaxis(pack_planes(wc, fmt.nbits), 0, 1)  # [C, NIN, Mw]


def encode_inputs(i_f32, w_f32, fmt: FPFormat, rounding: str = RNE,
                  p_block: int = 8, m_block: int = 128, c_block: int = 64):
    """float32 [P,C] x [C,M] -> (i_masks [P,C,NIN], w_planes [C,NIN,Mw]),
    both padded out to the given launch blocks."""
    return (encode_input_masks(i_f32, fmt, rounding, p_block, c_block),
            encode_weight_planes(w_f32, fmt, rounding, c_block, m_block))


@functools.partial(jax.jit, static_argnames=(
    "fmt", "extended", "rounding", "backend", "interpret", "cout",
    "p_block", "m_block", "c_block", "c_unroll"))
def hobflops_matmul(i_f32, w_f32=None, *, fmt: FPFormat,
                    w_planes=None, cout: int | None = None,
                    extended: bool = False,
                    rounding: str = RNE, backend: str = "pallas",
                    interpret: bool = False, p_block: int = 8,
                    m_block: int = 128, c_block: int = 64,
                    c_unroll: int = 4):
    """GEMM [P,C] @ [C,M] -> [P,M] float32, in HOBFLOPS arithmetic.

    Weights are given either as float32 ``w_f32`` [C,M] (encoded to bit
    planes on every call) or pre-encoded ``w_planes`` [C,NIN,Mw] from
    :func:`encode_weight_planes` (``cout`` recovers M when it is not a
    full lane-word multiple).  Inference-time callers should pre-encode.
    """
    P, C = i_f32.shape
    if w_planes is None:
        C2, M = w_f32.shape
        assert C == C2
    else:
        assert w_f32 is None, "pass either w_f32 or w_planes, not both"
        C2, nin, Mw = w_planes.shape
        assert C == C2 and nin == fmt.nbits, (w_planes.shape, fmt)
        M = cout if cout is not None else Mw * LANE
        assert M <= Mw * LANE
    # Clamp blocks to the problem so padding never exceeds one block;
    # the fused kernel pads P and M to its own tile.
    fused = backend == "pallas_fused"
    p_block = 1 if fused else max(1, min(p_block, P))
    c_block = max(1, min(c_block, C))
    m_block = 1 if fused else max(1, min(m_block, -(-M // LANE)))
    i_masks = encode_input_masks(i_f32, fmt, rounding, p_block, c_block)
    if w_planes is None:
        w_planes = encode_weight_planes(w_f32, fmt, rounding, c_block,
                                        m_block)
    else:
        w_planes = _pad_to(_pad_to(w_planes, c_block, 0), m_block, 2)
    if backend == "pallas":
        out = bitslice_mac_pallas(
            i_masks, w_planes, fmt=fmt, extended=extended,
            rounding=rounding, p_block=p_block, m_block=m_block,
            c_block=c_block, c_unroll=c_unroll, interpret=interpret)
    elif backend == "pallas_fused":
        out = fused_mac_pallas(
            i_masks, w_planes, fmt=fmt, extended=extended,
            rounding=rounding, c_block=c_block, c_unroll=c_unroll,
            interpret=interpret)
    elif backend == "jnp":
        out = _bitslice_mac_jnp(i_masks, w_planes, fmt=fmt,
                                extended=extended, rounding=rounding,
                                c_unroll=c_unroll)
    else:
        raise ValueError(backend)
    fmt_out = fmt.mult_out(extended)
    codes = unpack_planes(out)                      # [P', Mw*32]
    vals = sf.decode_jnp(codes, fmt_out)
    return vals[:P, :M]


def _bitslice_mac_jnp(i_masks, w_planes, *, fmt: FPFormat, extended: bool,
                      rounding: str, c_unroll: int = 4):
    """Chain netlist over full arrays with a scan over C/c_unroll steps
    (pure XLA path).  C is padded to a multiple of ``c_unroll`` with +0
    codes — the all-zero planes — which are the MAC identity."""
    P, C, nin = i_masks.shape
    _, _, Mw = w_planes.shape
    nout = fmt.mult_out(extended).nbits
    ku = max(1, min(c_unroll, C))
    pad = (-C) % ku
    if pad:
        i_masks = jnp.pad(i_masks, ((0, 0), (0, pad), (0, 0)))
        w_planes = jnp.pad(w_planes, ((0, pad), (0, 0), (0, 0)))
        C += pad
    fn, _ = mac_chain_netlist_fn(fmt, ku, extended, rounding)
    acc0 = jnp.zeros((nout, P, Mw), jnp.int32)
    xs = (jnp.moveaxis(i_masks, 1, 0).reshape(C // ku, ku, P, nin),
          w_planes.reshape(C // ku, ku, nin, Mw))

    def step(acc, xw):
        ib, wp = xw                        # [ku, P, NIN], [ku, NIN, Mw]
        kwargs = {}
        for j in range(ku):
            kwargs[f"x{j}"] = wp[j][:, None, :]                 # [NIN,1,Mw]
            kwargs[f"y{j}"] = jnp.transpose(ib[j], (1, 0))[:, :, None]
        out = fn(acc=acc, **kwargs)["out"]
        return jnp.broadcast_to(out, acc.shape), None

    acc, _ = jax.lax.scan(step, acc0, xs)
    return acc


def hobflops_quantize(x_f32, fmt: FPFormat, rounding: str = RNE):
    """Round-trip float32 through the HOBFLOPS format (fake-quant)."""
    return sf.decode_jnp(sf.encode_jnp(x_f32, fmt, rounding), fmt)
