"""The paper's CNN convolution on the bitslice-parallel HOBFLOPS MAC.

Convolution is lowered to the verified bitslice GEMM by im2col: IFM
patches [B*Ho*Wo, kh*kw*C] against kernels [kh*kw*C, M] (the paper's
Fig. 5 layout with LANES of kernels per bitslice word).  ReLU runs *in
the HOBFLOPS domain* as one bitwise op per plane: clearing every plane
where the sign plane is set maps negative values to the canonical +0
code (exc=00) — activation for free inside the bitslice pipeline.

The layer is split into explicit stages so multi-layer networks stay in
the bitslice domain between layers — the "data stays in HOBFLOPS format
between layers" flow of paper §3.4, realized end-to-end by
``conv2d_bitslice.network.HobflopsNetwork`` (DESIGN.md §8):

* :func:`encode_activations`   — f32 NHWC -> :class:`BitsliceActivation`
* :func:`conv_core`            — activation x ConvWeights -> activation
                                 (plane-domain im2col + bitslice MAC
                                 + in-domain ReLU)
* :func:`cast_activations`     — accumulator-format planes -> next
                                 layer's operand format, via the
                                 optimized ``build_cast`` netlist
* :func:`decode_activations`   — activation -> f32 NHWC

``hobflops_conv2d`` composes encode/conv_core/decode for the one-layer
case and is bit-exact to the seed implementation.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings

import jax
import jax.numpy as jnp

from repro.core import softfloat as sf
from repro.core.bitslice import (BitsliceActivation, pack_planes,
                                 unpack_planes, window_gather_planes)
from repro.core.fpformat import EXC_INF, RNE, FPFormat
from repro.core.pallas_backend import fused_mac_pallas
from repro.kernels.bitslice_mac.kernel import (add_netlist_fn,
                                               bitslice_mac_pallas,
                                               cast_netlist_fn,
                                               max_netlist_fn,
                                               scale_netlist_fn)
from repro.kernels.bitslice_mac.ops import (LANE, _bitslice_mac_jnp,
                                            _pad_to, encode_weight_planes)


def _conv_pad(H: int, W: int, kh: int, kw: int, stride: int,
              padding: str) -> tuple[int, int]:
    """Total (pad_h, pad_w) applied by :func:`im2col`."""
    if padding == "SAME":
        return (max((-(-H // stride) - 1) * stride + kh - H, 0),
                max((-(-W // stride) - 1) * stride + kw - W, 0))
    return 0, 0


def conv_out_hw(H: int, W: int, kh: int, kw: int, stride: int = 1,
                padding: str = "SAME") -> tuple[int, int]:
    """Output spatial dims of :func:`im2col` (exact, incl. clamped
    SAME padding) — used for launch-parameter derivation and the
    network runner's shape plan."""
    pad_h, pad_w = _conv_pad(H, W, kh, kw, stride, padding)
    return ((H + pad_h - kh) // stride + 1,
            (W + pad_w - kw) // stride + 1)


def im2col(images, kh: int, kw: int, stride: int = 1,
           padding: str = "SAME"):
    """[B, H, W, C] -> patches [B, Ho, Wo, kh*kw*C]."""
    B, H, W, C = images.shape
    pad_h, pad_w = _conv_pad(H, W, kh, kw, stride, padding)
    x = jnp.pad(images, ((0, 0), (pad_h // 2, pad_h - pad_h // 2),
                         (pad_w // 2, pad_w - pad_w // 2), (0, 0)))
    Ho = (x.shape[1] - kh) // stride + 1
    Wo = (x.shape[2] - kw) // stride + 1
    cols = []
    for i in range(kh):
        for j in range(kw):
            cols.append(jax.lax.slice(
                x, (0, i, j, 0),
                (B, i + (Ho - 1) * stride + 1, j + (Wo - 1) * stride + 1,
                 C), (1, stride, stride, 1)))
    return jnp.concatenate(cols, axis=-1).reshape(B, Ho, Wo, kh * kw * C)


def hobflops_relu_planes(planes, fmt: FPFormat):
    """OFM bit planes [NOUT, ...] -> ReLU'd planes.  One ANDN per plane.

    Semantics (pinned by an exhaustive test against the word-parallel
    ``softfloat.fp_relu`` oracle): every code whose *sign bit* is set —
    negative normals, -0, -inf, and any non-canonical sign-set NaN —
    becomes the canonical all-zero +0 code (exc=00); every sign-clear
    code passes through unchanged.  In particular -inf maps to +0 (not
    to a saturated finite value), and NaN propagates iff it is the
    canonical sign-clear NaN the datapaths emit.  This is the
    ``max(x, +0)`` of the FloPoCo encoding up to the NaN convention:
    a true FP max would also map sign-set NaN to NaN, but the datapaths
    never produce one, so the 1-gate-per-plane mask is used instead of
    a ~100-gate ``build_max`` against a +0 constant.
    """
    sign = planes[fmt.sign_off]
    keep = ~sign
    return planes & keep[None]


# ---------------------------------------------------------------------------
# Pre-encoded conv weights (encode static kernels once, reuse per call)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class ConvWeights:
    """Conv kernels pre-encoded to HOBFLOPS bit planes.

    ``planes`` is ``[kh*kw*cin, NIN, Mw]`` int32 (reduction axis in
    im2col (i, j, c) order, output channels packed along int32 lanes).
    Registered as a JAX pytree — the geometry and format ride in the
    static treedef, so a ConvWeights passes through ``jax.jit``.
    """
    planes: "jnp.ndarray"
    kh: int
    kw: int
    cin: int
    cout: int
    fmt: FPFormat

    def tree_flatten(self):
        return ((self.planes,),
                (self.kh, self.kw, self.cin, self.cout, self.fmt))

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], *aux)


jax.tree_util.register_pytree_node(
    ConvWeights, ConvWeights.tree_flatten, ConvWeights.tree_unflatten)


def encode_conv_weights(kernels, fmt: FPFormat,
                        rounding: str = RNE) -> ConvWeights:
    """f32 [kh,kw,C,M] -> :class:`ConvWeights` (encode + bitslice once).

    The planes carry minimal padding (M up to the next lane-word
    multiple only); launch-time block padding happens in
    :func:`conv_core`, so one encoding serves any block configuration.
    """
    kh, kw, C, M = kernels.shape
    planes = encode_weight_planes(jnp.asarray(kernels).reshape(kh * kw * C,
                                                               M),
                                  fmt, rounding, c_block=1, m_block=1)
    return ConvWeights(planes, kh, kw, C, M, fmt)


# ---------------------------------------------------------------------------
# Pipeline stages: encode / im2col-in-planes / conv_core / cast / decode
# ---------------------------------------------------------------------------
def encode_activations(images, fmt: FPFormat, rounding: str = RNE,
                       p_block: int = 8) -> BitsliceActivation:
    """f32 [B,H,W,C] -> bitslice activation (the pipeline's single
    entry encode)."""
    B, H, W, C = images.shape
    codes = sf.encode_jnp(jnp.asarray(images).reshape(B * H * W, C),
                          fmt, rounding)
    codes = _pad_to(codes, min(p_block, B * H * W), 0)
    planes = pack_planes(codes, fmt.nbits)     # pads C to a lane word
    return BitsliceActivation(planes, fmt, (B, H, W, C))


def decode_activations(act: BitsliceActivation):
    """Bitslice activation -> f32 [B,H,W,C] (the single exit decode)."""
    B, H, W, C = act.shape
    codes = unpack_planes(act.planes)          # [P, Mw*LANE]
    vals = sf.decode_jnp(codes, act.fmt)
    return vals[:B * H * W, :C].reshape(B, H, W, C)


def cast_activations(act: BitsliceActivation, dst_fmt: FPFormat,
                     rounding: str = RNE) -> BitsliceActivation:
    """Re-round an activation into ``dst_fmt`` without leaving the
    bitslice domain: the optimized ``build_cast`` netlist runs as a few
    dozen bitwise ops over the plane array.  Bit-exact to
    decode -> f32 -> encode (``softfloat.fp_cast``; tests verify)."""
    if act.fmt == dst_fmt:
        return act
    fn, _ = cast_netlist_fn(act.fmt, dst_fmt, rounding)
    out = fn(x=act.planes)["out"]
    out = jnp.broadcast_to(out, (dst_fmt.nbits,) + act.planes.shape[1:])
    return BitsliceActivation(out, dst_fmt, act.shape)


def activation_patch_masks(act: BitsliceActivation, kh: int, kw: int,
                           stride: int = 1, padding: str = "SAME"):
    """Plane-domain im2col: gather layer-(n+1) IFM patches directly
    from layer-n output planes.

    Expands the channel-lane-packed planes to per-(pixel, channel) 0/-1
    broadcast masks (pure shift/mask ops — no f32 materialization),
    restores the NHWC spatial structure, and gathers kh x kw patches in
    the mask domain.  SAME padding inserts all-zero masks == the +0
    code, the MAC identity.  Returns ``(i_masks [B*Ho*Wo, kh*kw*C, NIN],
    (Ho, Wo))``.
    """
    nb = act.nbits
    B, H, W, C = act.shape
    shifts = jnp.arange(LANE, dtype=jnp.int32)
    bits = (act.planes[:, :, :, None] >> shifts) & 1   # [nb, P, Mw, LANE]
    masks = -bits.reshape(nb, bits.shape[1], -1)[:, :B * H * W, :C]
    masks = jnp.moveaxis(masks, 0, -1)                 # [BHW, C, nb]
    masks = masks.reshape(B, H, W, C * nb)
    pat = im2col(masks, kh, kw, stride, padding)
    _, Ho, Wo, _ = pat.shape
    return pat.reshape(B * Ho * Wo, kh * kw * C, nb), (Ho, Wo)


# ---------------------------------------------------------------------------
# Plane-domain elementwise / pooling ops (the graph runner's node kinds)
# ---------------------------------------------------------------------------
def relu_activations(act: BitsliceActivation) -> BitsliceActivation:
    """In-domain ReLU as a standalone graph node (one ANDN per plane;
    see :func:`hobflops_relu_planes` for the pinned semantics)."""
    return BitsliceActivation(hobflops_relu_planes(act.planes, act.fmt),
                              act.fmt, act.shape)


def _align_rows(a, b):
    """Zero-pad the shorter of two plane arrays along the row axis so
    elementwise netlists can combine activations whose P padding
    differs (zero rows are the +0 code — identity for add, and beyond
    every logical pixel for max)."""
    P = max(a.shape[1], b.shape[1])
    return _pad_to(a, P, 1), _pad_to(b, P, 1)


def add_activations(a: BitsliceActivation, b: BitsliceActivation,
                    fmt: FPFormat | None = None,
                    rounding: str = RNE) -> BitsliceActivation:
    """Elementwise FP add of two activations in the plane domain — the
    residual-merge node.  Branches whose formats differ are first cast
    (``cast_activations``, a no-op on matching formats) to ``fmt``,
    which defaults to the first operand's format; the sum is computed
    by the optimized ``build_add`` netlist at that format."""
    assert a.shape == b.shape, (a.shape, b.shape)
    tgt = fmt or a.fmt
    a = cast_activations(a, tgt, rounding)
    b = cast_activations(b, tgt, rounding)
    pa, pb = _align_rows(a.planes, b.planes)
    fn, _ = add_netlist_fn(tgt, rounding)
    out = fn(x=pa, y=pb)["out"]
    out = jnp.broadcast_to(out, (tgt.nbits,) + pa.shape[1:])
    return BitsliceActivation(out, tgt, a.shape)


def _fold_pairwise(items, combine):
    """Balanced pairwise reduction (the 'add-tree' order); both the
    resident plane path and the word-parallel oracle fold windows with
    this exact shape, so they stay bit-identical even though FP add is
    not associative."""
    items = list(items)
    while len(items) > 1:
        nxt = [combine(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


def _pool_geometry(act: BitsliceActivation, window, stride, padding):
    kh, kw = (window, window) if isinstance(window, int) else window
    stride = stride or kh
    B, H, W, C = act.shape
    pad_h, pad_w = _conv_pad(H, W, kh, kw, stride, padding)
    return kh, kw, stride, pad_h, pad_w


def neg_inf_code(fmt: FPFormat) -> int:
    """The canonical -inf code word — the max identity, used to fill
    SAME-padding slots of a plane-domain maxpool."""
    return (1 << fmt.sign_off) | (EXC_INF << fmt.exc_off)


def maxpool2d_activations(act: BitsliceActivation, window=2,
                          stride: int | None = None,
                          padding: str = "VALID") -> BitsliceActivation:
    """Max pooling entirely inside the bitslice domain.

    Windows are gathered by pure row selection
    (:func:`~repro.core.bitslice.window_gather_planes`; channels stay
    lane-packed) and folded pairwise through the optimized ``build_max``
    netlist — FP compare/select with the :func:`softfloat.fp_max`
    semantics (NaN propagates, -inf loses to everything).  SAME padding
    fills with -inf, the max identity; ``stride`` defaults to the
    window size (non-overlapping pooling)."""
    kh, kw, stride, pad_h, pad_w = _pool_geometry(act, window, stride,
                                                  padding)
    wins, (Ho, Wo) = window_gather_planes(
        act.planes, act.shape, kh, kw, stride, pad_h, pad_w,
        fill_code=neg_inf_code(act.fmt))
    fn, _ = max_netlist_fn(act.fmt)
    nb = act.fmt.nbits

    def combine(x, y):
        return jnp.broadcast_to(fn(x=x, y=y)["out"], (nb,) + x.shape[1:])

    out = _fold_pairwise(list(wins), combine)
    B, _, _, C = act.shape
    return BitsliceActivation(out, act.fmt, (B, Ho, Wo, C))


def avgpool2d_activations(act: BitsliceActivation, window=2,
                          stride: int | None = None,
                          padding: str = "VALID",
                          rounding: str = RNE) -> BitsliceActivation:
    """Average pooling in the bitslice domain: a pairwise ``build_add``
    tree over the window followed by one ``build_scale`` (multiply by
    ``2**-log2(window area)``) — no divider anywhere, so the window
    area must be a power of two.  SAME padding fills with +0 (the add
    identity) and still divides by the full window area
    (count-include-pad semantics); ``stride`` defaults to the window
    size."""
    kh, kw, stride, pad_h, pad_w = _pool_geometry(act, window, stride,
                                                  padding)
    area = kh * kw
    assert area & (area - 1) == 0, \
        f"avgpool window area must be a power of two, got {kh}x{kw}"
    wins, (Ho, Wo) = window_gather_planes(
        act.planes, act.shape, kh, kw, stride, pad_h, pad_w, fill_code=0)
    fn, _ = add_netlist_fn(act.fmt, rounding)
    nb = act.fmt.nbits

    def combine(x, y):
        return jnp.broadcast_to(fn(x=x, y=y)["out"], (nb,) + x.shape[1:])

    summed = _fold_pairwise(list(wins), combine)
    sfn, _ = scale_netlist_fn(act.fmt, area.bit_length() - 1)
    out = jnp.broadcast_to(sfn(x=summed)["out"], summed.shape)
    B, _, _, C = act.shape
    return BitsliceActivation(out, act.fmt, (B, Ho, Wo, C))


def derive_blocks(P: int, K: int, M: int, *, p_block: int | None = None,
                  m_block: int | None = None, c_block: int | None = None,
                  c_unroll: int | None = None) -> dict:
    """Launch parameters for a [P, K] @ [K, M] bitslice GEMM.

    ``p_block`` and ``m_block`` tile the gate-interpreter Pallas kernel
    (``bitslice_mac_pallas``) and pad the ``jnp`` backend's operands: 8
    sublanes of output pixels per tile, up to 128 int32 lane words of
    kernels (never padding M past the next lane-word multiple).  The
    fused kernel takes neither and tiles P and M from the shapes
    (``pallas_backend.tile_plan``).  Every backend takes ``c_block``
    channels resident in VMEM per grid step (up to 64) and ``c_unroll``
    chained channels per netlist call (default 4).  Every value is
    clamped to the problem size; explicit arguments win (the autotune
    sweep passes candidates through here).  See DESIGN.md §5.
    """
    m_words = -(-M // LANE)
    blocks = {
        "p_block": min(p_block or 8, P),
        "m_block": min(m_block or 128, m_words),
        "c_block": min(c_block or 64, K),
        "c_unroll": c_unroll or 4,
    }
    blocks["c_unroll"] = max(1, min(blocks["c_unroll"], blocks["c_block"]))
    while blocks["c_block"] % blocks["c_unroll"]:
        blocks["c_unroll"] -= 1
    return blocks


def conv_core(act: BitsliceActivation, weights: ConvWeights, *,
              stride: int = 1, padding: str = "SAME",
              extended: bool = False, rounding: str = RNE,
              relu: bool = False, backend: str = "jnp",
              interpret: bool = False, p_block: int | None = None,
              m_block: int | None = None, c_block: int | None = None,
              c_unroll: int | None = None) -> BitsliceActivation:
    """One conv layer entirely inside the bitslice domain.

    Consumes an activation in the layer's operand format, performs the
    plane-domain im2col + bitslice MAC (+ in-domain ReLU), and returns
    the OFM activation in the accumulator format
    ``weights.fmt.mult_out(extended)`` — ready to be cast to the next
    layer's operand format by :func:`cast_activations` without touching
    float32.
    """
    assert act.fmt == weights.fmt, (act.fmt, weights.fmt)
    assert act.shape[3] == weights.cin, (act.shape, weights.cin)
    i_masks, (Ho, Wo) = activation_patch_masks(
        act, weights.kh, weights.kw, stride, padding)
    B = act.shape[0]
    P, K, M = B * Ho * Wo, weights.kh * weights.kw * weights.cin, \
        weights.cout
    blk = derive_blocks(P, K, M, p_block=p_block, m_block=m_block,
                        c_block=c_block, c_unroll=c_unroll)
    if backend == "pallas_fused":
        # The fused kernel picks its own P and M tile from the shapes
        # (``tile_plan``) and pads P, M and C itself; it absorbs the ReLU
        # epilogue (two in-kernel ops on the final C step) — no post-hoc
        # hobflops_relu_planes pass, the whole layer is one pallas_call.
        if p_block is not None or m_block is not None:
            raise ValueError("the pallas_fused backend tiles P and M from "
                             "the shapes; p_block/m_block do not apply")
        out = fused_mac_pallas(i_masks, weights.planes, fmt=weights.fmt,
                               extended=extended, rounding=rounding,
                               relu=relu, interpret=interpret,
                               c_block=blk["c_block"],
                               c_unroll=blk["c_unroll"])
        return BitsliceActivation(out, weights.fmt.mult_out(extended),
                                  (B, Ho, Wo, M))
    i_masks = _pad_to(_pad_to(i_masks, blk["p_block"], 0),
                      blk["c_block"], 1)
    w_planes = _pad_to(_pad_to(weights.planes, blk["c_block"], 0),
                       blk["m_block"], 2)
    if backend == "pallas":
        out = bitslice_mac_pallas(i_masks, w_planes, fmt=weights.fmt,
                                  extended=extended, rounding=rounding,
                                  interpret=interpret, **blk)
    else:
        out = _bitslice_mac_jnp(i_masks, w_planes, fmt=weights.fmt,
                                extended=extended, rounding=rounding,
                                c_unroll=blk["c_unroll"])
    fmt_out = weights.fmt.mult_out(extended)
    if relu:
        out = hobflops_relu_planes(out, fmt_out)
    return BitsliceActivation(out, fmt_out, (B, Ho, Wo, M))


@functools.partial(jax.jit, static_argnames=(
    "fmt", "kh", "kw", "stride", "padding", "extended", "rounding",
    "relu", "backend", "interpret", "p_block", "m_block", "c_block",
    "c_unroll"))
def hobflops_conv2d(images, kernels, *, fmt: FPFormat, stride: int = 1,
                    padding: str = "SAME", extended: bool = False,
                    rounding: str = RNE, relu: bool = False,
                    backend: str = "jnp", interpret: bool = False,
                    kh: int | None = None, kw: int | None = None,
                    p_block: int | None = None, m_block: int | None = None,
                    c_block: int | None = None, c_unroll: int | None = None):
    """images [B,H,W,C] f32, kernels [kh,kw,C,M] f32 (or a pre-encoded
    :class:`ConvWeights`) -> [B,Ho,Wo,M] f32 computed entirely in
    HOBFLOPS bitslice arithmetic.

    This is the one-layer composition encode -> conv_core -> decode.
    Multi-layer networks should use
    :class:`repro.kernels.conv2d_bitslice.network.HobflopsNetwork`,
    which keeps the interior boundaries in the bitslice domain.

    Block sizes / ``c_unroll`` default to shape-derived values
    (:func:`derive_blocks`) and are exposed for autotuning
    (:func:`tune_conv_blocks`)."""
    if not isinstance(kernels, ConvWeights):
        kernels = encode_conv_weights(kernels, fmt, rounding)
    assert kernels.fmt == fmt, (kernels.fmt, fmt)
    act = encode_activations(images, fmt, rounding)
    out = conv_core(act, kernels, stride=stride, padding=padding,
                    extended=extended, rounding=rounding, relu=relu,
                    backend=backend, interpret=interpret,
                    p_block=p_block, m_block=m_block, c_block=c_block,
                    c_unroll=c_unroll)
    return decode_activations(out)


# Errors that mean "this block-size candidate cannot launch" (shape /
# tiling asserts, Mosaic lowering limits, XLA runtime rejections).
# Deliberately NOT BaseException: KeyboardInterrupt and SystemExit
# propagate out of the sweep immediately.
_LAUNCH_ERRORS = (ValueError, TypeError, AssertionError,
                  NotImplementedError, IndexError, RuntimeError)


def default_tune_candidates(backend: str = "jnp") -> list[dict]:
    """Backend-aware candidate set for :func:`tune_conv_blocks`.

    The gate-interpreter backends sweep the full c_unroll x m_block
    cross.  The fused backend sweeps ``c_unroll`` alone: its kernel
    tiles P and M from the shapes (``tile_plan``), and it drops
    ``c_unroll=8``, since its win comes from the single-kernel emission
    rather than chain depth, wide formats are clamped to ``k=1`` anyway
    (``fused_chain_k``), and every extra chain depth is another
    multi-minute XLA compile in the sweep.
    """
    if backend == "pallas_fused":
        return [{"c_unroll": u} for u in (1, 2, 4)]
    return [{"c_unroll": u, "m_block": m}
            for u in (1, 2, 4, 8) for m in (8, 32, 128)]


def tune_conv_blocks(images, kernels, *, fmt: FPFormat,
                     backend: str = "jnp", interpret: bool = False,
                     candidates=None, iters: int = 2, **conv_kw):
    """Small sweep helper: time ``hobflops_conv2d`` over block-size /
    ``c_unroll`` candidates and return ``(best_blocks, results)``.

    ``candidates`` is an iterable of dicts with any of
    ``p_block/m_block/c_block/c_unroll`` set (missing keys fall back to
    the derived defaults); by default a c_unroll x m_block cross sweep.
    ``results`` maps the *resolved* (post-clamp) parameter tuple to
    seconds/call — candidates that clamp to the same launch config are
    timed once.  Only launch-relevant errors (``_LAUNCH_ERRORS``) mark
    a candidate as failed — interrupts re-raise immediately.  Each
    skipped candidate emits a ``RuntimeWarning`` naming it and the
    first line of what the compiler refused, and if
    every candidate fails the final ``RuntimeError`` names the last
    failing candidate dict and its error.
    """
    if candidates is None:
        candidates = default_tune_candidates(backend)
    if isinstance(kernels, ConvWeights):
        khh, kww, C, M = (kernels.kh, kernels.kw, kernels.cin,
                          kernels.cout)
    else:
        khh, kww, C, M = kernels.shape
    B, H, W, _ = images.shape
    # Resolve through the same clamping the launch will apply so
    # equivalent candidates dedupe — with the exact strided Ho*Wo patch
    # count, not the unstrided B*H*W (which could clamp differently).
    Ho, Wo = conv_out_hw(H, W, khh, kww, conv_kw.get("stride", 1),
                         conv_kw.get("padding", "SAME"))
    results: dict[tuple, float] = {}
    best, best_dt = None, float("inf")
    last_err, last_cand = None, None
    for cand in candidates:
        key = tuple(sorted(derive_blocks(B * Ho * Wo, khh * kww * C, M,
                                         **cand).items()))
        if key in results:
            continue
        run = lambda: jax.block_until_ready(hobflops_conv2d(
            images, kernels, fmt=fmt, backend=backend,
            interpret=interpret, **cand, **conv_kw))
        try:
            run()                                   # compile + warm
            t0 = time.perf_counter()
            for _ in range(iters):
                run()
            dt = (time.perf_counter() - t0) / iters
        except _LAUNCH_ERRORS as e:                 # unlaunchable combo
            last_err, last_cand = e, dict(cand)
            first = (str(e).strip().splitlines() or [""])[0]
            warnings.warn(
                f"tune_conv_blocks: skipping candidate {cand!r}, which "
                f"cannot launch: {type(e).__name__}: {first}",
                RuntimeWarning, stacklevel=2)
            continue
        results[key] = dt
        if dt < best_dt:
            best, best_dt = dict(cand), dt
    if best is None:
        raise RuntimeError(
            "tune_conv_blocks: no candidate launched; last failing "
            f"candidate {last_cand!r} raised "
            f"{type(last_err).__name__}: {last_err}") from last_err
    return best, results
