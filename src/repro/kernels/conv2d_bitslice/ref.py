"""Oracles for the HOBFLOPS convolution."""
from __future__ import annotations

import numpy as np

from repro.core import softfloat as sf
from repro.core.fpformat import RNE, FPFormat
from repro.kernels.bitslice_mac.ref import hobflops_matmul_ref


def conv2d_f32(images, kernels, stride: int = 1, padding: str = "SAME"):
    """Plain float conv oracle (numpy, NHWC x HWIO -> NHWC), at full
    float32 precision on every backend (a TPU would otherwise multiply
    in bfloat16 passes)."""
    import jax
    import jax.numpy as jnp
    out = jax.lax.conv_general_dilated(
        jnp.asarray(images), jnp.asarray(kernels),
        window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST)
    return np.asarray(out)


def hobflops_conv2d_ref(images, kernels, fmt: FPFormat, stride: int = 1,
                        padding: str = "SAME", extended: bool = False,
                        rounding: str = RNE, relu: bool = False):
    """Sequential HOBFLOPS conv oracle via im2col + code-level MAC."""
    from repro.kernels.conv2d_bitslice.ops import im2col
    kh, kw, C, M = kernels.shape
    patches = np.asarray(im2col(images, kh, kw, stride, padding),
                         np.float64)
    B, Ho, Wo, K = patches.shape
    ic = sf.encode(patches.reshape(-1, K), fmt, rounding)
    wc = sf.encode(np.asarray(kernels, np.float64).reshape(K, M), fmt,
                   rounding)
    out_codes = hobflops_matmul_ref(ic, wc, fmt, extended, rounding)
    fmt_out = fmt.mult_out(extended)
    vals = sf.decode(out_codes, fmt_out)
    if relu:
        vals = np.maximum(vals, 0.0)
    return vals.reshape(B, Ho, Wo, M)
