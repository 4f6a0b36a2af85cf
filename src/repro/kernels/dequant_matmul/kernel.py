"""Pallas TPU kernel: fused bitplane-dequant + MXU matmul.

The serving-side of the paper's idea on TPU: weights live in HBM as
HOBFLOPS bitplane codes (exactly nbits bits per weight), and each
(K_blk, N_blk) weight tile is reassembled and decoded to bf16 *in VMEM*
right before the MXU consumes it — HBM weight traffic shrinks by
16/nbits vs bf16 with no persistent dequantized copy anywhere.

Tiling: grid (M/bm, N/bn, K/bk), K innermost with output revisiting so
the f32 accumulator tile stays in VMEM.  The plane tile is
[nbits, bk, bn//32] int32 and is never reshaped in-kernel: for each
bit position j of a lane word, ``nbits`` shift-ands reassemble the
codes of columns {32*w + j} as a [bk, bn//32] tile, an exponent/
mantissa bit-assembly turns them into f32 via bitcast, and the MXU
multiplies that tile.  The kernel therefore writes its output
bit-position major, ``[32, M, N//32]``, and one XLA transpose outside
the kernel restores column order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fpformat import StorageFormat

LANE = 32


def _decode_codes(codes, sfmt: StorageFormat, scale):
    """StorageFormat codes -> f32 weights (no subnormals; code 0 == +0)."""
    frac = codes & ((1 << sfmt.w_f) - 1)
    exp = (codes >> sfmt.w_f) & ((1 << sfmt.w_e) - 1)
    sign = (codes >> (sfmt.w_e + sfmt.w_f)) & 1
    e8 = exp - sfmt.bias + 127
    bits32 = (sign << 31) | (e8 << 23) | (frac << (23 - sfmt.w_f))
    val = jax.lax.bitcast_convert_type(bits32, jnp.float32)
    return jnp.where(codes == 0, 0.0, val) * scale


def _dq_matmul_kernel(x_ref, w_ref, scale_ref, o_ref, *, sfmt):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    words = w_ref[...]                        # [nbits, bk, bw]
    x = x_ref[...].astype(jnp.float32)
    scale = scale_ref[0]

    def column(j, carry):
        # codes of columns {32*w + j}: bit b of plane b, lane j
        codes = jnp.zeros(words.shape[1:], jnp.int32)
        for b in range(words.shape[0]):
            codes = codes | (((words[b] >> j) & 1) << b)
        w = _decode_codes(codes, sfmt, scale)
        o_ref[j] += jax.lax.dot(x, w, preferred_element_type=jnp.float32)
        return carry

    jax.lax.fori_loop(0, LANE, column, 0)


def dequant_matmul_pallas(x, planes, scale, sfmt: StorageFormat,
                          *, N: int, bm: int = 128, bn: int = 4096,
                          bk: int = 256, interpret: bool = False):
    """x [M, K] f32/bf16, planes [nbits, K, N//32] int32 -> [M, N] f32.

    On a TPU the plane block's lane dimension ``bn // 32`` must be a
    multiple of 128 words or all of ``N // 32``; the default ``bn``
    covers 128 words (4096 columns)."""
    M, K = x.shape
    nbits, K2, Nw = planes.shape
    assert K2 == K and Nw * LANE == N, (planes.shape, (K, N))
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    assert M % bm == 0 and N % bn == 0 and K % bk == 0
    bw = bn // LANE
    grid = (M // bm, N // bn, K // bk)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)
    kernel = functools.partial(_dq_matmul_kernel, sfmt=sfmt)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((nbits, bk, bw),
                         lambda mi, ni, ki: (0, ki, ni)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((LANE, bm, bw),
                               lambda mi, ni, ki: (0, mi, ni)),
        out_shape=jax.ShapeDtypeStruct((LANE, M, Nw), jnp.float32),
        interpret=interpret,
    )(x, planes, scale_arr)
    # [j, m, w] -> column 32*w + j
    return jnp.transpose(out, (1, 2, 0)).reshape(M, N)
