"""Public API for the fused bitplane-dequant matmul."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.fpformat import StorageFormat
from repro.quant.storage import LANE, QuantizedTensor, quantize

from .kernel import dequant_matmul_pallas
from .ref import dequant_matmul_ref


def pack_weights(w, sfmt: StorageFormat) -> QuantizedTensor:
    """[K, N] float weights -> bitplane QuantizedTensor with the 2-D
    [nbits, K, N//32] layout the kernel streams (N % 32 == 0)."""
    K, N = w.shape
    assert N % LANE == 0, f"N={N} must be a multiple of {LANE}"
    qt = quantize(w, sfmt, layout="bitplane")
    data = qt.data.reshape(qt.data.shape[0], K, N // LANE)
    return QuantizedTensor(data=data, scale=qt.scale, sfmt=sfmt,
                           layout="bitplane2d", shape=(K, N))


@functools.partial(jax.jit, static_argnames=("backend", "interpret",
                                             "bm", "bn", "bk"))
def dequant_matmul(x, qt: QuantizedTensor, *, backend: str = "pallas",
                   interpret: bool = False, bm: int = 128, bn: int = 4096,
                   bk: int = 256):
    """x [M, K] @ dequant(qt [K, N]) -> [M, N] f32."""
    K, N = qt.shape
    if backend == "pallas":
        return dequant_matmul_pallas(x, qt.data, qt.scale, qt.sfmt,
                                     N=N, bm=bm, bn=bn, bk=bk,
                                     interpret=interpret)
    assert backend == "jnp"
    return dequant_matmul_ref(x, qt.data, qt.scale, qt.sfmt, N)
