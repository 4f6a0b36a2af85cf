"""Plain float32 reference of a HOBFLOPS CNN, in ``jax.numpy``.

Every value is a float32, and every operation is followed by rounding
to the custom floating-point format it is computed in: round to
nearest, ties to even, no subnormals (a result below the format's
least normal flushes to +0) and overflow to infinity.  That is the
arithmetic the served program states (FloPoCo encoding, paper Table 3):

* encode: the input image and the weights round to the operand format;
* conv: each product rounds to the accumulator format, and is added to
  the running sum in im2col order (tap by tap, channel fastest), the
  sum rounding to the accumulator format after every step;
* the accumulator rounds to the next conv's operand format on entry;
* max-pool, ReLU and decode are exact; a residual add rounds once; an
  average pool adds its window pairwise, rounding each sum, then scales
  by a power of two.

A sum is rounded from its exact value: ``two_sum`` keeps the part that
the float32 addition dropped, and it decides a tie.  Products of two
operands of at most 12 significant bits are exact in float32.

This module imports nothing of the program under test.  The formats it
knows are listed in ``FORMATS`` as (exponent bits, fraction bits).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# (w_e, w_f) of the HOBFLOPS formats (paper Table 3; MS-FP8/9 are
# hobflops8/9).  A MAC in format (w_e, w_f) accumulates in (w_e, w_f+1).
FORMATS = {
    "hobflops8": (5, 2), "hobflops9": (5, 3), "hobflops10": (5, 4),
    "hobflops11": (5, 5), "hobflops12": (5, 6), "hobflops13": (5, 7),
    "hobflops14": (5, 8), "hobflops15": (5, 9), "hobflops16": (5, 10),
}


def acc_format(fmt):
    we, wf = fmt
    return we, wf + 1


def round_to(x, fmt, err=None):
    """Round float32 ``x`` to ``fmt`` = (w_e, w_f).

    ``err`` is what a float32 sum dropped (see :func:`two_sum`): the
    exact value is ``x + err``.  It matters only where ``x`` lies on a
    tie of the format, since a float32 rounding cannot cross one."""
    we, wf = fmt
    bias = (1 << (we - 1)) - 1
    tiny = 2.0 ** -bias
    huge = (2.0 - 2.0 ** -wf) * 2.0 ** ((1 << we) - 1 - bias)
    x = jnp.asarray(x, jnp.float32)
    bits = lax.bitcast_convert_type(x, jnp.int32)
    sign = bits & jnp.int32(-(1 << 31))
    mag = bits & jnp.int32(0x7FFFFFFF)
    drop = 23 - wf
    half = 1 << (drop - 1)
    low = mag & ((1 << drop) - 1)
    keep = mag >> drop
    odd = (keep & 1) == 1
    if err is None:
        tie_up = odd
    else:
        beyond = (err != 0) & ((err > 0) == (x > 0))
        tie_up = beyond | ((err == 0) & odd)
    up = (low > half) | ((low == half) & tie_up)
    r = lax.bitcast_convert_type(
        sign | ((keep + up.astype(jnp.int32)) << drop), jnp.float32)
    a = jnp.abs(r)
    r = jnp.where(a < tiny, jnp.float32(0.0), r)
    return jnp.where(a > huge, jnp.copysign(jnp.float32(jnp.inf), r), r)


def two_sum(a, b):
    """``s = fl(a + b)`` and the exact remainder ``a + b - s``."""
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def add_round(a, b, fmt):
    s, e = two_sum(a, b)
    return round_to(s, fmt, e)


def same_pads(size, k, stride):
    """(before, after) padding of a SAME window, smaller half first."""
    total = max((-(-size // stride) - 1) * stride + k - size, 0)
    return total // 2, total - total // 2


def im2col(x, kh, kw, stride):
    """[B, H, W, C] -> [B, Ho, Wo, kh*kw*C], taps in (i, j) order with
    channels fastest; zero padding (SAME)."""
    B, H, W, C = x.shape
    x = jnp.pad(x, ((0, 0), same_pads(H, kh, stride),
                    same_pads(W, kw, stride), (0, 0)))
    Ho = (x.shape[1] - kh) // stride + 1
    Wo = (x.shape[2] - kw) // stride + 1
    cols = [x[:, i:i + (Ho - 1) * stride + 1:stride,
              j:j + (Wo - 1) * stride + 1:stride, :]
            for i in range(kh) for j in range(kw)]
    return jnp.concatenate(cols, axis=-1)


@functools.partial(jax.jit, static_argnames=("stride", "relu", "fmt"))
def conv(x, kernels, *, stride, relu, fmt):
    """One conv in ``fmt``: operands round to ``fmt``, the sum runs in
    its accumulator format, one input tap at a time."""
    acc_fmt = acc_format(fmt)
    kh, kw, C, M = kernels.shape
    x = round_to(x, fmt)
    w = round_to(kernels, fmt).reshape(kh * kw * C, M)
    pat = im2col(x, kh, kw, stride)
    B, Ho, Wo, K = pat.shape
    cols = pat.reshape(B * Ho * Wo, K).T                 # [K, P]

    def step(k, acc):
        xk = lax.dynamic_index_in_dim(cols, k, keepdims=False)
        wk = lax.dynamic_index_in_dim(w, k, keepdims=False)
        prod = round_to(xk[:, None] * wk[None, :], acc_fmt)
        return add_round(acc, prod, acc_fmt)

    acc = lax.fori_loop(0, K, step,
                        jnp.zeros((B * Ho * Wo, M), jnp.float32))
    if relu:
        acc = jnp.maximum(acc, 0.0)
    return acc.reshape(B, Ho, Wo, M)


def _windows(x, k, stride, fill, same):
    """The k*k strided views of a pool window, in (i, j) order."""
    B, H, W, C = x.shape
    if same:
        x = jnp.pad(x, ((0, 0), same_pads(H, k, stride),
                        same_pads(W, k, stride), (0, 0)),
                    constant_values=fill)
    Ho = (x.shape[1] - k) // stride + 1
    Wo = (x.shape[2] - k) // stride + 1
    return [x[:, i:i + (Ho - 1) * stride + 1:stride,
              j:j + (Wo - 1) * stride + 1:stride, :]
            for i in range(k) for j in range(k)]


def _fold_pairwise(items, combine):
    items = list(items)
    while len(items) > 1:
        nxt = [combine(items[i], items[i + 1])
               for i in range(0, len(items) - 1, 2)]
        if len(items) % 2:
            nxt.append(items[-1])
        items = nxt
    return items[0]


@functools.partial(jax.jit, static_argnames=("window", "stride", "same"))
def maxpool(x, *, window, stride, same):
    return _fold_pairwise(_windows(x, window, stride, -jnp.inf, same),
                          jnp.maximum)


@functools.partial(jax.jit, static_argnames=("window", "fmt"))
def avgpool(x, *, window, fmt):
    """Non-overlapping average pool: pairwise sum of the window in
    (i, j) order, each sum rounded, then an exact scale by
    ``1 / window**2`` (a power of two)."""
    area = window * window
    assert area & (area - 1) == 0, window
    s = _fold_pairwise(_windows(x, window, window, 0.0, False),
                       lambda a, b: add_round(a, b, fmt))
    return round_to(s * jnp.float32(1.0 / area), fmt)


@functools.partial(jax.jit, static_argnames=("fmt",))
def add(a, b, *, fmt):
    return add_round(a, b, fmt)


class Reference:
    """Runs the builder calls of a configuration (``conv``,
    ``maxpool2d``, ``avgpool2d``, ``add``, ``relu``) eagerly on float32
    arrays, node by node, in the arithmetic above.  ``fmt`` is the
    operand format of every conv; the input rounds to it, and every
    other node works in the accumulator format its inputs carry."""

    input_name = "input"

    def __init__(self, images, fmt):
        self.fmt = tuple(fmt)
        self.acc = acc_format(self.fmt)
        self.v = {self.input_name: round_to(jnp.asarray(images), self.fmt)}

    def _put(self, name, val):
        self.v[name] = val
        return name

    def conv(self, name, src, kernels, *, stride=1, relu=False):
        # The operand rounding inside ``conv`` is the cast from the
        # accumulator format (or, for the stem, the encode).
        return self._put(name, conv(self.v[src], jnp.asarray(kernels),
                                    stride=stride, relu=relu,
                                    fmt=self.fmt))

    def maxpool2d(self, name, src, window, *, stride, padding):
        return self._put(name, maxpool(self.v[src], window=window,
                                       stride=stride,
                                       same=padding == "SAME"))

    def avgpool2d(self, name, src, window, *, stride=None,
                  padding="VALID"):
        assert padding == "VALID" and stride in (None, window)
        return self._put(name, avgpool(self.v[src], window=window,
                                       fmt=self.acc))

    def add(self, name, a, b):
        return self._put(name, add(self.v[a], self.v[b], fmt=self.acc))

    def relu(self, name, src):
        return self._put(name, jnp.maximum(self.v[src], 0.0))

    def output(self, name):
        return np.asarray(self.v[name])
