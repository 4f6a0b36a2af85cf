"""Work of a configuration, counted from its shapes alone.

``Shapes`` takes the same builder calls as the served graph and records
the geometry of every conv.  From it: multiply-accumulates per image,
and for each conv kernel call the least bytes it moves (its operands
and result as the kernel takes them, unpadded: the input bit masks
``[P, K, NIN]``, the weight planes ``[K, NIN, ceil(M/32)]`` and the
output planes ``[NOUT, P, ceil(M/32)]``, all int32) and the vector
operations it needs (``ops_per_mac`` bitwise int32 operations for every
32 multiply-accumulates, which one int32 word carries).
"""
from __future__ import annotations

import dataclasses

from bench.reference import same_pads

LANE = 32           # multiply-accumulates carried by one int32 word


@dataclasses.dataclass(frozen=True)
class Conv:
    name: str
    batch: int
    ho: int
    wo: int
    kh: int
    kw: int
    cin: int
    cout: int

    @property
    def p(self) -> int:
        return self.batch * self.ho * self.wo

    @property
    def k(self) -> int:
        return self.kh * self.kw * self.cin

    @property
    def macs(self) -> int:
        return self.p * self.k * self.cout


class Shapes:
    """Builder that tracks NHWC shapes and records each conv."""

    input_name = "input"

    def __init__(self, in_shape):
        self.shape = {self.input_name: tuple(in_shape)}
        self.convs: list[Conv] = []

    def _out(self, src, k, stride):
        B, H, W, C = self.shape[src]
        (h0, h1), (w0, w1) = same_pads(H, k, stride), same_pads(W, k, stride)
        return B, (H + h0 + h1 - k) // stride + 1, \
            (W + w0 + w1 - k) // stride + 1, C

    def conv(self, name, src, kernels, *, stride=1, relu=False):
        kh, kw, cin, cout = kernels
        B, Ho, Wo, _ = self._out(src, kh, stride)
        self.convs.append(Conv(name, B, Ho, Wo, kh, kw, cin, cout))
        self.shape[name] = (B, Ho, Wo, cout)
        return name

    def maxpool2d(self, name, src, window, *, stride, padding):
        assert padding == "SAME"
        self.shape[name] = self._out(src, window, stride)
        return name

    def avgpool2d(self, name, src, window, *, stride=None,
                  padding="VALID"):
        B, H, W, C = self.shape[src]
        self.shape[name] = (B, H // window, W // window, C)
        return name

    def add(self, name, a, b):
        assert self.shape[a] == self.shape[b], (a, b)
        self.shape[name] = self.shape[a]
        return name

    def relu(self, name, src):
        self.shape[name] = self.shape[src]
        return name


def convs(model, cfg, batch: int) -> list[Conv]:
    """The conv calls of one wave of ``batch`` images."""
    g = Shapes((batch,) + tuple(cfg["input_hwc"]))
    model.build(g, cfg, model.kernel_shapes(cfg))
    return g.convs


def macs_per_image(model, cfg) -> int:
    return sum(c.macs for c in convs(model, cfg, 1))


def kernel_bytes(c: Conv, nin: int, nout: int) -> int:
    """Least bytes one MAC kernel call reads and writes."""
    mw = -(-c.cout // LANE)
    return 4 * (c.p * c.k * nin + c.k * nin * mw + nout * c.p * mw)


def kernel_ops(c: Conv, ops_per_mac: float) -> float:
    """Bitwise int32 operations one MAC kernel call needs."""
    return ops_per_mac * c.macs / LANE


def least_seconds(c: Conv, nin: int, nout: int, ops_per_mac: float,
                  vpu_ops_per_s: float, hbm_bytes_per_s: float):
    """(seconds, bound) of the chip's least time for one kernel call:
    the larger of operations over the VPU's peak and bytes over HBM's."""
    t_ops = kernel_ops(c, ops_per_mac) / vpu_ops_per_s
    t_mem = kernel_bytes(c, nin, nout) / hbm_bytes_per_s
    return (t_ops, "vpu") if t_ops >= t_mem else (t_mem, "hbm")
