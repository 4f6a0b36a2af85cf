"""Multi-layer pipeline benchmark: bitslice-resident vs per-layer
decode/re-encode (paper §3.4's "data stays in HOBFLOPS format between
layers", DESIGN.md §8).

Workload: a MobileNets-style pointwise stack (the paper's Fig. 5 layer,
depth-chained; channel width scaled for CPU wall-clock like
``conv_layer.py``).  Three contenders over identical arithmetic:

* ``resident``     — :class:`HobflopsNetwork`: one activation encode,
                     one decode, bitwise format casts + plane-domain
                     im2col at every interior boundary; weights
                     pre-encoded once.
* ``roundtrip``    — the pre-PR per-layer path: chained
                     ``hobflops_conv2d`` calls with f32 kernels, paying
                     activation decode/re-encode *and* weight
                     re-encoding at every layer.  The headline
                     ``speedup_vs_roundtrip`` is against this (the
                     trajectory baseline: what callers paid before the
                     resident pipeline existed).
* ``roundtrip_preencoded`` — per-layer calls with ``ConvWeights``:
                     isolates the activation-residency saving alone
                     (``speedup_vs_preencoded``) from the weight
                     pre-encoding saving, which per-layer callers can
                     also get via ``hobflops_conv2d(ConvWeights)``.

All three produce bit-identical outputs (tests assert it).  Timing is
best-of-reps, interleaved rep-by-rep, to reject scheduler noise on
shared CPUs.

A second workload, ``residual_pool``, exercises the graph runner
(DESIGN.md §9): a residual block with in-domain max/avg pooling and a
strided downsample, measured resident vs the per-layer f32-boundary
oracle path (``NetworkGraph.run_roundtrip``) and emitted as the
``residual_pool`` section of ``BENCH_network.json``.  Note the graph
workload is only ~4 convs deep: the entry ``pack_planes`` cost the
resident path pays once (the per-layer path never packs planes — its
convs go straight from f32 to broadcast masks) is amortized over far
fewer layers than in the 8-deep stack, so expect ~parity here on CPU
versus the clear resident win on the deep stack.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from repro.core.fpformat import HOBFLOPS_FORMATS
from repro.kernels.conv2d_bitslice.network import (ConvLayerSpec,
                                                   HobflopsNetwork,
                                                   NetworkGraph)
from repro.kernels.conv2d_bitslice.ops import hobflops_conv2d

# Workload: depth x (1x1, C->C) convs on a HW x HW feature map.
HW_, C_, DEPTH_, KH_ = 14, 8, 8, 1
# residual_pool workload: graph topology feature-map side / channels.
G_HW_, G_C_ = 12, 8


def _time_all(fns, iters: int = 20, reps: int = 8):
    """Best-of-reps for several contenders, *interleaved* rep-by-rep so
    scheduler noise on shared CPUs hits every contender equally."""
    for fn in fns:
        jax.block_until_ready(fn())
    best = [float("inf")] * len(fns)
    for _ in range(reps):
        for i, fn in enumerate(fns):
            t0 = time.perf_counter()
            for _ in range(iters):
                out = fn()
            jax.block_until_ready(out)
            best[i] = min(best[i], (time.perf_counter() - t0) / iters)
    return best


def build_stack(fmt_name: str, hw: int = HW_, c: int = C_,
                depth: int = DEPTH_, kh: int = KH_, seed: int = 0):
    """Returns (images, f32 kernel list, HobflopsNetwork)."""
    fmt = HOBFLOPS_FORMATS[fmt_name]
    rng = np.random.default_rng(seed)
    img = rng.standard_normal((1, hw, hw, c)).astype(np.float32)
    kernels = [(rng.standard_normal((kh, kh, c, c)) * 0.3)
               .astype(np.float32) for _ in range(depth)]
    net = HobflopsNetwork([ConvLayerSpec(k, fmt, relu=True)
                           for k in kernels])
    return img, kernels, net


def bench_network(fmt_name: str, hw: int = HW_, c: int = C_,
                  depth: int = DEPTH_, kh: int = KH_,
                  iters: int = 20, reps: int = 8, stack=None) -> dict:
    img, kernels, net = stack or build_stack(fmt_name, hw, c, depth, kh)
    fmt = HOBFLOPS_FORMATS[fmt_name]
    macs = net.macs(img.shape)

    def roundtrip():
        x = img
        for k in kernels:
            x = hobflops_conv2d(x, k, fmt=fmt, relu=True, backend="jnp")
        return x

    def roundtrip_preencoded():
        x = img
        for w in net.weights:
            x = hobflops_conv2d(x, w, fmt=fmt, relu=True, backend="jnp")
        return x

    dt_res, dt_rt, dt_pre = _time_all(
        [lambda: net(img), roundtrip, roundtrip_preencoded], iters, reps)
    return {
        "format": fmt_name, "depth": depth, "hw": hw, "c": c, "kh": kh,
        "macs": macs,
        "resident_macs_per_s": macs / dt_res,
        "roundtrip_macs_per_s": macs / dt_rt,
        "roundtrip_preencoded_macs_per_s": macs / dt_pre,
        "resident_us_per_call": dt_res * 1e6,
        "roundtrip_us_per_call": dt_rt * 1e6,
        "roundtrip_preencoded_us_per_call": dt_pre * 1e6,
        "speedup_vs_roundtrip": dt_rt / dt_res,
        "speedup_vs_preencoded": dt_pre / dt_res,
    }


def build_residual_pool(fmt_name: str, hw: int = G_HW_, c: int = G_C_,
                        seed: int = 0):
    """The graph-runner workload (DESIGN.md §9): 3x3 conv -> maxpool ->
    residual pointwise block -> strided 3x3 downsample -> 2x2 avgpool
    head.  Returns (images, NetworkGraph)."""
    fmt = HOBFLOPS_FORMATS[fmt_name]
    rng = np.random.default_rng(seed)
    img = rng.standard_normal((1, hw, hw, c)).astype(np.float32)

    def k(*shape, s=0.3):
        return (rng.standard_normal(shape) * s).astype(np.float32)

    g = NetworkGraph(fmt)
    c1 = g.conv("c1", g.input_name, k(3, 3, c, c), relu=True)
    p1 = g.maxpool2d("p1", c1, window=2)
    c2 = g.conv("c2", p1, k(1, 1, c, c), relu=True)
    c3 = g.conv("c3", c2, k(1, 1, c, c))
    res = g.relu("r", g.add("res", c3, p1))
    d = g.conv("d", res, k(3, 3, c, c), stride=2)
    g.output(g.avgpool2d("head", d, window=2))
    return img, g


def bench_residual_pool(fmt_name: str, hw: int = G_HW_, c: int = G_C_,
                        iters: int = 20, reps: int = 8,
                        stack=None) -> dict:
    """Resident vs per-layer-oracle MACs/s for the residual_pool graph
    (in-domain pooling + residual adds vs f32 boundaries + word-parallel
    softfloat pooling at every node)."""
    img, g = stack or build_residual_pool(fmt_name, hw, c)
    macs = g.macs(img.shape)
    dt_res, dt_rt = _time_all([lambda: g.run(img),
                               lambda: g.run_roundtrip(img)], iters, reps)
    return {
        "format": fmt_name, "hw": hw, "c": c, "macs": macs,
        "resident_macs_per_s": macs / dt_res,
        "roundtrip_macs_per_s": macs / dt_rt,
        "resident_us_per_call": dt_res * 1e6,
        "roundtrip_us_per_call": dt_rt * 1e6,
        "speedup_vs_roundtrip": dt_rt / dt_res,
    }


def run(quick: bool = False):
    formats = ["hobflops8", "hobflops9"] if quick else \
        ["hobflops8", "hobflops9", "hobflops10", "hobflops16"]
    rows = ["impl,format,macs_per_s,us_per_call,speedup_vs_roundtrip"]
    results = {"workload": {"hw": HW_, "c": C_, "depth": DEPTH_,
                            "kh": KH_},
               "residual_pool_workload": {"hw": G_HW_, "c": G_C_},
               "formats": {}, "residual_pool": {}}
    for name in formats:
        r = bench_network(name)
        rows.append(f"network_resident,{name},"
                    f"{r['resident_macs_per_s']:.3e},"
                    f"{r['resident_us_per_call']:.1f},"
                    f"{r['speedup_vs_roundtrip']:.2f}")
        rows.append(f"network_roundtrip,{name},"
                    f"{r['roundtrip_macs_per_s']:.3e},"
                    f"{r['roundtrip_us_per_call']:.1f},1.00")
        rows.append(f"network_roundtrip_preencoded,{name},"
                    f"{r['roundtrip_preencoded_macs_per_s']:.3e},"
                    f"{r['roundtrip_preencoded_us_per_call']:.1f},"
                    f"{r['roundtrip_preencoded_macs_per_s'] / r['roundtrip_macs_per_s']:.2f}")
        results["formats"][name] = r
        gr = bench_residual_pool(name)
        rows.append(f"residual_pool_resident,{name},"
                    f"{gr['resident_macs_per_s']:.3e},"
                    f"{gr['resident_us_per_call']:.1f},"
                    f"{gr['speedup_vs_roundtrip']:.2f}")
        rows.append(f"residual_pool_roundtrip,{name},"
                    f"{gr['roundtrip_macs_per_s']:.3e},"
                    f"{gr['roundtrip_us_per_call']:.1f},1.00")
        results["residual_pool"][name] = gr
    return "\n".join(rows), results


if __name__ == "__main__":
    from repro.compile_cache import use_classic_cpu_emitters
    use_classic_cpu_emitters()
    text, _ = run()
    print(text)
