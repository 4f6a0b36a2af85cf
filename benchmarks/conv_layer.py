"""Paper §3.4/§4: the CNN convolution layer experiment.

MobileNets' 14x14x512 feature-map stage: the pointwise Conv/s1
1x1x512x512 that dominates its MACs (and a 3x3 general conv at reduced
width), in HOBFLOPS9 bitslice arithmetic with in-format ReLU, vs the
same layer in f32 — reporting MACs/s and the quantization error.
Dimensions are scaled by --scale for CPU wall-clock sanity; the MACs/s
figure is what the paper's Figs 6/8a/9a report.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.fpformat import HOBFLOPS_FORMATS
from repro.kernels.conv2d_bitslice.ops import hobflops_conv2d
from repro.kernels.conv2d_bitslice.ref import conv2d_f32


def bench_conv(fmt_name: str = "hobflops9", hw: int = 14, cin: int = 64,
               cout: int = 64, kh: int = 1, relu: bool = True):
    fmt = HOBFLOPS_FORMATS[fmt_name]
    rng = np.random.default_rng(0)
    img = rng.standard_normal((1, hw, hw, cin)).astype(np.float32)
    ker = (rng.standard_normal((kh, kh, cin, cout)) * 0.2).astype(
        np.float32)

    fn = jax.jit(lambda a, b: hobflops_conv2d(
        a, b, fmt=fmt, relu=relu, backend="jnp"))
    out = fn(img, ker)
    jax.block_until_ready(out)
    t0 = time.time()
    out = fn(img, ker)
    jax.block_until_ready(out)
    dt = time.time() - t0

    f32 = np.asarray(conv2d_f32(img, ker))
    if relu:
        f32 = np.maximum(f32, 0)
    err = np.abs(np.asarray(out) - f32).max() / (np.abs(f32).max() + 1e-9)
    macs = img.shape[0] * hw * hw * kh * kh * cin * cout
    return {"format": fmt_name, "kh": kh, "macs_per_s": macs / dt,
            "us_per_call": dt * 1e6, "rel_err_vs_f32": float(err)}


def run(quick: bool = False):
    rows = ["name,format,macs_per_s,us_per_call,rel_err"]
    cases = [("pointwise_14x14", "hobflops9", 1, 64, 64)]
    if not quick:
        cases += [("pointwise_14x14", "hobflops8", 1, 64, 64),
                  ("conv3x3_14x14", "hobflops9", 3, 32, 32)]
    results = {}
    for name, fmt, kh, cin, cout in cases:
        r = bench_conv(fmt, 14, cin, cout, kh)
        rows.append(f"{name},{fmt},{r['macs_per_s']:.3e},"
                    f"{r['us_per_call']:.1f},{r['rel_err_vs_f32']:.4f}")
        results[(name, fmt)] = r
    return "\n".join(rows), results


if __name__ == "__main__":
    from repro.compile_cache import use_classic_cpu_emitters
    use_classic_cpu_emitters()
    print(run()[0])
