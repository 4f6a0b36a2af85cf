"""The paper's own experiment, grown to real network topology.

Two demos, both computed end-to-end in HOBFLOPS bitslice arithmetic
with activations resident in the plane domain (one encode at the
input, one decode at the output — DESIGN.md §8-§9):

* the original MobileNets-style linear stack (3x3 conv + two pointwise
  convs, ReLU between) through :class:`HobflopsNetwork`;
* a graph topology through :class:`NetworkGraph`: 3x3 conv -> 2x2
  maxpool -> residual pointwise block (skip merged by an in-domain
  ``build_add``) -> strided 3x3 downsample at a *higher* per-layer
  precision (the paper's mixed-precision prototyping pitch) -> 2x2
  avgpool head (add-tree + ``build_scale``, no divider).

The same graphs chained through per-layer f32 boundaries and the
word-parallel softfloat oracles are bit-exact — run with ``--check``.

Run: PYTHONPATH=src python examples/mobilenet_conv.py [--fmt hobflops9]
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import (enable_compile_cache,
                                  use_classic_cpu_emitters)
from repro.core.fpformat import HOBFLOPS_FORMATS
from repro.kernels.conv2d_bitslice.network import (ConvLayerSpec,
                                                   HobflopsNetwork,
                                                   NetworkGraph)
from repro.kernels.conv2d_bitslice.ref import conv2d_f32


def run_linear_stack(args, fmt, rng):
    img = rng.standard_normal((1, args.hw, args.hw, args.cin)) \
        .astype(np.float32)
    shapes = [(3, 3, args.cin, args.width),
              (1, 1, args.width, args.width),
              (1, 1, args.width, args.width)]
    kernels = [(rng.standard_normal(s) * 0.2).astype(np.float32)
               for s in shapes]
    net = HobflopsNetwork([ConvLayerSpec(k, fmt, relu=True)
                           for k in kernels])

    t0 = time.time()
    out = np.asarray(net(img))
    dt = time.time() - t0

    f32 = img
    for k in kernels:
        f32 = np.maximum(np.asarray(conv2d_f32(f32, k)), 0.0)
    print(f"{len(kernels)}-layer stack @ {args.hw}x{args.hw}x{args.cin} "
          f"in {args.fmt} (bitslice-resident, incl. compile): {dt:.2f}s")
    print(f"  MACs: {net.macs(img.shape):,}  (1 activation encode, "
          f"1 decode, {len(kernels) - 1} in-domain casts)")
    print(f"  rel err vs f32 conv+relu chain: "
          f"{np.abs(out - f32).max() / np.abs(f32).max():.4f}")
    if args.check:
        rt = np.asarray(net.run_roundtrip(img))
        assert (out == rt).all(), "resident != per-layer roundtrip"
        print("  bit-exact vs per-layer decode/re-encode path: OK")


def run_residual_graph(args, fmt, rng):
    """Residual + strided-downsample + pooled-head topology, mixing the
    base format with a higher-precision late layer."""
    from repro.core.fpformat import FPFormat
    hi = FPFormat(fmt.w_e, fmt.w_f + 2)    # always above the body fmt
    c = args.cin
    img = rng.standard_normal((1, args.hw, args.hw, c)) \
        .astype(np.float32)

    def k(*shape, s=0.3):
        return (rng.standard_normal(shape) * s).astype(np.float32)

    g = NetworkGraph(fmt)
    c1 = g.conv("c1", g.input_name, k(3, 3, c, args.width), relu=True)
    p1 = g.maxpool2d("p1", c1, window=2)
    c2 = g.conv("c2", p1, k(1, 1, args.width, args.width), relu=True)
    c3 = g.conv("c3", c2, k(1, 1, args.width, args.width))
    res = g.relu("r", g.add("res", c3, p1))     # skip merged in-domain
    d = g.conv("d", res, k(3, 3, args.width, args.width), hi, stride=2)
    g.output(g.avgpool2d("head", d, window=2))

    t0 = time.time()
    out = np.asarray(g.run(img))
    dt = time.time() - t0
    shapes = g.shape_plan(img.shape)
    fmts = g.format_plan()
    print(f"\nresidual_pool graph @ {args.hw}x{args.hw}x{c} "
          f"({args.fmt} body, {fmts['d']} downsample) "
          f"(bitslice-resident, incl. compile): {dt:.2f}s")
    print(f"  MACs: {g.macs(img.shape):,}  out {shapes['head']}")
    print("  nodes: " + " -> ".join(
        f"{name}[{node.kind},{fmts[name]}]"
        for name, node in g._nodes.items()))
    if args.check:
        rt = np.asarray(g.run_roundtrip(img))
        assert (out == rt).all(), "graph resident != per-layer oracle"
        print("  bit-exact vs per-layer f32-boundary oracle: OK")


def main():
    use_classic_cpu_emitters()
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fmt", default="hobflops9",
                    choices=sorted(HOBFLOPS_FORMATS))
    ap.add_argument("--hw", type=int, default=14)
    ap.add_argument("--cin", type=int, default=16)
    ap.add_argument("--width", type=int, default=16,
                    help="channel width of the stack")
    ap.add_argument("--check", action="store_true",
                    help="verify bit-exactness vs the per-layer path")
    args = ap.parse_args()
    fmt = HOBFLOPS_FORMATS[args.fmt]
    rng = np.random.default_rng(0)
    run_linear_stack(args, fmt, rng)
    run_residual_graph(args, fmt, rng)


if __name__ == "__main__":
    main()
