"""Quickstart: the HOBFLOPS flow end to end in one minute.

1. Pick a custom FP format (here HOBFLOPS9 = e5m3, MS-FP9-shaped).
2. Generate the gate-level MAC circuit (the in-repo FloPoCo analogue).
3. Technology-map it against the four cell libraries and compare gate
   counts (the paper's synthesis-area experiment).
4. Run a GEMM through the bitslice-parallel MAC and compare against
   both the exact-semantics oracle and plain f32.

Run: PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

import numpy as np

from repro.compile_cache import use_classic_cpu_emitters
from repro.core.fpcore import build_mac
from repro.core.fpformat import HOBFLOPS_FORMATS
from repro.core.opt import CELL_LIBS, tech_map
from repro.kernels.bitslice_mac.ops import hobflops_matmul
from repro.kernels.bitslice_mac.ref import hobflops_matmul_f64


def main():
    use_classic_cpu_emitters()
    fmt = HOBFLOPS_FORMATS["hobflops9"]
    print(f"format: hobflops9 = {fmt} "
          f"({fmt.nbits} bits incl. FloPoCo exception field)")

    g = build_mac(fmt)
    print(f"\nMAC circuit: {g.live_gate_count()} raw gates, "
          f"depth {g.depth()}")
    print("tech-mapped gate counts (paper Table 1 libraries + TPU):")
    for lib in ("avx2", "neon", "avx512", "tpu_vpu"):
        mapped = tech_map(g, CELL_LIBS[lib]())
        print(f"  {lib:8s}: {mapped.live_gate_count():4d} ops "
              f"({mapped.op_histogram()})")

    rng = np.random.default_rng(0)
    P, C, M = 8, 16, 64
    a = rng.standard_normal((P, C)).astype(np.float32)
    b = rng.standard_normal((C, M)).astype(np.float32)

    out = np.asarray(hobflops_matmul(a, b, fmt=fmt, backend="jnp"))
    oracle = hobflops_matmul_f64(a, b, fmt)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    print(f"\nGEMM [{P}x{C}] @ [{C}x{M}] in bitslice HOBFLOPS9:")
    print(f"  bit-exact vs oracle : "
          f"{np.array_equal(out, oracle)}")
    print(f"  max |err| vs f64    : {np.abs(out - exact).max():.4f} "
          f"(9-bit arithmetic quantization)")
    print(f"  f64 magnitude scale : {np.abs(exact).max():.4f}")


if __name__ == "__main__":
    main()
