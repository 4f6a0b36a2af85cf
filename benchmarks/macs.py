"""Paper Figs 6/8a/9a: MACs/second — HOBFLOPS bitslice-parallel vs
SoftFP-style word-parallel emulation vs native float.

The paper's machines are Neon/AVX2/AVX512 CPUs; here both contenders
are XLA-compiled on the host CPU backend, which preserves the paper's
*comparison* (bitslice-parallel vs integer-word emulation of the same
custom format) while the TPU numbers come from the §Roofline dry-run.
Inputs are pre-transformed (codes / bit planes), matching the paper's
"IFM and Kernel data pre-transformed to HOBFLOPS" methodology.

Three bitslice variants are measured per format to track the perf
trajectory (recorded in BENCH_macs.json by ``benchmarks/run.py``):

* ``seed``          — one MAC netlist per channel step (c_unroll=1),
                      the repo's original hot path (the gate
                      interpreter backend).
* ``chain{K}``      — the fused K-step MAC chain netlist advancing K
                      channels per step (fewer gates/MAC + fewer scan
                      steps; DESIGN.md §3).
* ``pallas_fused``  — the fused compiler backend (DESIGN.md §12): the
                      whole chain lowered to one register-file Pallas
                      kernel with the fusion-shaped bus assembly.

Every format row carries the full column set (seed / chain / fused /
speedups) plus per-format ``vs_native_f32`` / ``vs_softfp16`` ratios
so regressions read at a glance.  ``python -m benchmarks.macs --smoke``
is the CI backend-parity gate: both backends run one small workload
and the process fails on any bit mismatch.
"""
from __future__ import annotations

import functools
import time

import jax
import numpy as np

from repro.core import softfloat as sf
from repro.core.fpformat import HOBFLOPS_FORMATS, RNE, RTZ, FPFormat
from repro.core.pallas_backend import fused_chain_k, fused_mac_pallas
from repro.kernels.bitslice_mac.ops import _bitslice_mac_jnp, encode_inputs

# Workload: P output pixels x C channels x M kernels (paper Fig. 5).
P_, C_, M_ = 16, 32, 512
CHAIN_K = 4


def _time(fn, *args, iters: int = 3, reps: int = 5):
    """Best-of-reps mean over iters (robust against scheduler noise)."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / iters)
    return best


def _workload(fmt: FPFormat, rounding: str):
    rng = np.random.default_rng(0)
    i = rng.standard_normal((P_, C_)).astype(np.float32)
    w = rng.standard_normal((C_, M_)).astype(np.float32)
    return encode_inputs(i, w, fmt, rounding, p_block=P_,
                         m_block=M_ // 32, c_block=C_)


def bench_bitslice(fmt: FPFormat, rounding: str = RNE,
                   extended: bool = False, c_unroll: int = 1):
    i_masks, w_planes = _workload(fmt, rounding)
    fn = jax.jit(lambda a, b: _bitslice_mac_jnp(
        a, b, fmt=fmt, extended=extended, rounding=rounding,
        c_unroll=c_unroll))
    dt = _time(fn, i_masks, w_planes)
    return (P_ * C_ * M_) / dt, dt


def bench_fused(fmt: FPFormat, rounding: str = RNE,
                extended: bool = False, c_unroll: int = CHAIN_K):
    """The pallas_fused backend on the same workload; c_unroll is
    resolved through the backend's own chain-depth policy."""
    i_masks, w_planes = _workload(fmt, rounding)
    fn = jax.jit(functools.partial(
        fused_mac_pallas, fmt=fmt, extended=extended, rounding=rounding,
        p_block=P_, m_block=M_ // 32, c_block=C_, c_unroll=c_unroll,
        interpret=True))
    dt = _time(fn, i_masks, w_planes)
    return (P_ * C_ * M_) / dt, dt


def bench_softfp(fmt: FPFormat, rounding: str = RNE,
                 extended: bool = False):
    """Word-parallel integer-op FP emulation (the SoftFP analogue) over
    the same MAC count."""
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    fmt_out = fmt.mult_out(extended)
    ic = sf.encode(rng.standard_normal((P_, C_)), fmt)
    wc = sf.encode(rng.standard_normal((C_, M_)), fmt)
    icj = jnp.asarray(ic, jnp.int32)
    wcj = jnp.asarray(wc, jnp.int32)

    def mac_all(i_codes, w_codes):
        acc0 = jnp.zeros((P_, M_), jnp.int32)

        def step(acc, cw):
            col, wrow = cw
            x = jnp.broadcast_to(col[:, None], (P_, M_))
            y = jnp.broadcast_to(wrow[None, :], (P_, M_))
            return sf.fp_mac(x, y, acc, fmt, fmt_out, rounding, jnp), None

        acc, _ = jax.lax.scan(step, acc0,
                              (jnp.moveaxis(i_codes, 1, 0), w_codes))
        return acc

    fn = jax.jit(mac_all)
    dt = _time(fn, icj, wcj)
    return (P_ * C_ * M_) / dt, dt


def bench_native_f32():
    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    i = jnp.asarray(rng.standard_normal((P_, C_)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((C_, M_)), jnp.float32)
    fn = jax.jit(lambda a, b: a @ b)
    dt = _time(fn, i, w)
    return (P_ * C_ * M_) / dt, dt


FORMATS_FULL = ["hobflops8", "hobflops9", "hobflops10", "hobflops11",
                "hobflops12", "hobflops14", "hobflops16"]


def _bench_format(name: str, fmt: FPFormat, rounding: str,
                  extended: bool, f32_rate: float, sf_rate: float,
                  rows: list) -> dict:
    """The full column set for one (format, rounding, extended) row —
    every benchmarked format gets the same columns (the seed report
    left extended rows with chain-only numbers)."""
    label = name + ("e" if extended else "")
    seed_rate, seed_dt = bench_bitslice(fmt, rounding, extended,
                                        c_unroll=1)
    chain_rate, chain_dt = bench_bitslice(fmt, rounding, extended,
                                          c_unroll=CHAIN_K)
    fused_k = fused_chain_k(fmt, extended, CHAIN_K)
    fused_rate, fused_dt = bench_fused(fmt, rounding, extended)
    rows.append(f"hobflops_bitslice_seed,{label},{rounding},"
                f"{seed_rate:.3e},{seed_dt*1e6:.1f}")
    rows.append(f"hobflops_bitslice_chain{CHAIN_K},{label},"
                f"{rounding},{chain_rate:.3e},{chain_dt*1e6:.1f}")
    rows.append(f"hobflops_pallas_fused,{label},{rounding},"
                f"{fused_rate:.3e},{fused_dt*1e6:.1f}")
    best = max(seed_rate, chain_rate, fused_rate)
    return {
        "seed_macs_per_s": seed_rate,
        f"chain{CHAIN_K}_macs_per_s": chain_rate,
        "speedup_vs_seed": chain_rate / seed_rate,
        "pallas_fused_macs_per_s": fused_rate,
        "fused_chain_k": fused_k,
        "fused_speedup_vs_interpreter": fused_rate / seed_rate,
        "vs_native_f32": best / f32_rate,
        "vs_softfp16": best / sf_rate,
    }


def run(quick: bool = False):
    formats = ["hobflops8", "hobflops9", "hobflops16"] if quick \
        else FORMATS_FULL
    rows = ["impl,format,rounding,macs_per_s,us_per_call"]
    results = {"workload": {"P": P_, "C": C_, "M": M_,
                            "macs": P_ * C_ * M_},
               "chain_k": CHAIN_K, "formats": {}}
    f32_rate, f32_dt = bench_native_f32()
    rows.append(f"native_f32,f32,-,{f32_rate:.3e},{f32_dt*1e6:.1f}")
    results["native_f32_macs_per_s"] = f32_rate
    sf_rate, sf_dt = bench_softfp(HOBFLOPS_FORMATS["hobflops16"])
    rows.append(f"softfp_word,hobflops16,rne,{sf_rate:.3e},"
                f"{sf_dt*1e6:.1f}")
    results["softfp16_macs_per_s"] = sf_rate
    for name in formats:
        fmt = HOBFLOPS_FORMATS[name]
        per_fmt = results["formats"].setdefault(name, {})
        for rounding in ((RNE,) if quick else (RNE, RTZ)):
            per_fmt[rounding] = _bench_format(name, fmt, rounding, False,
                                              f32_rate, sf_rate, rows)
    for name in (["hobflops9"] if quick else ["hobflops8", "hobflops9",
                                              "hobflops16"]):
        results["formats"].setdefault(name + "e", {})["rne"] = \
            _bench_format(name, HOBFLOPS_FORMATS[name], RNE, True,
                          f32_rate, sf_rate, rows)
    return "\n".join(rows), results


# ---------------------------------------------------------------------------
# CI backend-parity smoke
# ---------------------------------------------------------------------------
def smoke() -> bool:
    """Both backends on one small workload, compared bit-for-bit on
    the raw OFM planes — the CI ``backend-parity`` gate.  Covers the
    plain-stack (hobflops8) and one-hot (hobflops16) assembly paths.
    Returns True on exact agreement."""
    ok = True
    for name in ("hobflops8", "hobflops16"):
        fmt = HOBFLOPS_FORMATS[name]
        i_masks, w_planes = _workload(fmt, RNE)
        ku = fused_chain_k(fmt, False, CHAIN_K)
        ref = np.asarray(jax.jit(functools.partial(
            _bitslice_mac_jnp, fmt=fmt, extended=False, rounding=RNE,
            c_unroll=ku))(i_masks, w_planes))
        got = np.asarray(jax.jit(functools.partial(
            fused_mac_pallas, fmt=fmt, extended=False, rounding=RNE,
            p_block=P_, m_block=M_ // 32, c_block=C_, c_unroll=ku,
            interpret=True))(i_masks, w_planes))
        same = np.array_equal(ref, got)
        ok &= same
        print(f"smoke {name}: jnp vs pallas_fused "
              f"{'MATCH' if same else 'MISMATCH'} "
              f"(planes {ref.shape}, chain_k={ku})")
    return ok


if __name__ == "__main__":
    from repro.compile_cache import use_classic_cpu_emitters
    use_classic_cpu_emitters()
    import sys

    if "--smoke" in sys.argv:
        sys.exit(0 if smoke() else 1)
    text, _ = run("--quick" in sys.argv)
    print(text)
