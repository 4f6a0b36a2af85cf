"""Tier-1 wiring for the dev tooling: the exhaustive circuit check
script, the CPU code generator of the entry points, and the
machine-readable benchmark emission."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import COMPILE_HEAVY
from repro import compile_cache

_ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), os.pardir)
sys.path.insert(0, os.path.join(_ROOT, "scripts"))


def test_dev_check_circuits_quick(monkeypatch):
    """scripts/dev_check_circuits.py is wired into the tier-1 flow: its
    sweep runs a netlist against its oracle and returns the verdict.
    One small sweep each way; the exhaustive sweeps of every circuit
    are tests/test_circuits.py's."""
    import dev_check_circuits
    from repro.core.fpformat import FPFormat
    assert dev_check_circuits.check_scale(FPFormat(3, 2), 2)
    monkeypatch.setattr(dev_check_circuits.sf, "fp_scale",
                        lambda xs, k, fmt: xs ^ 1)
    assert not dev_check_circuits.check_scale(FPFormat(3, 2), 2)


_EXTENDED_MATMUL = """
import numpy as np
from repro.compile_cache import use_classic_cpu_emitters
use_classic_cpu_emitters()
from repro.core.fpformat import FPFormat
from repro.kernels.bitslice_mac.ops import hobflops_matmul
rng = np.random.default_rng(0)
i = rng.standard_normal((4, 8)).astype(np.float32)
w = rng.standard_normal((8, 32)).astype(np.float32)
np.asarray(hobflops_matmul(i, w, fmt=FPFormat(5, 3), extended=True,
                           backend="jnp"))
"""


def test_classic_cpu_emitters_run_extended_mac_chain_promptly():
    """Regression: under XLA:CPU's MLIR fusion emitters the compiled
    4-deep hobflops9e MAC chain ran for minutes on a 4x8x32 matmul, so
    the hobflops9e case of test_kernels.py::test_bitslice_mac_formats
    never finished.  A fresh process without the flag, calling
    use_classic_cpu_emitters() as the CPU entry points do, compiles and
    runs that matmul well inside the time limit (25 s alone)."""
    flags = os.environ.get("XLA_FLAGS", "").split()
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=" ".join(
        f for f in flags if f != compile_cache.CPU_CODEGEN_FLAG))
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(_ROOT, "src"), env.get("PYTHONPATH", "")])
    subprocess.run([sys.executable, "-c", _EXTENDED_MATMUL], env=env,
                   check=True, timeout=100)


def test_classic_cpu_emitters_refused_after_backend_start(monkeypatch):
    """The flag only works before XLA starts a backend; asked for later
    (this process has run JAX already), it raises instead of doing
    nothing.  Where the flag is already set it is a no-op."""
    import jax.numpy as jnp
    jnp.zeros(1).block_until_ready()
    monkeypatch.setenv("XLA_FLAGS", "--xla_cpu_enable_fast_math=false")
    with pytest.raises(RuntimeError, match="before JAX starts a backend"):
        compile_cache.use_classic_cpu_emitters()
    flags = f"--xla_cpu_enable_fast_math=false {compile_cache.CPU_CODEGEN_FLAG}"
    monkeypatch.setenv("XLA_FLAGS", flags)
    compile_cache.use_classic_cpu_emitters()
    assert os.environ["XLA_FLAGS"] == flags


def test_bench_json_writer(tmp_path):
    """run.py's JSON emission produces the BENCH_<section>.json layout
    future PRs read for the perf trajectory."""
    sys.path.insert(0, _ROOT)
    from benchmarks.run import _write_json
    results = {"formats": {"hobflops9": {"rne": {
        "seed_macs_per_s": 1.0, "chain4_macs_per_s": 1.6,
        "speedup_vs_seed": 1.6}}}}
    path = _write_json(str(tmp_path), "macs", results)
    assert os.path.basename(path) == "BENCH_macs.json"
    with open(path) as f:
        assert json.load(f) == results


@COMPILE_HEAVY
def test_network_bench_smoke():
    """Tier-1 smoke of the multi-layer pipeline benchmark: a tiny
    bitslice-resident stack runs, matches the per-layer roundtrip
    bit-exactly, and yields the BENCH_network.json row layout.  Two
    layers keep one interior boundary, the one place the resident path
    differs from the roundtrip; tests/test_network.py checks three."""
    sys.path.insert(0, _ROOT)
    from benchmarks.network import bench_network, build_stack
    stack = build_stack("hobflops8", hw=6, c=8, depth=2)
    img, _, net = stack
    res = np.asarray(net(img))
    assert res.shape == net.out_shape(img.shape)
    np.testing.assert_array_equal(res, np.asarray(net.run_roundtrip(img)))
    row = bench_network("hobflops8", iters=1, reps=1, stack=stack)
    for key in ("resident_macs_per_s", "roundtrip_macs_per_s",
                "speedup_vs_roundtrip", "macs"):
        assert key in row, row
    assert row["macs"] > 0


@COMPILE_HEAVY
def test_network_bench_residual_pool_smoke():
    """The benchmark's graph workload (residual + pools) runs, stays
    bit-exact to its per-layer oracle, and yields the ``residual_pool``
    row of BENCH_network.json."""
    sys.path.insert(0, _ROOT)
    from benchmarks.network import bench_residual_pool, build_residual_pool
    img, g = build_residual_pool("hobflops8", hw=8, c=4)
    np.testing.assert_array_equal(np.asarray(g.run(img)),
                                  np.asarray(g.run_roundtrip(img)))
    row = bench_residual_pool("hobflops8", iters=1, reps=1, stack=(img, g))
    for key in ("resident_macs_per_s", "roundtrip_macs_per_s",
                "speedup_vs_roundtrip", "macs"):
        assert key in row, row
    assert row["macs"] > 0


def test_serve_bench_smoke():
    """Tier-1 smoke of the lane-batched serve engine: a tiny graph
    serves 5 queued requests (one full wave + a ragged wave),
    bit-exact vs the per-request run, and reports engine stats."""
    sys.path.insert(0, _ROOT)
    from benchmarks.serve import smoke
    st = smoke()
    assert st["waves"] == 2 and st["images_served"] == 5
    assert st["runner_cache"]["misses"] >= 1
    assert 0.0 < st["mean_occupancy"] <= 1.0


def test_gates_chain_table_shape():
    """chain_table reports gates/MAC per lib with the fields the
    acceptance trajectory tracks."""
    sys.path.insert(0, _ROOT)
    from benchmarks.gates import LIBS, chain_table
    rows = chain_table(["hobflops8"], k=2)
    (row,) = rows
    for lib in LIBS:
        cell = row[lib]
        assert cell["chain_gates_per_mac"] < cell["mac_gates"]
        assert cell["saving_pct"] > 0


def test_compile_cache_placement(monkeypatch):
    """An outside JAX_COMPILATION_CACHE_DIR is left to JAX; otherwise the
    cache goes to one fixed directory at the root of the checkout.  The
    config update is intercepted: tests never turn the cache on."""
    import jax
    from repro import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    placed = compile_cache.enable_compile_cache()
    assert calls == [("jax_compilation_cache_dir", placed)]
    assert os.path.samefile(os.path.dirname(placed), _ROOT)
    assert os.path.basename(placed) == ".jax_cache"
