"""Fast CPU tests of the benchmark's own pieces.

The served program's MAC netlists take minutes to compile on the CPU,
so the runs here put the float32 reference in the program's place (at
the configuration's format, or at the format below it for the control)
and drive everything else of a run: weights and images from the seed,
the engine's admission and packing, the client's loops, the metric
readers and the comparison.  The program itself is compared with the
reference one conv at a time, at the smallest sizes.
"""
from __future__ import annotations

import json
import math
import os
import types

import numpy as np
import pytest

from bench import client, counts, system
from bench.models import resnet
from bench.reference import FORMATS, Reference
from bench.run import run_cell
from bench.trace import reduce

HERE = os.path.dirname(os.path.abspath(__file__))

TINY = {"family": "resnet", "format": "hobflops9", "backend": "jnp",
        "input_hwc": [32, 32, 3],
        "stem": {"kernel": 3, "stride": 2, "channels": 4,
                 "pool": {"window": 3, "stride": 2}},
        "stages": [4, 8], "blocks_per_stage": 1, "shortcut": "projection",
        "head": {"avgpool": 4}, "image_pool": 8,
        "check": {"sample": 8, "max_rel_gap": 1e-3}}

SPEC = {
    "workloads": [
        {"name": "tiny.offline", "config": "tiny", "traffic": "closed",
         "chips": 1},
        {"name": "tiny.poisson", "config": "tiny", "traffic": "poisson",
         "chips": 1}],
    "end_to_end": [
        {"name": "images_per_s", "unit": "images/s",
         "workloads": ["tiny.offline"]},
        {"name": "latency_p50_ms", "unit": "ms",
         "workloads": ["tiny.poisson"]},
        {"name": "latency_p95_ms", "unit": "ms",
         "workloads": ["tiny.poisson"]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": []}

MIXES = {"tiny.offline": {"loop": "closed"},
         "tiny.poisson": {"loop": "open", "arrivals": "poisson",
                          "rate_per_s": 40.0, "order_seed": 1}}


def reference_in_place(monkeypatch, fmt=None, fault=None):
    """Serve the reference (in ``fmt``, default the configuration's) in
    the program's place, with ``fault`` applied to each wave's output."""
    build = system.build

    def fake(model, cfg, seed, **kw):
        graph, kernels = build(model, cfg, seed, **kw)

        def one(image):
            ref = Reference(image[None], FORMATS[fmt or cfg["format"]])
            return ref.output(model.build(ref, cfg, kernels))[0]

        def wave(images, _weights):
            out = np.stack([one(x) for x in images])
            return fault(out) if fault else out

        graph._resident_fn = wave
        return graph, kernels

    monkeypatch.setattr(system, "build", fake)


def tiny_run(cell, seed=7, max_batch=None, **kw):
    return run_cell(cell, seed, 0.3, False, spec=SPEC, cfg=TINY,
                    mix=MIXES[cell], need_chip=False, **kw)


@pytest.mark.parametrize("cell", ["tiny.offline", "tiny.poisson"])
def test_sound_run_is_correct(monkeypatch, cell):
    reference_in_place(monkeypatch)
    res = tiny_run(cell)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert res["check"]["max_rel_gap"]["value"] == 0.0
    assert list(res)[-1] == "check"
    want = {m["name"] for m in SPEC["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) == want
    assert all(v["value"] > 0 for v in res["metrics"].values())


def _altered(out):
    out = np.array(out)
    out.flat[0] += 1.0
    return out


def _half_batch(out):
    out = np.array(out)
    out[len(out) // 2:] = 0.0
    return out


@pytest.mark.parametrize("fault", [_altered, _half_batch],
                         ids=["answer_altered", "half_batch_left_out"])
def test_fault_makes_run_incorrect(monkeypatch, fault):
    reference_in_place(monkeypatch, fault=fault)
    res = tiny_run("tiny.offline")
    assert not res["correct"]
    assert res["check"]["max_rel_gap"]["value"] > TINY["check"][
        "max_rel_gap"]


def test_control_one_format_below_is_incorrect(monkeypatch):
    reference_in_place(monkeypatch, fmt="hobflops8")
    res = tiny_run("tiny.offline")
    assert not res["correct"]


def _config(name):
    with open(os.path.join(HERE, "..", "configs", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["resnet18-imagenet-h9",
                                  "resnet20-cifar-h16"])
def test_macs_equal_network_graph(name):
    """The benchmark's count against the program's, on a graph whose
    weights are shapes only (nothing is encoded or run)."""
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from repro.kernels.conv2d_bitslice.network import NetworkGraph
    from repro.kernels.conv2d_bitslice.ops import ConvWeights
    cfg = _config(name)
    fmt = HOBFLOPS_FORMATS[cfg["format"]]
    g = NetworkGraph(fmt)
    shapes = {n: ConvWeights(None, *s, fmt)
              for n, s in resnet.kernel_shapes(cfg).items()}
    g.output(resnet.build(g, cfg, shapes))
    assert counts.macs_per_image(resnet, cfg) == \
        g.macs((1,) + tuple(cfg["input_hwc"]))


def test_resnet18_macs_match_published_trunk():
    cfg = _config("resnet18-imagenet-h9")
    assert counts.macs_per_image(resnet, cfg) == 1_813_561_344
    assert len(counts.convs(resnet, cfg, 1)) == 20


def test_op_count_equals_lowered_netlist():
    """Two counts of one lowered netlist agree: the bitwise primitives
    its trace emits, and its gate table (NOT/AND/OR/XOR one op, ANDN
    two, MUX three, LUT3 per minterm its complements, two ANDs and an
    OR), plus the one NOT that makes the all-ones word."""
    from repro.core.circuit import (OP_AND, OP_ANDN, OP_INPUT, OP_LUT3,
                                    OP_MUX, OP_NOT, OP_OR, OP_XOR)
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from repro.core.pallas_backend import fused_chain_lowered

    from bench.count_ops import lowered_ops
    fmt = HOBFLOPS_FORMATS["hobflops8"]
    low = fused_chain_lowered(fmt, 1, False, "rne", stack_max=1 << 30)
    cost = {OP_INPUT: 0, OP_NOT: 1, OP_AND: 1, OP_OR: 1, OP_XOR: 1,
            OP_ANDN: 2, OP_MUX: 3}
    table = 1
    for nid, _, _, _ in low.steps:
        n = low.graph.nodes[nid]
        if n.op == OP_LUT3:
            table += sum(3 - bin(m).count("1") + 3
                         for m in range(8) if (n.aux >> m) & 1)
        else:
            table += cost[n.op]
    assert lowered_ops(low, fmt.nbits, fmt.mult_out().nbits, 1) == table


def test_kernel_counts_at_a_tiny_size():
    c = counts.Conv("c", 2, 4, 4, 3, 3, 8, 64)
    assert c.macs == 2 * 16 * 72 * 64
    assert counts.kernel_bytes(c, 12, 13) == \
        4 * (32 * 72 * 12 + 72 * 12 * 2 + 13 * 32 * 2)
    assert counts.kernel_ops(c, 700.0) == 700.0 * c.macs / 32
    s, bound = counts.least_seconds(c, 12, 13, 700.0, 1e12, 1e12)
    assert bound == "vpu" and s == counts.kernel_ops(c, 700.0) / 1e12


def test_traffic_is_seeded():
    mix = {"loop": "open", "arrivals": "poisson", "rate_per_s": 50.0}
    a = client.arrival_times({**mix, "order_seed": 2 ** 33 + 5}, 10)
    b = client.arrival_times({**mix, "order_seed": 2 ** 33 + 5}, 10)
    c = client.arrival_times({**mix, "order_seed": 6}, 10)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    assert len(a) == 500 and a[0] == 0.0 and np.all(np.diff(a) > 0)
    # another order seed: the same gaps in another order, one load
    ga, gc = np.diff(a), np.diff(c)
    assert math.isclose(a[-1] + 1 / 50, c[-1] + 1 / 50, rel_tol=0.05)
    assert abs(np.mean(ga) - np.mean(gc)) < 0.1 / 50
    x = system.images(TINY, 2 ** 33 + 5, 3)
    assert np.array_equal(x, system.images(TINY, 2 ** 33 + 5, 3))
    assert not np.array_equal(x, system.images(TINY, 6, 3))


def test_weights_are_seeded():
    _, k1 = system.build(resnet, TINY, 2 ** 33 + 5)
    _, k2 = system.build(resnet, TINY, 2 ** 33 + 5)
    _, k3 = system.build(resnet, TINY, 2 ** 33 + 6)
    assert all(np.array_equal(k1[n], k2[n]) for n in k1)
    assert not np.array_equal(k1["stem"], k3["stem"])


@pytest.mark.parametrize("name", ["hobflops9", "hobflops16"])
def test_round_to_equals_program_encode(name):
    """The reference's rounding against the program's softfloat
    encoder, over the whole range: ties, underflow and overflow."""
    from repro.core import softfloat as sf
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from bench.reference import round_to
    fmt = HOBFLOPS_FORMATS[name]
    rng = np.random.default_rng(0)
    x = (rng.standard_normal(20000) * 2.0 ** rng.integers(-20, 20, 20000)
         ).astype(np.float32)
    ties = (1 + (2 * rng.integers(0, 1 << fmt.w_f, 2000) + 1)
            / 2.0 ** (fmt.w_f + 1)) * 2.0 ** rng.integers(-14, 15, 2000)
    x = np.concatenate([x, ties.astype(np.float32), -x[:100]])
    want = sf.decode(sf.encode(x.astype(np.float64), fmt), fmt)
    got = np.asarray(round_to(x, FORMATS[name]), np.float64)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name", ["hobflops9", "hobflops16"])
def test_reference_add_equals_program_adder(name):
    from repro.core import softfloat as sf
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from bench.reference import add_round, round_to
    fmt = HOBFLOPS_FORMATS[name].mult_out()
    rng = np.random.default_rng(1)
    a, b = (np.asarray(round_to(
        (rng.standard_normal(20000) * 2.0 ** rng.integers(-16, 14, 20000)
         ).astype(np.float32), (fmt.w_e, fmt.w_f))) for _ in range(2))
    want = sf.decode(sf.fp_add(sf.encode(a, fmt), sf.encode(b, fmt), fmt),
                     fmt)
    got = np.asarray(add_round(a, b, (fmt.w_e, fmt.w_f)), np.float64)
    assert np.array_equal(got, want)


@pytest.mark.parametrize("name,stride", [("hobflops9", 1),
                                         ("hobflops16", 2)])
def test_reference_conv_equals_program_oracle(name, stride):
    """One conv with ReLU against the program's sequential code-level
    MAC oracle, at a tiny size."""
    from repro.core.fpformat import HOBFLOPS_FORMATS
    from repro.kernels.conv2d_bitslice.ref import hobflops_conv2d_ref
    from bench.reference import conv
    rng = np.random.default_rng(2)
    x = rng.standard_normal((2, 5, 6, 3)).astype(np.float32)
    w = rng.standard_normal((3, 3, 3, 5)).astype(np.float32)
    want = hobflops_conv2d_ref(x, w, HOBFLOPS_FORMATS[name], stride,
                               relu=True)
    got = conv(x, w, stride=stride, relu=True, fmt=FORMATS[name])
    assert np.array_equal(np.asarray(got, np.float64), want)


KERNEL = 'x = s32[12,8,1] custom-call(y), custom_call_target="tpu_custom_call"'


def test_trace_reduction_by_hand():
    """A window of 100 ns: a kernel 10-40, an op 30-50 overlapping it,
    an op 90-120 running past the window's end; host spans name the
    gaps 0-10 and 50-90."""
    ev = {"window": [0, 100],
          "devices": [[[KERNEL, 10, 30], ["fusion.1", 30, 20],
                       ["fusion.2", 90, 30]]],
          "host": [["bench.submit", 0, 12], ["bench.step", 12, 100]]}
    r = reduce(ev)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(50e-9)        # 10-50 and 90-100
    assert r["kernel_s"] == pytest.approx(30e-9)
    assert r["other_s"] == pytest.approx(30e-9)       # 20 + the 10 inside
    assert r["idle_gaps"] == [["bench.step", pytest.approx(40e-9)],
                              ["bench.submit", pytest.approx(10e-9)]]
    # the step, cut to the window at 100, is busy 12-50 and 90-100
    assert r["step_idle_s"] == [pytest.approx(40e-9)]
    assert r["device_ops"][0][0].startswith("fused_mac ")


def test_trace_reduction_on_a_recorded_trace():
    """Two waves of resnet20 (bucket 1) traced on a TPU v5e: every wave
    runs its 21 MAC kernels, and the busy time lies between the longest
    operation and the window."""
    with open(os.path.join(HERE, "data", "trace_r20_small.json")) as f:
        ev = json.load(f)
    r = reduce(ev)
    ops = ev["devices"][0]
    kernels = [o for o in ops if "tpu_custom_call" in o[0]]
    waves = [h for h in ev["host"] if h[0] == "bench.step"]
    assert len(kernels) == 21 * len(waves) > 0
    assert max(d for _, _, d in ops) * 1e-9 <= r["busy_s"] <= r["window_s"]
    assert r["kernel_s"] == pytest.approx(
        sum(d for _, _, d in kernels) * 1e-9)
    assert r["busy_s"] <= r["kernel_s"] + r["other_s"] + 1e-12
    assert len(r["idle_gaps"]) <= 10 and len(r["device_ops"]) <= 10


def test_host_ms_per_wave_is_idle_inside_the_step():
    """On the recorded trace: the one wave's step span less the device's
    busy time inside it, and nothing from a run without a trace."""
    from bench.run import reader
    with open(os.path.join(HERE, "data", "trace_r20_small.json")) as f:
        ev = json.load(f)
    r = reduce(ev)
    (_, s, d), = [h for h in ev["host"] if h[0] == "bench.step"]
    inside = [(max(a, s), min(a + n, s + d)) for _, a, n in ev["devices"][0]
              if a < s + d and a + n > s]
    edge, busy = s, 0
    for a, b in sorted(inside):
        busy += max(0, b - max(a, edge))
        edge = max(edge, b)
    read = reader("host_ms_per_wave")
    got = read(types.SimpleNamespace(trace=r))
    assert got == pytest.approx((d - busy) * 1e-6)
    assert 0 < got < d * 1e-6
    assert read(types.SimpleNamespace(trace=None)) is None


def test_gc_pauses_counts_full_collections():
    import gc
    pauses = system.GcPauses()
    gc.collect(1)
    gc.collect()
    pauses.stop()
    gc.collect()
    assert pauses.count == 1 and pauses.longest_ms > 0
