"""Fused Pallas netlist compiler backend (DESIGN.md §12).

Differential coverage for ``repro.core.pallas_backend``: the lowered
register-file emission must be bit-identical to the ``eval_netlist``
oracle on every format x rounding (exhaustive on the smallest format,
randomized wide-lane elsewhere), the register file must fail loudly on
overflow, and a fused conv must emit exactly one ``pallas_call``.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.codegen import eval_netlist
from repro.core.fpcore import build_mac_chain
from repro.core.fpformat import HOBFLOPS_FORMATS, RNE, RTZ
from repro.core.opt import optimize_mapped
from repro.core.pallas_backend import (STACK_MAX_DEFAULT,
                                       RegisterFileOverflow,
                                       fused_chain_k, fused_mac_pallas,
                                       lower_netlist, tile_plan)
from repro.kernels.bitslice_mac.ops import (_bitslice_mac_jnp,
                                            hobflops_matmul)
from repro.kernels.conv2d_bitslice.network import NetworkGraph
from repro.kernels.conv2d_bitslice.ops import (default_tune_candidates,
                                               hobflops_conv2d,
                                               hobflops_relu_planes)

F8 = HOBFLOPS_FORMATS["hobflops8"]
F16 = HOBFLOPS_FORMATS["hobflops16"]


def _mac_graph(fmt, k=1, rounding=RNE, extended=False):
    return optimize_mapped(build_mac_chain(fmt, k, extended, rounding),
                           "tpu_vpu")


def _rand_chain_inputs(graph, rng, P=4, Mw=2):
    """Random lane-resolved planes for every input bus of a MAC chain:
    x buses get independent per-lane bits, y buses 0/-1 broadcast
    masks, acc full random planes — the real kernel's value classes."""
    inputs = {}
    for name, bus in graph.inputs.items():
        w = len(bus)
        if name.startswith("y"):
            v = -rng.integers(0, 2, (w, P, 1)).astype(np.int64)
            inputs[name] = np.broadcast_to(v, (w, P, Mw))
        else:
            inputs[name] = rng.integers(-2**31, 2**31, (w, P, Mw),
                                        dtype=np.int64)
    return {k: v.astype(np.int32) for k, v in inputs.items()}


# ---------------------------------------------------------------------------
# Emitter vs eval_netlist oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["hobflops8", "hobflops9",
                                  "hobflops16"])
@pytest.mark.parametrize("rounding", [RNE, RTZ])
def test_lowered_matches_eval_netlist(name, rounding):
    """Randomized wide-lane differential: the lowered register-file
    program is bit-identical to the numpy interpreter for every output
    plane.  hobflops16's 19-plane out bus exercises the one-hot
    assembly, hobflops8/9 the plain-stack path."""
    fmt = HOBFLOPS_FORMATS[name]
    g = _mac_graph(fmt, k=2, rounding=rounding)
    lowered = lower_netlist(g)
    rng = np.random.default_rng(hash((name, rounding)) % 2**32)
    inputs = _rand_chain_inputs(g, rng)
    want = eval_netlist(g, inputs)
    got = jax.jit(lambda kw: lowered(**kw))(
        {k: jnp.asarray(v) for k, v in inputs.items()})
    for bus in want:
        assert np.array_equal(np.asarray(got[bus]), want[bus]), bus


def test_lowered_exhaustive_small_format():
    """Exhaustive hobflops8 sweep: every (x code, y code) pair runs
    through one lowered MAC step via broadcasting — x codes packed
    along lanes, y codes as row masks — and must match the oracle on
    all 2^16 pairs at both roundings."""
    n = 1 << F8.nbits
    codes = np.arange(n, dtype=np.int64)
    bits = (codes[:, None] >> np.arange(F8.nbits)) & 1      # [n, nbits]
    # x: all n codes along int32 lanes -> [nbits, 1, n/32]
    xp = np.zeros((F8.nbits, 1, n // 32), np.int64)
    for c in range(n):
        xp[:, 0, c // 32] |= bits[c] << (c % 32)
    # y: all n codes as per-row 0/-1 masks -> [nbits, n, 1]
    yp = -bits.T[:, :, None]
    for rounding in (RNE, RTZ):
        g = _mac_graph(F8, k=1, rounding=rounding)
        lowered = lower_netlist(g)
        inputs = {"x0": xp.astype(np.int32), "y0": yp.astype(np.int32),
                  "acc": np.zeros((len(g.inputs["acc"]), n, n // 32),
                                  np.int32)}
        want = eval_netlist(g, inputs)["out"]
        got = np.asarray(jax.jit(lambda kw: lowered(**kw)["out"])(
            {k: jnp.asarray(v) for k, v in inputs.items()}))
        assert np.array_equal(np.broadcast_to(got, want.shape), want)


def test_onehot_assembly_used_and_bit_exact():
    """Forcing ``stack_max`` below the bus width switches hobflops8 to
    the one-hot or-tree assembly; values must not change."""
    g = _mac_graph(F8)
    rng = np.random.default_rng(3)
    inputs = _rand_chain_inputs(g, rng)
    jinp = {k: jnp.asarray(v) for k, v in inputs.items()}
    plain = lower_netlist(g)(**jinp)["out"]
    forced = lower_netlist(g, stack_max=2)(**jinp)["out"]
    assert np.array_equal(np.asarray(plain), np.asarray(forced))


# ---------------------------------------------------------------------------
# Register file
# ---------------------------------------------------------------------------
def test_register_file_overflow_fails_loudly():
    """A file smaller than the schedule's peak must raise at lowering
    time — never spill silently or corrupt lanes; an exact-size file
    still evaluates bit-identically to the oracle."""
    g = _mac_graph(F8)
    nslots = lower_netlist(g).nslots
    with pytest.raises(RegisterFileOverflow) as ei:
        lower_netlist(g, regfile_size=nslots - 1)
    assert ei.value.need == nslots and ei.value.have == nslots - 1
    exact = lower_netlist(g, regfile_size=nslots)
    rng = np.random.default_rng(4)
    inputs = _rand_chain_inputs(g, rng)
    want = eval_netlist(g, inputs)["out"]
    got = np.asarray(exact(**{k: jnp.asarray(v)
                              for k, v in inputs.items()})["out"])
    assert np.array_equal(np.broadcast_to(got, want.shape), want)


# ---------------------------------------------------------------------------
# Backend wiring: matmul / conv / network
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", ["hobflops8", "hobflops16"])
@pytest.mark.parametrize("rounding", [RNE, RTZ])
def test_fused_matmul_matches_jnp(name, rounding):
    fmt = HOBFLOPS_FORMATS[name]
    rng = np.random.default_rng(5)
    i = rng.standard_normal((8, 12)).astype(np.float32)
    w = rng.standard_normal((12, 40)).astype(np.float32)
    a = hobflops_matmul(i, w, fmt=fmt, rounding=rounding, backend="jnp",
                        c_unroll=1)
    b = hobflops_matmul(i, w, fmt=fmt, rounding=rounding, c_unroll=1,
                        backend="pallas_fused", interpret=True)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_fused_conv_relu_epilogue_matches_jnp():
    """The in-kernel ReLU epilogue (applied only on the final C grid
    step) must agree with the post-hoc hobflops_relu_planes pass.  One
    channel per netlist call: the epilogue does not depend on the chain
    depth, and the default 4-deep chain makes this test four times
    slower."""
    rng = np.random.default_rng(6)
    img = rng.standard_normal((1, 6, 6, 4)).astype(np.float32)
    ker = (rng.standard_normal((3, 3, 4, 8)) * 0.3).astype(np.float32)
    for relu in (False, True):
        a = hobflops_conv2d(img, ker, fmt=F8, relu=relu, backend="jnp",
                            c_unroll=1)
        b = hobflops_conv2d(img, ker, fmt=F8, relu=relu, c_unroll=1,
                            backend="pallas_fused", interpret=True)
        assert np.array_equal(np.asarray(a), np.asarray(b)), relu


def _count_pallas_calls(jaxpr, n=0):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            n += 1
        for v in eqn.params.values():
            if hasattr(v, "eqns"):
                n = _count_pallas_calls(v, n)
            elif hasattr(v, "jaxpr"):
                n = _count_pallas_calls(v.jaxpr, n)
    return n


def test_fused_conv_emits_single_pallas_call():
    """The acceptance pin: a fused conv (MAC chain + ReLU epilogue) is
    ONE pallas_call in the jaxpr, not hundreds of elementwise ops."""
    rng = np.random.default_rng(7)
    img = rng.standard_normal((1, 4, 4, 4)).astype(np.float32)
    ker = (rng.standard_normal((3, 3, 4, 8)) * 0.3).astype(np.float32)
    jx = jax.make_jaxpr(lambda x, k: hobflops_conv2d(
        x, k, fmt=F8, relu=True, backend="pallas_fused",
        interpret=True))(img, ker)
    assert _count_pallas_calls(jx.jaxpr) == 1


def test_fused_chain_k_policy():
    """Wide out buses (hobflops16: 19 planes) clamp the fused chain to
    k=1 — deeper chains compile superlinearly for no duplication win —
    while narrow formats keep the requested depth."""
    assert F16.mult_out(False).nbits > STACK_MAX_DEFAULT
    assert fused_chain_k(F16, False, 4) == 1
    assert F8.mult_out(False).nbits <= STACK_MAX_DEFAULT
    assert fused_chain_k(F8, False, 4) == 4


def test_fused_network_graph_end_to_end():
    """backend='pallas_fused' selected at NetworkGraph construction
    flows through the resident interpreter and changes signature()
    (so RunnerCache keys can never collide across backends)."""
    rng = np.random.default_rng(8)
    img = rng.standard_normal((1, 6, 6, 4)).astype(np.float32)
    ker = (rng.standard_normal((3, 3, 4, 8)) * 0.3).astype(np.float32)

    def build(backend, interpret=False):
        g = NetworkGraph(F8, backend=backend, interpret=interpret)
        y = g.conv("c1", g.input_name, ker, relu=True,
                   blocks={"c_unroll": 2})
        g.output(g.cast("cast", y, F8))
        return g

    ref = build("jnp")
    fused = build("pallas_fused", interpret=True)
    a = ref.run(img)
    b = fused.run(img)
    assert np.array_equal(np.asarray(a), np.asarray(b))
    assert ref.signature() != fused.signature()


# ---------------------------------------------------------------------------
# Output tiles: every (P, Mw) regime of tile_plan
# ---------------------------------------------------------------------------
# format: (chain depth, C) — hobflops8 on the plain-stack bus at depth
# 4, hobflops16 on the or-tree bus at depth 1.
_TILE_FORMATS = {"hobflops8": (4, 8), "hobflops16": (1, 4)}
_TILE_P, _TILE_MW = 1100, 16


@functools.lru_cache(maxsize=None)
def _tile_operands(name):
    """Random masks [P, C, NIN] and weight planes [C, NIN, Mw] at the
    largest case's size, and the jnp backend's ReLU'd planes for them.
    Every (pixel, word) of the MAC is independent of the others, so a
    case slices its operands and the reference out of these: the jnp
    chain compiles once per format."""
    fmt = HOBFLOPS_FORMATS[name]
    k, C = _TILE_FORMATS[name]
    rng = np.random.default_rng(fmt.nbits)
    codes = rng.integers(0, 1 << fmt.nbits, (_TILE_P, C))
    masks = -((codes[..., None] >> np.arange(fmt.nbits)) & 1)
    planes = rng.integers(-2**31, 2**31, (C, fmt.nbits, _TILE_MW),
                          dtype=np.int64)
    masks, planes = masks.astype(np.int32), planes.astype(np.int32)
    want = _bitslice_mac_jnp(jnp.asarray(masks), jnp.asarray(planes),
                             fmt=fmt, extended=False, rounding=RNE,
                             c_unroll=k)
    want = hobflops_relu_planes(want, fmt.mult_out(False))
    return masks, planes, np.asarray(want)


@pytest.mark.parametrize("name,P,Mw,c_block", [
    ("hobflops8", 1100, 1, 4),   # 8 pixel groups, 1024-pixel tiles
    ("hobflops8", 20, 2, 4),     # 4 groups, P below one tile
    ("hobflops8", 140, 16, 4),   # 8-word rows: 2 M blocks, 2nd P tile partial
    ("hobflops16", 600, 4, 3),   # 2 groups, 256-pixel tiles; C 4 -> 6
    ("hobflops16", 20, 16, 2),   # 8-word rows, P below one 128-pixel row
])
def test_fused_mac_tiles_match_jnp(name, P, Mw, c_block):
    """The fused kernel (interpret mode) is bit-identical to the jnp
    backend in every tile regime, with the ReLU epilogue on, over two
    C grid steps (one case pads C to the block with +0 codes)."""
    fmt = HOBFLOPS_FORMATS[name]
    k, _ = _TILE_FORMATS[name]
    masks, planes, want = _tile_operands(name)
    got = fused_mac_pallas(jnp.asarray(masks[:P]),
                           jnp.asarray(planes[..., :Mw]), fmt=fmt,
                           rounding=RNE, c_block=c_block, c_unroll=k,
                           relu=True, interpret=True)
    assert got.shape == (fmt.mult_out(False).nbits, P, Mw)
    assert np.array_equal(np.asarray(got), want[:, :P, :Mw])


@pytest.mark.parametrize("P,Mw,fill", [
    (12544, 2, 0.98),          # ResNet-18 stem, 224x224
    (3136, 2, 0.875),          # ResNet-18 stage 1
    (784, 4, 0.765625),        # ResNet-18 stage 2
    (196, 8, 0.765625),        # ResNet-18 stage 3
    (49, 16, 0.3828125),       # ResNet-18 stage 4
    (4096, 1, 1.0),            # ResNet-20 stage 1, a wave of 4 images
    (1024, 1, 1.0),            # ResNet-20 stage 2, a wave of 4 images
    (256, 2, 0.5),             # ResNet-20 stage 3, a wave of 4 images
])
def test_tile_plan_fill_for_resnet_convs(P, Mw, fill):
    """The share of tile words that carry a real (pixel, channel word)
    pair, for the convs PERF.md lists; a tile is one (8, 128) vreg."""
    t = tile_plan(P, Mw)
    assert t.fill == pytest.approx(fill)
    assert t.words * t.groups == 8 and t.lanes <= 128
    assert t.p_pad >= P and t.mw_pad >= Mw


def test_fused_backend_refuses_p_and_m_blocks():
    """The fused kernel tiles P and M from the shapes: a launch override
    naming p_block/m_block is refused, not dropped in silence, and the
    tune sweep does not offer one."""
    ker = np.zeros((3, 3, 4, 8), np.float32)
    for blocks in ({"p_block": 8}, {"m_block": 1, "c_unroll": 2}):
        g = NetworkGraph(F8, backend="pallas_fused", interpret=True)
        with pytest.raises(ValueError, match="tiles P and M"):
            g.conv("c1", g.input_name, ker, blocks=blocks)
    g = NetworkGraph(F8, backend="jnp")
    g.conv("c1", g.input_name, ker, blocks={"p_block": 8, "m_block": 1})
    img = np.zeros((1, 4, 4, 4), np.float32)
    with pytest.raises(ValueError, match="p_block/m_block"):
        hobflops_conv2d(img, ker, fmt=F8, backend="pallas_fused",
                        interpret=True, m_block=1)
    assert all(set(c) == {"c_unroll"}
               for c in default_tune_candidates("pallas_fused"))
