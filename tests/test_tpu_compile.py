"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each test lowers one kernel at a real-width shape for a
v5e that is described, not attached, and asserts that Mosaic accepted
it (a ``tpu_custom_call`` in the compiled program).  A refusal here —
an unaligned block, too much VMEM, an unsupported in-kernel op — is
what interpret-mode tests cannot see.

The topology is described inside a module fixture (never at import:
only one process may load the TPU library, and every test worker
imports this file), and the persistent compilation cache is off
around the compiles, since an entry written for a described chip
cannot be read back without one.
"""
from __future__ import annotations

import functools
import os
import signal

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from repro.core.fpformat import HOBFLOPS_FORMATS, StorageFormat
from repro.core.pallas_backend import fused_mac_pallas
from repro.kernels.bitslice_mac.kernel import bitslice_mac_pallas
from repro.kernels.conv2d_bitslice.network import NetworkGraph
from repro.kernels.conv2d_bitslice.ops import derive_blocks
from repro.kernels.dequant_matmul.kernel import dequant_matmul_pallas
from repro.serve_conv.sharding import wave_program

LANE = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    on_term = signal.getsignal(signal.SIGTERM)
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        # Loading the TPU library installs a crash reporter on SIGTERM;
        # a test worker stopped from outside should end as it did
        # before, not print a stack dump into the test report.
        signal.signal(signal.SIGTERM, on_term)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _mac_shapes(P, K, M, fmt):
    """Padded (i_masks, w_planes) shapes and derived blocks of the conv
    GEMM [P, K] @ [K, M], exactly as ``conv_core`` launches it."""
    blk = derive_blocks(P, K, M)
    P = -(-P // blk["p_block"]) * blk["p_block"]
    K = -(-K // blk["c_block"]) * blk["c_block"]
    mw = -(-M // LANE)
    mw = -(-mw // blk["m_block"]) * blk["m_block"]
    return ((P, K, fmt.nbits), jnp.int32), ((K, fmt.nbits, mw),
                                            jnp.int32), blk


@pytest.mark.parametrize("name,P,K,M", [
    ("hobflops9", 3136, 576, 64),      # ResNet-18 stage-1 3x3 conv
    ("hobflops16", 3136, 576, 64),
    ("hobflops9", 12544, 147, 64),     # ResNet-18 7x7/2 stem
    ("hobflops9", 49, 4608, 512),      # ResNet-18 stage 4: 16 words
    ("hobflops16", 4096, 144, 16),     # ResNet-20 stage 1, 4 images
])
def test_fused_mac_compiles(one_chip, name, P, K, M):
    """Every tile regime of ``tile_plan``: pixel groups folded onto
    the sublanes (1 and 2 channel words), and 8-word rows with one
    block below a full 128-pixel row (P = 49)."""
    fmt = HOBFLOPS_FORMATS[name]
    blk = derive_blocks(P, K, M)
    # As conv_core launches it: C padded to c_block, P and M unpadded.
    K = -(-K // blk["c_block"]) * blk["c_block"]
    fn = functools.partial(fused_mac_pallas, fmt=fmt, relu=True,
                           c_block=blk["c_block"],
                           c_unroll=blk["c_unroll"])
    assert "tpu_custom_call" in _compiled_text(
        fn, one_chip, ((P, K, fmt.nbits), jnp.int32),
        ((K, fmt.nbits, -(-M // LANE)), jnp.int32))


def test_bitslice_mac_compiles(one_chip):
    fmt = HOBFLOPS_FORMATS["hobflops9"]
    i_s, w_s, blk = _mac_shapes(3136, 576, 64, fmt)
    fn = functools.partial(bitslice_mac_pallas, fmt=fmt, **blk)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, i_s, w_s)


def test_dequant_matmul_compiles(one_chip):
    """A decode-sized step: 8 tokens through a 4096x4096 projection
    whose weights stream as 9-bit planes."""
    sfmt = StorageFormat(5, 3)
    M, K, N = 8, 4096, 4096
    fn = functools.partial(dequant_matmul_pallas, sfmt=sfmt, N=N)
    text = _compiled_text(fn, one_chip, ((M, K), jnp.bfloat16),
                          ((sfmt.nbits, K, N // LANE), jnp.int32),
                          ((), jnp.float32))
    assert "tpu_custom_call" in text


def test_wave_program_compiles_on_four_chips(topo):
    """The serving wave path over a 2x2 v5e: a stage-1 ResNet conv
    shard_mapped one image per chip, with its kernel on every chip."""
    rng = np.random.default_rng(0)
    g = NetworkGraph(HOBFLOPS_FORMATS["hobflops9"], backend="pallas_fused")
    g.output(g.conv("c", g.input_name,
                    rng.standard_normal((3, 3, 64, 64)).astype(np.float32),
                    relu=True))
    mesh = Mesh(np.array(topo.devices), ("wave",))
    images = jax.ShapeDtypeStruct(
        (4, 56, 56, 64), jnp.float32,
        sharding=NamedSharding(mesh, PartitionSpec("wave")))
    weights = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype,
            sharding=NamedSharding(mesh, PartitionSpec())),
        g._live_weights)
    text = wave_program(g, mesh).lower(images, weights).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
