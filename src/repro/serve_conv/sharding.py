"""Multi-device wave sharding for the lane-batched serve engine.

A packed wave is ``[B, H, W, C]`` images that become ``B*H*W`` pixel
rows of the plane carrier — requests occupy disjoint row slabs (lanes
carry channels; see ``lanes.py``).  Sharding a wave therefore splits
the batch axis over a 1-D ``wave`` mesh: each device encodes, runs,
and decodes its own slab of whole images through the same compiled
resident graph.  No cross-device communication exists anywhere in the
graph body (every plane op is row-local to an image), so the only
collective is the implicit gather of ``out_specs``.

Bit-exactness is inherited from the lane-packing argument: an image's
rows compute identical codes whether its slab is the whole wave or a
per-device shard, and each shard still performs exactly one encode and
one decode.  ``tests/test_serve_conv.py`` asserts the sharded wave
output equals the single-device wave bit-for-bit on a CPU mesh (and on
a forced 2-device host in a subprocess).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.conv2d_bitslice.network import NetworkGraph
from repro.serve_conv.errors import WaveShardingError


def wave_mesh(ndev: int | None = None):
    """A 1-D ``wave`` mesh over the first ``ndev`` local devices (all
    of them by default)."""
    devices = jax.devices()[:ndev]
    return jax.make_mesh((len(devices),), ("wave",),
                         axis_types=(AxisType.Auto,), devices=devices)


def mesh_size(mesh) -> int:
    return int(mesh.devices.size)


def wave_program(graph: NetworkGraph, mesh):
    """The graph's resident runner shard_mapped over the batch axis of
    ``mesh``, jitted: ``(images [B,H,W,C], weights) -> [B,Ho,Wo,M]``
    with images and output split on ``wave`` and weights replicated."""
    # check_vma=False: the graph body has no collective, and its scan
    # carries start from fresh zeros, which the varying-axes check
    # would reject against the wave-varying outputs.
    return jax.jit(jax.shard_map(graph._resident_fn, mesh=mesh,
                                 in_specs=(P("wave"), P()),
                                 out_specs=P("wave"), check_vma=False))


def wave_sharded_runner(graph: NetworkGraph, mesh=None):
    """A wave entrypoint ``images [B,H,W,C] -> [B,Ho,Wo,M]`` running
    :func:`wave_program`.  ``B`` must divide by the mesh size (the
    engine guarantees this by scaling its batch buckets to multiples
    of it).  The weights are copied to every device of the mesh once,
    here, and the output is one array whose shards together hold the
    whole wave."""
    mesh = mesh or wave_mesh()
    n = mesh_size(mesh)
    weights = jax.device_put(graph._live_weights,
                             NamedSharding(mesh, P()))
    sharded = wave_program(graph, mesh)
    split = NamedSharding(mesh, P("wave"))

    def runner(images):
        if np.shape(images)[0] % n:
            raise WaveShardingError(
                f"wave batch {np.shape(images)[0]} does not divide over "
                f"the {n}-device wave mesh")
        # each device receives its own slab of the wave from the host
        return sharded(jax.device_put(np.asarray(images, np.float32),
                                      split), weights)

    return runner
