"""Production mesh factories.

Functions, not module-level constants: importing this module never
touches jax device state.  The dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import so these meshes build on the CPU container; on real hardware the
same factories lay the axes out over the actual ICI topology.

Axis semantics:
  pod   — outer data-parallel axis across pods (gradient all-reduce and
          optimizer sharding cross DCN/ICI links between pods)
  data  — in-pod data parallelism / FSDP (params' embed dims sharded)
  model — tensor parallelism (vocab/heads/mlp/experts/ssm)
"""
from __future__ import annotations

import math

import jax


def _mk(shape, axes):
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...] | None = None):
    """Elastic mesh factory: any (pods, data, model) shape.  1-sized
    leading axes are squeezed so the same code serves 1..N pods."""
    if axes is None:
        axes = ("pod", "data", "model")[-len(shape):]
    keep = [(s, a) for s, a in zip(shape, axes) if s > 1 or a == "model"]
    if not keep:
        keep = [(1, "data")]
    shape = tuple(s for s, _ in keep)
    axes = tuple(a for _, a in keep)
    return _mk(shape, axes)


def host_mesh():
    """Whatever this process actually has (tests: 1 CPU device)."""
    n = len(jax.devices())
    return _mk((1, n), ("data", "model"))
