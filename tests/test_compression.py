"""Gradient compression: unbiasedness, error feedback convergence, and
the shard_map psum path."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import (compress_grads,
                                           compressed_psum,
                                           dequantize_int8,
                                           make_error_feedback,
                                           quantize_int8, wire_bytes)


def test_quantize_roundtrip_error_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(1024), jnp.float32)
    codes, scale = quantize_int8(x)
    back = dequantize_int8(codes, scale)
    assert float(jnp.max(jnp.abs(back - x))) <= float(scale) * 0.5 + 1e-7


def test_stochastic_rounding_unbiased():
    x = jnp.full((20000,), 0.3)
    codes, scale = quantize_int8(x, key=jax.random.PRNGKey(0))
    mean = float(jnp.mean(dequantize_int8(codes, scale)))
    assert abs(mean - 0.3) < 2e-3


def test_error_feedback_accumulates_to_truth():
    """Sum over steps of EF-compressed grads converges to sum of true
    grads (the EF telescoping property)."""
    rng = np.random.default_rng(1)
    true_sum = np.zeros(64, np.float32)
    sent_sum = np.zeros(64, np.float32)
    ef = make_error_feedback({"g": jnp.zeros(64)})
    for step in range(50):
        g = {"g": jnp.asarray(rng.standard_normal(64) * 0.01,
                              jnp.float32)}
        true_sum += np.asarray(g["g"])
        sent, ef = compress_grads(g, ef)
        sent_sum += np.asarray(sent["g"])
    # residual is bounded by one quantization step, not growing in t
    resid = np.abs(true_sum - sent_sum).max()
    assert resid < 0.01, resid


def test_compressed_psum_shard_map():
    from repro.launch.mesh import _mk
    mesh = _mk((1,), ("data",))
    x = jnp.arange(8, dtype=jnp.float32) / 7.0

    def f(x):
        return compressed_psum(x, "data")

    y = jax.shard_map(f, mesh=mesh, in_specs=P(), out_specs=P())(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), atol=0.01)


def test_wire_bytes():
    g = {"a": jnp.zeros((128, 128)), "b": jnp.zeros(64)}
    assert wire_bytes(g, compressed=True) * 3.9 < wire_bytes(
        g, compressed=False)
