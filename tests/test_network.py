"""The bitslice-resident multi-layer pipeline (DESIGN.md §8).

Acceptance-level checks: a >=3-layer CNN with exactly one activation
encode and one decode must be bit-exact to the chained single-layer
decode/re-encode path, and within format tolerance of the f32 chain;
the plane-domain cast must agree with the word-parallel fp_cast oracle.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import softfloat as sf
from repro.core.bitslice import BitsliceActivation, pack_planes
from repro.core.fpformat import RNE, FPFormat
from repro.kernels.conv2d_bitslice.network import (ConvLayerSpec,
                                                   HobflopsNetwork)
from repro.kernels.conv2d_bitslice.ops import (ConvWeights,
                                               cast_activations, conv_core,
                                               conv_out_hw,
                                               decode_activations,
                                               encode_activations,
                                               encode_conv_weights)
from repro.kernels.conv2d_bitslice.ref import conv2d_f32

from conftest import COMPILE_HEAVY


def _rand(rng, shape, scale=1.0):
    return (rng.standard_normal(shape) * scale).astype(np.float32)


def _stack(fmt, rng, cin=4, width=8):
    """3-layer mixed stack: 3x3, pointwise, strided 3x3."""
    ks = [_rand(rng, (3, 3, cin, width), 0.4),
          _rand(rng, (1, 1, width, width), 0.4),
          _rand(rng, (3, 3, width, width), 0.4)]
    specs = [ConvLayerSpec(ks[0], fmt, relu=True),
             ConvLayerSpec(ks[1], fmt, relu=True),
             ConvLayerSpec(ks[2], fmt, stride=2, relu=False)]
    return ks, specs


@pytest.fixture(scope="module")
def stack8():
    """The 3-layer hobflops8 stack and one image, shared by the tests
    of this file that read it."""
    rng = np.random.default_rng(0)
    img = _rand(rng, (1, 6, 6, 4))
    _, specs = _stack(FPFormat(5, 2), rng)
    return HobflopsNetwork(specs), img


@COMPILE_HEAVY
def test_resident_matches_roundtrip_bit_exact(stack8):
    """The tentpole acceptance: 3 layers, single encode + single
    decode, bit-exact to the per-layer decode/re-encode path."""
    net, img = stack8
    res = np.asarray(net(img))
    rt = np.asarray(net.run_roundtrip(img))
    assert res.shape == net.out_shape(img.shape)
    np.testing.assert_array_equal(res, rt)


@COMPILE_HEAVY
def test_resident_tracks_f32_reference():
    fmt = FPFormat(5, 3)   # hobflops9
    rng = np.random.default_rng(1)
    img = _rand(rng, (1, 6, 6, 4))
    ks, specs = _stack(fmt, rng)
    net = HobflopsNetwork(specs)
    res = np.asarray(net(img))
    x = img
    for k, s in zip(ks, specs):
        x = np.asarray(conv2d_f32(x, k, stride=s.stride))
        if s.relu:
            x = np.maximum(x, 0.0)
    # 3 layers of w_f=3 quantization: loose, format-scaled tolerance
    rel = np.abs(res - x).max() / (np.abs(x).max() + 1e-9)
    assert rel < 12 * 2.0 ** -fmt.w_f, rel


@COMPILE_HEAVY
def test_resident_mixed_formats():
    """Per-layer operand formats differ; boundary casts re-round."""
    rng = np.random.default_rng(2)
    img = _rand(rng, (1, 5, 5, 4))
    k1 = _rand(rng, (3, 3, 4, 8), 0.4)
    k2 = _rand(rng, (1, 1, 8, 8), 0.4)
    net = HobflopsNetwork([
        ConvLayerSpec(k1, FPFormat(5, 3), relu=True),
        ConvLayerSpec(k2, FPFormat(5, 2), relu=True)])
    res = np.asarray(net(img))
    rt = np.asarray(net.run_roundtrip(img))
    np.testing.assert_array_equal(res, rt)


@COMPILE_HEAVY
def test_resident_pallas_backend_interpret():
    fmt = FPFormat(5, 2)
    rng = np.random.default_rng(3)
    img = _rand(rng, (1, 5, 5, 4))
    ks = [_rand(rng, (1, 1, 4, 32), 0.4), _rand(rng, (1, 1, 32, 32), 0.4)]
    specs = [ConvLayerSpec(k, fmt) for k in ks]
    want = np.asarray(HobflopsNetwork(specs)(img))
    got = np.asarray(HobflopsNetwork(specs, backend="pallas",
                                     interpret=True)(img))
    np.testing.assert_array_equal(got, want)


def test_no_f32_at_interior_boundaries(stack8):
    """The resident jaxpr contains exactly one encode (bitcast from f32)
    and one decode (bitcast to f32): interior boundaries never touch
    float32."""
    net, img = stack8
    jaxpr = jax.make_jaxpr(lambda x: net._resident(x, net.weights))(img)

    from conftest import count_primitives
    # one f32->i32 bitcast at encode + one i32->f32 at decode; the conv
    # cores and casts in between operate on int planes only.
    assert count_primitives(jaxpr.jaxpr, "bitcast_convert_type") == 2


@COMPILE_HEAVY
def test_resident_stride2_valid_bit_exact():
    """stride=2 and padding=VALID through the *resident* pipeline (not
    just the per-layer path): bit-exact to the roundtrip oracle, and
    the strided net still has exactly one encode + one decode."""
    fmt = FPFormat(5, 2)
    rng = np.random.default_rng(20)
    img = _rand(rng, (2, 9, 9, 4))
    specs = [ConvLayerSpec(_rand(rng, (3, 3, 4, 8), 0.4), fmt,
                           stride=2, padding="VALID", relu=True),
             ConvLayerSpec(_rand(rng, (3, 3, 8, 8), 0.4), fmt,
                           stride=2, padding="VALID", relu=False)]
    net = HobflopsNetwork(specs)
    res = np.asarray(net(img))
    assert res.shape == net.out_shape(img.shape) == (2, 1, 1, 8)
    np.testing.assert_array_equal(res, np.asarray(net.run_roundtrip(img)))

    from conftest import count_primitives
    jaxpr = jax.make_jaxpr(lambda x: net._resident(x, net.weights))(img)
    assert count_primitives(jaxpr.jaxpr, "bitcast_convert_type") == 2


def test_cast_activations_matches_oracle():
    """Plane-domain cast == word-parallel fp_cast on the same codes."""
    src, dst = FPFormat(5, 3), FPFormat(5, 2)
    rng = np.random.default_rng(5)
    vals = _rand(rng, (64,), 4.0)
    codes = sf.encode_jnp(jnp.asarray(vals), src)
    planes = pack_planes(codes, src.nbits)[:, None, :]   # [nb, 1, Mw]
    act = BitsliceActivation(planes, src, (1, 1, 1, 64))
    out = cast_activations(act, dst)
    assert out.fmt == dst and out.shape == act.shape
    got = np.asarray(decode_activations(out)).ravel()
    want_codes = sf.fp_cast(np.asarray(codes), src, dst)
    want = sf.decode(want_codes, dst).astype(np.float32)
    np.testing.assert_array_equal(got, want)


def test_cast_activations_identity_is_noop():
    fmt = FPFormat(5, 3)
    rng = np.random.default_rng(6)
    act = encode_activations(jnp.asarray(_rand(rng, (1, 4, 4, 8))), fmt)
    assert cast_activations(act, fmt) is act


def test_conv_core_stages_compose_to_conv2d():
    """encode -> conv_core -> decode == hobflops_conv2d (the one-layer
    composition), including relu and stride."""
    from repro.kernels.conv2d_bitslice.ops import hobflops_conv2d
    fmt = FPFormat(5, 3)
    rng = np.random.default_rng(7)
    img = _rand(rng, (1, 6, 6, 4))
    ker = _rand(rng, (3, 3, 4, 8), 0.4)
    cw = encode_conv_weights(ker, fmt)
    act = encode_activations(jnp.asarray(img), fmt)
    out = conv_core(act, cw, stride=2, relu=True)
    assert out.fmt == fmt.mult_out()
    got = np.asarray(decode_activations(out))
    want = np.asarray(hobflops_conv2d(img, ker, fmt=fmt, stride=2,
                                      relu=True, backend="jnp"))
    np.testing.assert_array_equal(got, want)


def test_conv_weights_pytree_roundtrip():
    fmt = FPFormat(5, 2)
    rng = np.random.default_rng(8)
    cw = encode_conv_weights(_rand(rng, (3, 3, 4, 8)), fmt)
    leaves, treedef = jax.tree_util.tree_flatten(cw)
    assert len(leaves) == 1
    back = jax.tree_util.tree_unflatten(treedef, leaves)
    assert isinstance(back, ConvWeights)
    assert (back.kh, back.kw, back.cin, back.cout, back.fmt) == \
        (3, 3, 4, 8, fmt)


@pytest.mark.parametrize("H,W,kh,kw,stride,padding", [
    (6, 6, 3, 3, 1, "SAME"), (6, 6, 3, 3, 2, "SAME"),
    (7, 5, 3, 3, 2, "SAME"), (7, 5, 3, 3, 2, "VALID"),
    (8, 8, 1, 1, 2, "SAME"), (5, 5, 3, 3, 1, "VALID"),
])
def test_conv_out_hw_matches_im2col(H, W, kh, kw, stride, padding):
    from repro.kernels.conv2d_bitslice.ops import im2col
    x = jnp.zeros((1, H, W, 2), jnp.float32)
    pat = im2col(x, kh, kw, stride, padding)
    assert (pat.shape[1], pat.shape[2]) == \
        conv_out_hw(H, W, kh, kw, stride, padding)
