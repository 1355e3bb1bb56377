"""MX quantization invariants — unit + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import scale_seed_property, seed_property

from repro.core import mx as mxlib


@pytest.mark.parametrize("fmt", ["mxfp4", "mxint4", "mxfp8", "mxfp6"])
def test_scales_are_powers_of_two(fmt):
    x = jax.random.normal(jax.random.PRNGKey(0), (16, 128)) * 10
    s = np.asarray(mxlib.compute_scales(x, mxlib.MXConfig(fmt=fmt)))
    np.testing.assert_array_equal(np.log2(s), np.round(np.log2(s)))


def test_idempotent():
    cfg = mxlib.MXConfig()
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64)) * 5
    q1 = mxlib.quantize(x, cfg, ste=False)
    q2 = mxlib.quantize(q1, cfg, ste=False)
    np.testing.assert_allclose(np.asarray(q1), np.asarray(q2), atol=1e-7)


def test_encode_decode_roundtrip():
    for fmt in ["mxfp4", "mxint4", "mxfp8"]:
        cfg = mxlib.MXConfig(fmt=fmt)
        x = jax.random.normal(jax.random.PRNGKey(2), (4, 96)) * 3
        c, s = mxlib.encode(x, cfg)
        dec = mxlib.decode(c, s, cfg)
        q = mxlib.quantize(x, cfg, ste=False)
        np.testing.assert_allclose(np.asarray(dec), np.asarray(q), atol=1e-6)


def test_ste_gradient_is_identity():
    cfg = mxlib.MXConfig()
    x = jax.random.normal(jax.random.PRNGKey(3), (4, 64))
    g = jax.grad(lambda z: jnp.sum(mxlib.quantize(z, cfg) * 2.0))(x)
    np.testing.assert_allclose(np.asarray(g), 2.0)


def test_nvfp4_block16():
    x = jax.random.normal(jax.random.PRNGKey(4), (8, 64)) * 4
    q = mxlib.quantize(x, mxlib.NVFP4, ste=False)
    assert q.shape == x.shape
    assert np.isfinite(np.asarray(q)).all()


@scale_seed_property(max_examples=30)
def test_property_relative_error_bound(scale, seed):
    """MX FP4 relative block error is bounded: per-element error <= half the
    largest grid step times the block scale => block-relative error < 2/3."""
    cfg = mxlib.MXConfig(fmt="mxfp4")
    x = np.random.default_rng(seed).standard_normal((2, 64)) * scale
    x = jnp.asarray(x, jnp.float32)
    q = mxlib.quantize(x, cfg, ste=False)
    xb = np.asarray(x).reshape(2, 2, 32)
    qb = np.asarray(q).reshape(2, 2, 32)
    amax = np.abs(xb).max(-1, keepdims=True)
    # FP4 max quantization step is 1 at scale 2^e where amax < 8*2^e
    # => |err| <= scale = 2^e <= amax/4; elementwise err <= amax/4 (+eps)
    assert (np.abs(xb - qb) <= amax / 4 + 1e-6).all()


@seed_property(max_examples=30)
def test_property_quantized_value_magnitude(seed):
    """|Q(x)| never exceeds max-grid x scale and sign is preserved."""
    cfg = mxlib.MXConfig(fmt="mxint4")
    x = np.random.default_rng(seed).standard_normal((4, 32)).astype(np.float32)
    q = np.asarray(mxlib.quantize(jnp.asarray(x), cfg, ste=False))
    assert ((q == 0) | (np.sign(q) == np.sign(x))).all()
    s = np.asarray(mxlib.compute_scales(jnp.asarray(x), cfg))  # (4, 1)
    assert (np.abs(q) <= s * 7 + 1e-9).all()


def test_packed_nbytes():
    cfg = mxlib.MXConfig(fmt="mxfp4", block_size=32)
    # 4-bit codes: n/2 bytes; scales: n/32 bytes
    assert mxlib.packed_nbytes((64, 64), cfg) == 64 * 64 // 2 + 64 * 64 // 32


@pytest.mark.parametrize("shape", [(64, 48), (3, 64, 48), (2, 3, 64, 48)])
def test_rtn_one_matrix_at_a_time_matches_whole_stack(shape):
    """RTN walks a stacked weight one (K, N) matrix at a time
    (map_matrices); the values equal quantizing the whole stack at once."""
    from repro.core import gptq
    cfg = mxlib.MXConfig(fmt="mxfp4")
    w = jax.random.normal(jax.random.PRNGKey(3), shape) * 3
    got = gptq.quantize_weights_rtn({"blocks": {"wd": w}}, None,
                                    cfg)["blocks"]["wd"]
    whole = jnp.swapaxes(mxlib.quantize(jnp.swapaxes(w, -1, -2), cfg,
                                        ste=False), -1, -2)
    assert got.shape == w.shape and got.dtype == w.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(whole))
