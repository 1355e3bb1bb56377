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


def _encode_searchsorted(x, cfg):
    """Reference encode: the element index by a binary search of |z|
    over the grid's midpoints."""
    B = cfg.block_size
    *lead, d = x.shape
    scales = mxlib.compute_scales(x, cfg)
    xb = x.reshape(*lead, d // B, B)
    z = (xb / scales[..., None].astype(x.dtype)).reshape(*lead, d)
    g = jnp.asarray(cfg.element.grid, dtype=jnp.float32)
    mids = (g[1:] + g[:-1]) / 2.0
    zf = z.astype(jnp.float32)
    idx = jnp.searchsorted(mids, jnp.abs(zf), side="right")
    center = len(cfg.element.grid) - 1
    codes = center + jnp.where(zf < 0, -idx, idx)
    return codes.astype(jnp.uint8), scales


@pytest.mark.parametrize("fmt", ["mxfp4", "mxfp6", "mxfp8", "mxint4",
                                 "mxint8"])
def test_encode_matches_searchsorted_oracle(fmt):
    """The closed-form grid index gives the oracle's codes and scales bit
    for bit: on every grid value, every midpoint and its f32 neighbours,
    the special values, and seeded values over the format's range."""
    cfg = mxlib.MXConfig(fmt=fmt)
    el = cfg.element
    g = np.asarray(el.grid, np.float32)
    mids = (g[1:] + g[:-1]) / 2
    special = np.array([0.0, np.inf, np.nan, 1e-45, 1e-40, 1e30],
                       np.float32)
    exact = np.concatenate([g, mids, np.nextafter(mids, np.float32(np.inf)),
                            np.nextafter(mids, np.float32(-np.inf)),
                            special])
    exact = np.concatenate([exact, -exact])

    # the element index on raw magnitudes, past the top and NaN included
    g32 = jnp.asarray(g)
    want_idx = jnp.searchsorted((g32[1:] + g32[:-1]) / 2.0,
                                jnp.abs(jnp.asarray(exact)), side="right")
    got_idx = mxlib._grid_index(jnp.abs(jnp.asarray(exact)), el)
    np.testing.assert_array_equal(np.asarray(got_idx), np.asarray(want_idx))
    top = len(g) - 1
    assert (np.asarray(got_idx)[np.abs(exact) > el.max_val] == top).all()
    assert (np.asarray(got_idx)[np.isnan(exact)] == top).all()

    # through encode: rows of 31 values and one 2^r_max, so that every
    # finite in-range value sits at scale 1 where it was written
    pad = (-exact.size) % 31
    rows = np.concatenate([exact, np.zeros(pad, np.float32)]).reshape(-1, 31)
    anchor = np.full((rows.shape[0], 1), 2.0 ** el.r_max, np.float32)
    on_grid = np.concatenate([rows, anchor], axis=1)
    rng = np.random.default_rng(17)
    mag = np.exp2(rng.uniform(-12.0, np.log2(2 * el.max_val), 100_000))
    spread = (mag * rng.choice([-1.0, 1.0], mag.size)).astype(np.float32)
    for x in (on_grid, spread.reshape(-1, 32)):
        x = jnp.asarray(x)
        codes, scales = mxlib.encode(x, cfg)
        want_codes, want_scales = _encode_searchsorted(x, cfg)
        np.testing.assert_array_equal(np.asarray(codes),
                                      np.asarray(want_codes))
        np.testing.assert_array_equal(np.asarray(scales),
                                      np.asarray(want_scales))


def test_encode_element_constants_describe_the_grid():
    """Each format's mantissa_bits / min_exponent rebuild its grid."""
    for el in {id(f): f for f in mxlib.FORMATS.values()}.values():
        m, e0 = el.mantissa_bits, el.min_exponent
        step = 2.0 ** (e0 - m)
        vals = [n * step for n in range(2 ** m)]
        vals += [(2 ** m + n) * 2.0 ** (e - m)
                 for e in range(e0, e0 + 16) for n in range(2 ** m)]
        want = [v for v in vals if v <= el.max_val]
        np.testing.assert_array_equal(np.asarray(el.grid), want)
