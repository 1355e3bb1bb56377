"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles
(interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops

SHAPES = [(8, 32), (16, 64), (64, 256), (128, 512), (33 * 8, 96)]
DTYPES = [jnp.float32, jnp.bfloat16]
FMTS = ["mxfp4", "mxint4"]


def _data(shape, dtype, seed=0, outliers=True):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    x = jax.random.normal(k1, shape, jnp.float32)
    if outliers:
        x = x * jnp.exp(jax.random.normal(k2, shape, jnp.float32))
    return x.astype(dtype)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("fmt", FMTS)
def test_mx_quant_matches_ref(shape, dtype, fmt):
    x = _data(shape, dtype)
    c, s = ops.mx_quantize(x, fmt, interpret=True)
    cr, sr = ops.mx_quant_ref(x, fmt)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


@pytest.mark.parametrize("mkn", [(8, 32, 16), (64, 128, 64),
                                 (128, 512, 256), (72, 96, 40)])
@pytest.mark.parametrize("fmt", FMTS)
def test_mx_matmul_matches_ref(mkn, fmt):
    m, k, n = mkn
    x = _data((m, k), jnp.float32, seed=1)
    w = _data((k, n), jnp.float32, seed=2, outliers=False) * 0.3
    wc, ws = ops.quantize_weight_for_kernel(w, fmt)
    y = ops.mx_gemm(x, wc, ws, fmt, interpret=True)
    yr = ops.mx_matmul_ref(x, wc, ws, fmt)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("fmt", FMTS)
def test_hadamard_quant_matches_ref(shape, fmt):
    x = _data(shape, jnp.float32, seed=3)
    c, s = ops.t3_quantize(x, fmt, interpret=True)
    cr, sr = ops.hadamard_quant_ref(x, fmt)
    np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_mx_matmul_quant_error_bounded():
    """The fused-quant GEMM must stay within the analytic MX error bound
    of the exact product."""
    x = _data((64, 256), jnp.float32, seed=4)
    w = _data((256, 64), jnp.float32, seed=5, outliers=False) * 0.2
    wc, ws = ops.quantize_weight_for_kernel(w)
    y = ops.mx_gemm(x, wc, ws, interpret=True)
    exact = x @ w
    rel = float(jnp.linalg.norm(y - exact) / jnp.linalg.norm(exact))
    assert rel < 0.2, rel


@pytest.mark.parametrize("mkn", [(8, 32, 16), (64, 128, 64),
                                 (128, 512, 256), (72, 96, 40)])
@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("t3", [False, True])
def test_mx_matmul_packed_matches_ref(mkn, fmt, t3):
    """Packed-native kernel (nibble codes + E8M0 bytes) vs its oracle."""
    from repro.kernels import packing
    m, k, n = mkn
    x = _data((m, k), jnp.float32, seed=8)
    w = _data((k, n), jnp.float32, seed=9, outliers=False) * 0.3
    b = packing.pack_weight(w, fmt)
    y = ops.mx_gemm_packed(x, b["codes_packed"], b["scales_e8m0"], fmt,
                           t3=t3, interpret=True)
    yr = ops.mx_matmul_packed_ref(x, b["codes_packed"], b["scales_e8m0"],
                                  fmt, t3=t3)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-5)


def test_mx_matmul_packed_layouts_agree():
    """Both weight layouts (uint8-per-code and nibble-packed) compute the
    same GEMM: the shared golden reference ties them together."""
    from repro.kernels import packing
    x = _data((16, 64), jnp.float32, seed=10)
    w = _data((64, 32), jnp.float32, seed=11, outliers=False) * 0.3
    wc, ws = ops.quantize_weight_for_kernel(w, "mxfp4")
    b = packing.pack_weight(w, "mxfp4")
    y_u8 = ops.mx_gemm(x, wc, ws, "mxfp4", interpret=True)
    y_pk = ops.mx_gemm_packed(x, b["codes_packed"], b["scales_e8m0"],
                              "mxfp4", interpret=True)
    np.testing.assert_allclose(np.asarray(y_pk), np.asarray(y_u8),
                               atol=1e-5, rtol=1e-6)


def test_mx_matmul_packed_t3_equals_separate_rotate():
    """The fused T3 prologue == hadamard rotate outside, then plain GEMM."""
    from repro.core import transforms as tfm
    from repro.kernels import packing
    x = _data((8, 96), jnp.float32, seed=12)
    w = _data((96, 32), jnp.float32, seed=13, outliers=False) * 0.3
    b = packing.pack_weight(w, "mxfp4")
    h = tfm.hadamard_matrix(32, dtype=jnp.float32)
    xr = tfm.apply_blockwise(x, h)
    y_sep = ops.mx_gemm_packed(xr, b["codes_packed"], b["scales_e8m0"],
                               "mxfp4", interpret=True)
    y_fus = ops.mx_gemm_packed(x, b["codes_packed"], b["scales_e8m0"],
                               "mxfp4", t3=True, interpret=True)
    np.testing.assert_allclose(np.asarray(y_fus), np.asarray(y_sep),
                               atol=1e-4, rtol=1e-5)


def test_mx_matmul_packed_stacked_vmap():
    """Leading (layer/expert) axes map over the kernel."""
    from repro.kernels import packing
    x = _data((3, 8, 64), jnp.float32, seed=14)
    w = _data((3, 64, 16), jnp.float32, seed=15, outliers=False) * 0.3
    b = packing.pack_weight(w, "mxfp4")
    y = ops.mx_gemm_packed(x, b["codes_packed"], b["scales_e8m0"],
                           "mxfp4", interpret=True)
    for i in range(3):
        yr = ops.mx_matmul_packed_ref(x[i], b["codes_packed"][i],
                                      b["scales_e8m0"][i], "mxfp4")
        np.testing.assert_allclose(np.asarray(y[i]), np.asarray(yr),
                                   atol=1e-4, rtol=1e-5)


def test_gemm_bf16_inputs():
    x = _data((32, 128), jnp.bfloat16, seed=6)
    w = _data((128, 32), jnp.float32, seed=7, outliers=False) * 0.3
    wc, ws = ops.quantize_weight_for_kernel(w)
    y = ops.mx_gemm(x, wc, ws, interpret=True)
    yr = ops.mx_matmul_ref(x.astype(jnp.float32), wc, ws)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("fmt", FMTS + ["mxfp8", "mxint8"])
def test_decode_tile_is_the_grid_lookup_bit_for_bit(fmt):
    """The closed-form in-kernel decode equals the format's grid, sign
    included, for every code the format has."""
    from repro.core import mx as mxlib
    from repro.kernels.mx_quant import _decode_tile
    full = mxlib.FORMATS[fmt].full_grid().astype(np.float32)
    codes = jnp.arange(len(full), dtype=jnp.int32)
    got = np.asarray(_decode_tile(codes, fmt))
    np.testing.assert_array_equal(got.view(np.uint32), full.view(np.uint32))


@pytest.mark.parametrize("fmt", FMTS)
def test_scale_application_matches_unpack_scales_e8m0(fmt):
    """Every decoded code times every E8M0 byte the packer can write
    (255 is the format's NaN) equals the packing module's dequant."""
    from repro.core import mx as mxlib
    from repro.kernels import packing
    from repro.kernels.mx_quant import _decode_tile, e8m0_to_f32
    full = mxlib.FORMATS[fmt].full_grid().astype(np.float32)
    codes = jnp.arange(len(full), dtype=jnp.int32)
    b = jnp.arange(255, dtype=jnp.uint8)
    got = _decode_tile(codes, fmt)[:, None] * e8m0_to_f32(b)[None, :]
    want = (jnp.asarray(full)[:, None]
            * packing.unpack_scales_e8m0(b)[None, :])
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


@pytest.mark.parametrize("fmt", FMTS)
def test_packed_weight_tile_is_the_dequant_in_bf16(fmt):
    """The GEMM's bf16 weight tile, built from each byte in place, is the
    packing module's dequant cast to bf16, bit for bit and in K order:
    every pair of codes in a byte, under every E8M0 byte the packer can
    write (one column per scale byte)."""
    from repro.kernels import packing
    from repro.kernels.mx_matmul import _weight_tile_bf16
    pairs = np.array([lo | (hi << 4) for hi in range(15)
                      for lo in range(15)], np.uint8)       # 225 bytes
    rows = 16 * 15                                          # 15 blocks
    wp = jnp.asarray(np.resize(pairs, rows)[:, None].repeat(255, 1))
    ws = jnp.asarray(np.arange(255, dtype=np.uint8)[None, :].repeat(15, 0))
    got = jax.jit(lambda a, b: _weight_tile_bf16(a, b, fmt))(wp, ws)
    want = packing.unpack_weight({"codes_packed": wp, "scales_e8m0": ws,
                                  "fmt": fmt}).astype(jnp.bfloat16)
    assert got.shape == want.shape == (2 * rows, 255)
    np.testing.assert_array_equal(np.asarray(got).view(np.uint16),
                                  np.asarray(want).view(np.uint16))


@pytest.mark.parametrize("fmt", FMTS + ["mxfp8", "mxint8"])
def test_quant_tile_snaps_like_encode_at_every_midpoint(fmt):
    """The in-kernel snap gives the reference's codes and scales for
    values on and 1-3 ulps either side of every midpoint, in blocks of
    every scale exponent from 2^-100 up (where a scale that is not
    exactly the power of two, as exp2 can give, moves a tie)."""
    from repro.core import mx as mxlib
    from repro.kernels.mx_quant import _format_consts, _quant_tile
    grid, mids, r_max, center = _format_consts(fmt)
    p = np.arange(-100, 126) - r_max
    base = np.ldexp(mids[None, :].astype(np.float32), p[:, None])
    vals = [base]
    for side in (np.inf, -np.inf):
        v = base
        for _ in range(3):
            v = np.nextafter(v, np.float32(side))
            vals.append(v)
    vals = np.stack(vals, -1).reshape(len(p), -1)         # (exps, n)
    blk = np.zeros((len(p), vals.shape[1], 32), np.float32)
    blk[..., 0] = np.ldexp(np.float32(grid[-1]), p)[:, None]  # sets e
    blk[..., 1], blk[..., 2] = vals, -vals
    x = jnp.asarray(blk.reshape(-1, 32))
    codes, scale = _quant_tile(x[:, None, :], grid, mids, r_max, center)
    codes_ref, scale_ref = mxlib.encode(
        x, mxlib.MXConfig(fmt=fmt, block_size=32))
    np.testing.assert_array_equal(np.asarray(codes[:, 0]),
                                  np.asarray(codes_ref))
    np.testing.assert_array_equal(np.asarray(scale[:, 0]),
                                  np.asarray(scale_ref[:, 0]))


def _on_grid_acts(m, k, fmt, rng):
    """(m, k) activations on the MX grid: every 32-block holds +-max, so
    its scale is exactly 2^p, with p in {-1, 0, 1}."""
    from repro.core import mx as mxlib
    el = mxlib.FORMATS[fmt]
    q = rng.choice(el.full_grid(), size=(m, k))
    q[:, ::32] = el.max_val * rng.choice([-1.0, 1.0], size=(m, k // 32))
    p = rng.integers(-1, 2, size=(m, k // 32))
    return (q * np.repeat(np.exp2(p), 32, axis=1)).astype(np.float32)


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("t3", [False, True])
def test_mx_gemm_packed_exact_at_decode_shape(fmt, t3):
    """At M = 64 the bf16-operand kernel equals its f32 oracle exactly
    when every partial sum is exact in f32: activations on the grid and
    weight scale bytes in 125..129. With t3 the activations are given
    pre-rotated by H, so the in-kernel rotation lands back on the grid."""
    from repro.core import transforms as tfm
    m, k, n = 64, 1024, 512
    rng = np.random.default_rng(21 + t3)
    x = jnp.asarray(_on_grid_acts(m, k, fmt, rng))
    if t3:
        x = tfm.apply_blockwise(x, tfm.hadamard_matrix(32, jnp.float32))
    codes = rng.integers(0, 15, size=(k, n), dtype=np.uint8)
    wp = jnp.asarray(codes[0::2] | (codes[1::2] << 4))
    ws = jnp.asarray(rng.integers(125, 130, size=(k // 32, n),
                                  dtype=np.uint8))
    y = ops.mx_gemm_packed(x, wp, ws, fmt, t3=t3, interpret=True)
    yr = ops.mx_matmul_packed_ref(x, wp, ws, fmt, t3=t3)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
