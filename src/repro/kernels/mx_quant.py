"""Pallas TPU kernel: MX quantization (codes + power-of-two block scales).

Tiling: grid over (M/BM, K/BK) with BK a multiple of the MX block (32).
Each kernel instance loads a (BM, BK) tile of x into VMEM, computes the
per-32-element-block max, derives the shared exponent (Eq. 1), snaps the
scaled elements to the FP4/INT4 grid by midpoint comparison (7 VPU compares
— exact, no transcendental rounding), and writes uint8 codes plus f32
scales.

VMEM budget per instance (defaults BM=256, BK=512, f32):
  in 512 KiB + codes 128 KiB + scales 16 KiB  « 16 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import mx as mxlib

MXBLOCK = 32
_SIGN_BIT = -(1 << 31)          # the f32 sign bit, as an int32


def _format_consts(fmt: str):
    el = mxlib.FORMATS[fmt]
    grid = np.asarray(el.grid, np.float32)
    mids = (grid[1:] + grid[:-1]) / 2.0
    return grid, mids, el.r_max, len(el.grid) - 1  # center code


def _f32_bits(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _bits_f32(b):
    return jax.lax.bitcast_convert_type(b, jnp.float32)


def _decode_tile(codes, fmt):
    """Symmetric code -> float value, in closed form (Pallas forbids
    captured jnp LUT constants). With k = |code - center|, each half-grid
    is a few integer ops on an f32 bit pattern:

      int4, int8:  v = k
      fp4 e2m1:    f = the f32 whose exponent's low bits and top mantissa
                   bit are k's three bits, scaled by 1/2 (0.5, 0.75, 1,
                   1.5, 2, 3, 4, 6); v = min(f, 2f - 1) turns the two
                   subnormals (k = 0, 1) into 0, 0.5 and leaves the rest
      fp8 e4m3:    v = k * 2^-9 (subnormals, k < 8), else the f32 whose
                   exponent's low bits and top three mantissa bits are
                   k - 8 (bias moved from 7 to 127)

    and the sign is the f32 sign bit of code - center, OR-ed in. Every
    value is exact in f32, so this is bit-identical to the grid lookup
    (pinned exhaustively in tests). Shared by every kernel that
    dequantizes codes in-tile."""
    el = mxlib.FORMATS[fmt]
    rel = codes.astype(jnp.int32) - (len(el.grid) - 1)
    if el.name in ("int4", "int8"):
        return rel.astype(jnp.float32)
    k = jnp.abs(rel)
    if el.name == "fp4_e2m1":
        f = _bits_f32((k << 22) + (126 << 23))
        mag = jnp.minimum(f, 2.0 * f - 1.0)
    elif el.name == "fp8_e4m3":
        sub = k.astype(jnp.float32) * jnp.float32(2.0 ** -9)
        mag = jnp.where(k < 8, sub, _bits_f32((k << 20) + (120 << 23)))
    else:
        raise NotImplementedError(f"no in-kernel decode for {fmt!r}")
    return _bits_f32(_f32_bits(mag) | (rel & _SIGN_BIT))


def e8m0_to_f32(b):
    """E8M0 scale bytes -> power-of-two f32 scales. Mosaic has no
    uint8 -> float32 cast, so the byte widens through int32 first."""
    return jnp.exp2(b.astype(jnp.int32).astype(jnp.float32) - 127.0)


def _quant_tile(xb, grid, mids, r_max, center, axis=-1):
    """xb: (BM, nb, 32) f32 -> (codes int32, scales f32 (BM, nb)).

    ``axis`` names the 32-element block axis. The compiled kernels pass
    a transposed (nb, 32, R) tile with ``axis=1``: Mosaic cannot split
    the lane axis into 32-blocks, but it can split the sublane axis."""
    amax = jnp.max(jnp.abs(xb), axis=axis, keepdims=True)
    safe = jnp.where(amax > 0, amax, 1.0)
    e = jnp.floor(jnp.log2(safe))
    scale = jnp.where(amax > 0, jnp.exp2(e - r_max), 1.0)
    z = xb / scale
    mag = jnp.abs(z)
    idx = jnp.zeros(z.shape, jnp.int32)
    for m in mids:                      # len(grid)-1 static compares
        idx += (mag >= m).astype(jnp.int32)
    codes = center + jnp.where(z < 0, -idx, idx)
    return codes, jnp.squeeze(scale, axis)


def _mx_quant_kernel(x_ref, codes_ref, scales_ref, *, fmt):
    grid, mids, r_max, center = _format_consts(fmt)
    x = x_ref[...].astype(jnp.float32)
    bm, bk = x.shape
    xb = x.reshape(bm, bk // MXBLOCK, MXBLOCK)
    codes, scale = _quant_tile(xb, grid, mids, r_max, center)
    codes_ref[...] = codes.reshape(bm, bk).astype(jnp.uint8)
    scales_ref[...] = scale.astype(jnp.float32)


def mx_quant(x: jnp.ndarray, fmt: str = "mxfp4", *, bm: int = 256,
             bk: int = 512, interpret: bool = True):
    """x: (M, K), K % 32 == 0 -> (codes uint8 (M, K), scales (M, K//32))."""
    M, K = x.shape
    bm = min(bm, M)
    bk = min(bk, K)
    while M % bm:
        bm //= 2
    while K % bk:
        bk //= 2
    assert bk % MXBLOCK == 0
    out_shapes = (
        jax.ShapeDtypeStruct((M, K), jnp.uint8),
        jax.ShapeDtypeStruct((M, K // MXBLOCK), jnp.float32),
    )
    kern = functools.partial(_mx_quant_kernel, fmt=fmt)
    return pl.pallas_call(
        kern,
        grid=(M // bm, K // bk),
        in_specs=[pl.BlockSpec((bm, bk), lambda i, j: (i, j))],
        out_specs=(
            pl.BlockSpec((bm, bk), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bk // MXBLOCK), lambda i, j: (i, j)),
        ),
        out_shape=out_shapes,
        interpret=interpret,
    )(x)
