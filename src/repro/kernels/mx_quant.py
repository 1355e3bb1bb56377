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


def _format_consts(fmt: str):
    el = mxlib.FORMATS[fmt]
    grid = np.asarray(el.grid, np.float32)
    mids = (grid[1:] + grid[:-1]) / 2.0
    return grid, mids, el.r_max, len(el.grid) - 1  # center code


def _decode_tile(codes, grid, center):
    """uint8 symmetric code -> float value, via static compares (the grid
    has <= 8 magnitudes; Pallas forbids captured jnp LUT constants).
    Shared by every GEMM kernel variant that dequantizes codes in-tile."""
    rel = codes.astype(jnp.int32) - center
    sign = jnp.where(rel < 0, -1.0, 1.0).astype(jnp.float32)
    k = jnp.abs(rel)
    val = jnp.zeros(codes.shape, jnp.float32)
    for i, g in enumerate(grid):                  # static python loop
        val += jnp.where(k == i, float(g), 0.0)
    return sign * val


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
