"""Microscaling (MX) quantization — OCP MX spec (Rouhani et al., 2023b).

Implements Eq. (1) of the paper:

    s_i = 2^( floor(log2(max_{j in I_i} |x_j|)) - r_max )
    Q(x)_j = s_i * Q_e(x_j / s_i)

for block-wise power-of-two dynamic scaling with low-precision element
formats (FP4 E2M1, INT4, FP8 E4M3, FP6 E2M3), plus the NVFP4 variant
(B=16, FP8-quantized non-pow2 scales) used in Appendix E.6.

Everything here is "fake-quant": values stay in the compute dtype but land
exactly on the element grid times the block scale. The packed-code path
(uint8 codes + fp32 scales) used by the Pallas kernels lives in
``encode``/``decode``. A straight-through estimator makes every op
differentiable so transformations can be learned through the quantizer
(Section 3.2).
"""
from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Element formats
# ---------------------------------------------------------------------------

# FP4 E2M1 positive grid per OCP MX spec: max exponent r_max = 2, max = 6.0
_FP4_POS = np.array([0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0], dtype=np.float64)
# FP6 E2M3 positive grid: mantissa 3 bits, exponents {2^0(subnormal step .125) .. 2^2}
_FP6_POS = np.concatenate(
    [
        np.arange(0, 8) / 8.0,          # subnormals of exponent 0: 0 .. 0.875
        (8 + np.arange(0, 8)) / 8.0,    # e=0: 1.0 .. 1.875
        (8 + np.arange(0, 8)) / 4.0,    # e=1: 2.0 .. 3.75
        (8 + np.arange(0, 8)) / 2.0,    # e=2: 4.0 .. 7.5
    ]
).astype(np.float64)


def _fp8_e4m3_grid() -> np.ndarray:
    """Positive representable values of FP8 E4M3 (OCP variant, max 448)."""
    vals = [0.0]
    for e in range(0, 16):
        for m in range(0, 8):
            if e == 0:
                v = (m / 8.0) * 2.0 ** (-6)
            else:
                v = (1 + m / 8.0) * 2.0 ** (e - 7)
            vals.append(v)
    vals = sorted(set(v for v in vals if v <= 448.0))
    return np.array(vals, dtype=np.float64)


@dataclasses.dataclass(frozen=True)
class ElementFormat:
    """A symmetric low-precision element format defined by its value grid."""

    name: str
    bits: int
    grid: tuple  # positive half-grid including 0, ascending
    r_max: int   # max representable power-of-two exponent (for scale calc)

    @property
    def max_val(self) -> float:
        return float(self.grid[-1])

    def full_grid(self) -> np.ndarray:
        pos = np.asarray(self.grid, dtype=np.float64)
        return np.concatenate([-pos[::-1][:-1], pos])


FP4 = ElementFormat("fp4_e2m1", 4, tuple(_FP4_POS.tolist()), r_max=2)
FP6 = ElementFormat("fp6_e2m3", 6, tuple(_FP6_POS.tolist()), r_max=2)
FP8 = ElementFormat("fp8_e4m3", 8, tuple(_fp8_e4m3_grid().tolist()), r_max=8)
# INT4 symmetric: codes -7..7. r_max chosen so max code magnitude (7) sits
# just inside [2^r_max, 2^(r_max+1)) => r_max = 2 (MR-GPTQ convention).
INT4 = ElementFormat(
    "int4", 4, tuple(np.arange(0.0, 8.0).tolist()), r_max=2
)
INT8 = ElementFormat("int8", 8, tuple(np.arange(0.0, 128.0).tolist()), r_max=6)

FORMATS = {f.name: f for f in (FP4, FP6, FP8, INT4, INT8)}
FORMATS.update({"mxfp4": FP4, "mxint4": INT4, "mxfp8": FP8, "mxfp6": FP6,
                "mxint8": INT8})


@dataclasses.dataclass(frozen=True)
class MXConfig:
    """Configuration of an MX quantizer.

    ``block_size`` divides the *last* axis of the tensor being quantized.
    ``scale_mode``: 'pow2' (OCP MX, Eq. 1) or 'fp8' (NVFP4-style real scales
    quantized to FP8 E4M3).
    """

    fmt: str = "mxfp4"
    block_size: int = 32
    scale_mode: str = "pow2"
    stochastic: bool = False  # stochastic rounding for the element quantizer

    @property
    def element(self) -> ElementFormat:
        return FORMATS[self.fmt]


NVFP4 = MXConfig(fmt="mxfp4", block_size=16, scale_mode="fp8")


# ---------------------------------------------------------------------------
# Element quantizer Q_e — snap to nearest grid point (ties-to-even-ish via
# midpoint comparison; the grids are tiny so a bucketize is exact & fast).
# ---------------------------------------------------------------------------

def _snap_to_grid(x: jnp.ndarray, grid: np.ndarray) -> jnp.ndarray:
    """Round each element of ``x`` to the nearest value in ``grid``.

    grid: ascending positive half-grid including 0. Symmetric handling of
    sign. Values beyond the max saturate.
    """
    g = jnp.asarray(grid, dtype=x.dtype)
    mids = (g[1:] + g[:-1]) / 2.0
    mag = jnp.abs(x)
    idx = jnp.searchsorted(mids, mag, side="right")  # 0..len(grid)-1
    snapped = g[idx]
    return jnp.sign(x) * snapped


def _snap_stochastic(x: jnp.ndarray, grid: np.ndarray,
                     key: jax.Array) -> jnp.ndarray:
    """Stochastic rounding between the two bracketing grid points."""
    g = jnp.asarray(grid, dtype=x.dtype)
    mag = jnp.clip(jnp.abs(x), 0.0, g[-1])
    hi_idx = jnp.clip(jnp.searchsorted(g, mag, side="left"), 0, len(grid) - 1)
    lo_idx = jnp.clip(hi_idx - 1, 0, len(grid) - 1)
    lo, hi = g[lo_idx], g[hi_idx]
    span = jnp.where(hi > lo, hi - lo, 1.0)
    p_hi = (mag - lo) / span
    u = jax.random.uniform(key, x.shape, dtype=x.dtype)
    snapped = jnp.where(u < p_hi, hi, lo)
    return jnp.sign(x) * snapped


# ---------------------------------------------------------------------------
# Block scales
# ---------------------------------------------------------------------------

def compute_scales(x: jnp.ndarray, cfg: MXConfig) -> jnp.ndarray:
    """Per-block scales for the last axis of ``x``.

    Returns an array of shape x.shape[:-1] + (x.shape[-1] // B,).
    """
    B = cfg.block_size
    *lead, d = x.shape
    if d % B != 0:
        raise ValueError(f"last dim {d} not divisible by block size {B}")
    xb = x.reshape(*lead, d // B, B)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    if cfg.scale_mode == "pow2":
        # s = 2^(floor(log2 amax) - r_max); amax==0 -> scale 1 (block is 0).
        safe = jnp.where(amax > 0, amax, 1.0)
        e = jnp.floor(jnp.log2(safe.astype(jnp.float32)))
        s = jnp.exp2(e - cfg.element.r_max)
        return jnp.where(amax > 0, s, 1.0).astype(jnp.float32)
    elif cfg.scale_mode == "fp8":
        # NVFP4: real scale amax / max_code, itself snapped to FP8 E4M3.
        s = amax.astype(jnp.float32) / cfg.element.max_val
        s = _snap_to_grid(s, np.asarray(FP8.grid))
        return jnp.where(s > 0, s, 1.0)
    raise ValueError(f"unknown scale_mode {cfg.scale_mode}")


# ---------------------------------------------------------------------------
# Fake-quantization (value-domain) with straight-through estimator
# ---------------------------------------------------------------------------

def _quantize_value(x: jnp.ndarray, cfg: MXConfig,
                    key: jax.Array | None = None) -> jnp.ndarray:
    B = cfg.block_size
    *lead, d = x.shape
    scales = compute_scales(x, cfg)  # (*lead, d//B)
    xb = x.reshape(*lead, d // B, B)
    z = xb / scales[..., None].astype(x.dtype)
    grid = np.asarray(cfg.element.grid)
    if cfg.stochastic and key is not None:
        q = _snap_stochastic(z, grid, key)
    else:
        q = _snap_to_grid(z, grid)
    out = q * scales[..., None].astype(x.dtype)
    return out.reshape(*lead, d)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def quantize_ste(x: jnp.ndarray, fmt: str, block_size: int, scale_mode: str):
    cfg = MXConfig(fmt=fmt, block_size=block_size, scale_mode=scale_mode)
    return _quantize_value(x, cfg)


def _q_fwd(x, fmt, block_size, scale_mode):
    return quantize_ste(x, fmt, block_size, scale_mode), None


def _q_bwd(fmt, block_size, scale_mode, _, g):
    # Straight-through: d quantize / dx = I.
    return (g,)


quantize_ste.defvjp(_q_fwd, _q_bwd)


def quantize(x: jnp.ndarray, cfg: MXConfig | None = None, *,
             ste: bool = True, key: jax.Array | None = None) -> jnp.ndarray:
    """MX fake-quantize ``x`` along its last axis. STE-differentiable."""
    cfg = cfg or MXConfig()
    if cfg.stochastic and key is not None:
        return _quantize_value(x, cfg, key)
    if ste:
        return quantize_ste(x, cfg.fmt, cfg.block_size, cfg.scale_mode)
    return _quantize_value(x, cfg)


def quantization_mse(x: jnp.ndarray, cfg: MXConfig | None = None) -> jnp.ndarray:
    """Mean squared quantization error of x under cfg (Definition 3.2 with
    T = identity)."""
    cfg = cfg or MXConfig()
    q = _quantize_value(x, cfg)
    return jnp.mean((x - q) ** 2)


def blockwise_error(x: jnp.ndarray, q: jnp.ndarray, block_size: int) -> jnp.ndarray:
    """Per-MX-block squared error E_B^i (Sec. 3.1 numerical analysis)."""
    *lead, d = x.shape
    e = ((x - q) ** 2).reshape(*lead, d // block_size, block_size)
    return jnp.mean(e, axis=(-1,) + tuple(range(len(lead))))


# ---------------------------------------------------------------------------
# Packed-code path (used by kernels & serving): uint8 codes + fp32 scales
# ---------------------------------------------------------------------------

def encode(x: jnp.ndarray, cfg: MXConfig | None = None):
    """Quantize and return (codes uint8, scales fp32).

    Codes index the *full* symmetric grid: code = index into
    ``full_grid()`` (length 2*len(grid)-1), so decoding is a table lookup.
    """
    cfg = cfg or MXConfig()
    B = cfg.block_size
    *lead, d = x.shape
    scales = compute_scales(x, cfg)
    xb = x.reshape(*lead, d // B, B)
    z = (xb / scales[..., None].astype(x.dtype)).reshape(*lead, d)
    # magnitude-symmetric code (matches _snap_to_grid tie behaviour and the
    # Pallas kernels): code = center ± halfgrid_index(|z|)
    g = jnp.asarray(cfg.element.grid, dtype=jnp.float32)
    mids = (g[1:] + g[:-1]) / 2.0
    zf = z.astype(jnp.float32)
    idx = jnp.searchsorted(mids, jnp.abs(zf), side="right")
    center = len(cfg.element.grid) - 1
    codes = center + jnp.where(zf < 0, -idx, idx)
    return codes.astype(jnp.uint8), scales


@functools.lru_cache(maxsize=None)
def _full_grid_np(fmt: str) -> np.ndarray:
    """Cached full symmetric grid (decode LUT) per element format."""
    return FORMATS[fmt].full_grid()


def decode(codes: jnp.ndarray, scales: jnp.ndarray,
           cfg: MXConfig | None = None, dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of ``encode``: one LUT gather (``jnp.take``) + a per-block
    scale multiply — the whole dequant cost of the fast fallback path."""
    cfg = cfg or MXConfig()
    B = cfg.block_size
    full = jnp.asarray(_full_grid_np(cfg.fmt), dtype=dtype)
    vals = jnp.take(full, codes.astype(jnp.int32), axis=0)
    *lead, d = vals.shape
    vb = vals.reshape(*lead, d // B, B) * scales[..., None].astype(dtype)
    return vb.reshape(*lead, d)


def map_matrices(fn, *xs):
    """Apply ``fn`` to one trailing (K, N) matrix of ``xs`` at a time.

    For offline whole-model passes over layer- or expert-stacked weights
    (RTN, packing). Run over a whole (L, K, N) stack at once, the
    32-blocked (…, K//32, 32) temporaries pad 4x on a TPU, and a stack of
    a 1B model's FFN weights then overflows the device; one matrix at a
    time they stay small. ``fn`` returns an array or a pytree of arrays,
    each with the matrices' leading dims restored."""
    lead = xs[0].shape[:-2]
    if not lead:
        return fn(*xs)
    flat = tuple(x.reshape((-1,) + x.shape[-2:]) for x in xs)
    out = jax.lax.map(lambda a: fn(*a), flat)
    return jax.tree.map(lambda o: o.reshape(lead + o.shape[1:]), out)


def packed_nbytes(shape: Sequence[int], cfg: MXConfig | None = None) -> int:
    """Deployable byte count: 4-bit packed codes + 1 byte scale per block.

    Used for roofline memory terms (the uint8 layout is only for the CPU
    interpreter)."""
    cfg = cfg or MXConfig()
    n = int(np.prod(shape))
    code_bytes = n * cfg.element.bits // 8
    scale_bytes = n // cfg.block_size  # E8M0 shared exponent = 1 byte
    return code_bytes + scale_bytes
