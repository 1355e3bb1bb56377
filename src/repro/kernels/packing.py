"""4-bit code packing — the deployable HBM layout.

The interpreter kernels address uint8 codes (one per byte); deployment
stores two 4-bit codes per byte plus one E8M0 (biased power-of-two
exponent) scale byte per 32-block. These utilities convert between the
layouts and are the source of the roofline packed-byte accounting
(`mx.packed_nbytes`).

``pack_weight``/``unpack_weight`` operate on the *contraction* axis
(axis -2, matching the qlinear weight orientation) and accept arbitrary
leading batch dims, so layer-stacked ``(L, K, N)`` and expert-batched
``(L, E, K, N)`` weights pack in one call. ``PackedWeight`` wraps the
packed arrays as a pytree so packed weights can live inside a params
tree: jit carries only the uint8 codes + scales in HBM and the dense
fp weight is reconstructed on the fly at each use site (layer-sliced
under ``lax.scan``, i.e. one layer dequantized at a time).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import mx as mxlib

# Formats that fit two codes per byte. The full symmetric code range of a
# 4-bit element grid is 2*8-1 = 15 values (codes 0..14 < 16).
PACKABLE_FMTS = ("mxfp4", "mxint4")

# Formats the KV cache can be stored in: 8-bit formats keep one code per
# byte; 4-bit formats nibble-pack along the feature axis like weights.
KV_FMTS = ("mxfp8", "mxint8", "mxfp4", "mxint4")


def _check_packable(fmt: str, block_size: int = 32, scale_mode: str = "pow2"):
    if fmt not in PACKABLE_FMTS:
        raise ValueError(
            f"fmt {fmt!r} is not 4-bit packable (supported: {PACKABLE_FMTS})")
    if scale_mode != "pow2":
        raise ValueError(
            f"E8M0 scale bytes require pow2 scales, got {scale_mode!r}")
    if block_size != 32:
        raise ValueError(f"packed layout is fixed at 32-blocks, "
                         f"got block_size={block_size}")


def pack_codes(codes: jnp.ndarray) -> jnp.ndarray:
    """uint8 codes in [0, 15] -> packed uint8, two per byte (even index in
    the low nibble). Last axis must be even."""
    *lead, d = codes.shape
    if d % 2 != 0:
        raise ValueError(f"packing axis must be even, got {d}")
    c = codes.reshape(*lead, d // 2, 2).astype(jnp.uint8)
    return (c[..., 0] | (c[..., 1] << 4)).astype(jnp.uint8)


def unpack_codes(packed: jnp.ndarray) -> jnp.ndarray:
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    *lead, h = packed.shape
    out = jnp.stack([lo, hi], axis=-1).reshape(*lead, h * 2)
    return out.astype(jnp.uint8)


def pack_scales_e8m0(scales: jnp.ndarray) -> jnp.ndarray:
    """Power-of-two f32 scales -> E8M0 byte (biased exponent, OCP MX)."""
    e = jnp.round(jnp.log2(scales.astype(jnp.float32))).astype(jnp.int32)
    return (e + 127).astype(jnp.uint8)


def unpack_scales_e8m0(b: jnp.ndarray) -> jnp.ndarray:
    return jnp.exp2(b.astype(jnp.int32) - 127).astype(jnp.float32)


def pack_weight(w: jnp.ndarray, fmt: str = "mxfp4"):
    """(*lead, K, N) float weight -> deployable bundle:
    {codes_packed (*lead, K//2, N) uint8,
     scales_e8m0 (*lead, K//32, N) uint8}.

    Blocked/packed along the contraction axis K (axis -2). Exact for any
    weight already on the MX grid (pack∘unpack is the identity there);
    otherwise it quantizes (RTN) as a side effect.
    """
    _check_packable(fmt)
    cfg = mxlib.MXConfig(fmt=fmt, block_size=32)
    if w.shape[-2] % cfg.block_size != 0:
        raise ValueError(f"contraction dim {w.shape[-2]} not divisible by "
                         f"block size {cfg.block_size}")

    def pack(m):                                  # (K, N) -> packed bytes
        codes_t, scales_t = mxlib.encode(m.T, cfg)   # blocked along K
        return (pack_codes(codes_t).T,               # (K//2, N)
                pack_scales_e8m0(scales_t).T)        # (K//32, N)

    codes, scales = mxlib.map_matrices(pack, jnp.asarray(w))
    return {"codes_packed": codes, "scales_e8m0": scales,
            "fmt": fmt, "shape": tuple(w.shape)}


def unpack_weight(bundle, dtype=jnp.float32) -> jnp.ndarray:
    cfg = mxlib.MXConfig(fmt=bundle["fmt"], block_size=32)

    def unpack(codes, scales):                    # packed bytes -> (K, N)
        return mxlib.decode(unpack_codes(codes.T),
                            unpack_scales_e8m0(scales.T), cfg, dtype).T

    return mxlib.map_matrices(unpack, jnp.asarray(bundle["codes_packed"]),
                              jnp.asarray(bundle["scales_e8m0"]))


def packed_bundle_nbytes(bundle) -> int:
    codes = bundle["codes_packed"]
    scales = bundle["scales_e8m0"]
    return int(codes.size) + int(scales.size)


# ---------------------------------------------------------------------------
# PackedWeight: packed bundle as a pytree leaf-group inside a params tree
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedWeight:
    """An MX-packed linear weight usable in place of a dense array.

    The codes/scales are pytree children (they flow through jit/scan and
    are layer-sliced like any stacked leaf); fmt and target dtype are
    static aux data. A params tree holding PackedWeight leaves serves
    directly: under ``QuantMode(backend='fused')`` ``qlinear``/``qeinsum``
    hand the codes/scales straight to the packed-native Pallas GEMM (no
    dense weight ever materialized); on the reference path they call
    :func:`maybe_dense`, so HBM keeps the 4-bit layout and the fp weight
    exists only transiently inside the compiled step.
    """

    codes_packed: jnp.ndarray   # (*lead, K//2, N) uint8
    scales_e8m0: jnp.ndarray    # (*lead, K//32, N) uint8
    fmt: str = "mxfp4"
    dtype: str = "float32"

    def tree_flatten(self):
        return (self.codes_packed, self.scales_e8m0), (self.fmt, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        """Logical dense shape (*lead, K, N)."""
        *lead, k2, n = self.codes_packed.shape
        return tuple(lead) + (k2 * 2, n)

    @property
    def ndim(self) -> int:
        return self.codes_packed.ndim

    @property
    def nbytes_packed(self) -> int:
        return int(self.codes_packed.size) + int(self.scales_e8m0.size)

    @property
    def nbytes_dense(self) -> int:
        """Byte count of the dense fp equivalent — the HBM traffic a
        non-packed weight would cost per use (bench/roofline term)."""
        n = 1
        for s in self.shape:
            n *= int(s)
        return n * jnp.dtype(self.dtype).itemsize

    def to_dense(self, dtype=None) -> jnp.ndarray:
        return unpack_weight(
            {"codes_packed": self.codes_packed,
             "scales_e8m0": self.scales_e8m0, "fmt": self.fmt},
            dtype if dtype is not None else jnp.dtype(self.dtype))

    @classmethod
    def from_dense(cls, w: jnp.ndarray, fmt: str = "mxfp4") -> "PackedWeight":
        b = pack_weight(w, fmt)
        return cls(b["codes_packed"], b["scales_e8m0"], fmt,
                   str(jnp.asarray(w).dtype))


def maybe_dense(w):
    """Resolve a PackedWeight to its dense fp array; pass others through."""
    if isinstance(w, PackedWeight):
        return w.to_dense()
    return w


# ---------------------------------------------------------------------------
# Packed KV cache: MX codes + E8M0 scale bytes along the *last* axis
# ---------------------------------------------------------------------------
#
# Weights pack along the contraction axis (-2); the KV cache packs along its
# feature axis (-1, the stored (B, S, kv_dim) layout — 32-blocks sit inside
# heads whenever head_dim % 32 == 0, i.e. every production config). 8-bit
# formats (mxfp8 / mxint8) store one code per byte; 4-bit formats
# nibble-pack two codes per byte exactly like PackedWeight.


def _kv_center(fmt: str) -> int:
    """The uint8 code that decodes to 0.0 (zero-init of a fresh cache)."""
    return len(mxlib.FORMATS[fmt].grid) - 1


def kv_fmt_bits(fmt: str) -> int:
    if fmt not in KV_FMTS:
        raise ValueError(f"fmt {fmt!r} is not a KV-cache format "
                         f"(supported: {KV_FMTS})")
    return mxlib.FORMATS[fmt].bits


def kv_encode(x: jnp.ndarray, fmt: str = "mxfp8"):
    """(..., D) float -> (codes uint8 (..., D*bits/8), scales uint8
    (..., D//32) E8M0). D % 32 == 0; pow2 scales per 32-block."""
    bits = kv_fmt_bits(fmt)
    cfg = mxlib.MXConfig(fmt=fmt, block_size=32)
    if x.shape[-1] % 32 != 0:
        raise ValueError(f"KV feature dim {x.shape[-1]} not divisible by 32")
    codes, scales = mxlib.encode(x, cfg)
    if bits == 4:
        codes = pack_codes(codes)
    return codes, pack_scales_e8m0(scales)


def kv_decode(codes: jnp.ndarray, scales_e8m0: jnp.ndarray,
              fmt: str = "mxfp8", dtype=jnp.float32) -> jnp.ndarray:
    """Inverse of :func:`kv_encode` -> (..., D) dense values."""
    bits = kv_fmt_bits(fmt)
    cfg = mxlib.MXConfig(fmt=fmt, block_size=32)
    if bits == 4:
        codes = unpack_codes(codes)
    return mxlib.decode(codes, unpack_scales_e8m0(scales_e8m0), cfg, dtype)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedKV:
    """An MX-quantized KV-cache tensor usable in place of a dense array.

    codes: (*lead, S, D*bits/8) uint8 — one code per byte (8-bit fmts) or
    nibble-packed (4-bit fmts) along the feature axis; scales: (*lead, S,
    D//32) uint8 E8M0 bytes. Registered as a pytree so a cache holding
    PackedKV leaves flows through jit / lax.scan (layer-sliced like any
    stacked leaf) and the engine's lane-merge ``tree_map`` untouched.
    ``fmt``/``dtype`` are static aux data, so dispatch on them never
    retraces."""

    codes: jnp.ndarray
    scales: jnp.ndarray
    fmt: str = "mxfp8"
    dtype: str = "float32"

    def tree_flatten(self):
        return (self.codes, self.scales), (self.fmt, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def shape(self):
        """Logical dense shape (*lead, S, D)."""
        *lead, s, db = self.codes.shape
        return tuple(lead) + (s, db * 8 // kv_fmt_bits(self.fmt))

    @property
    def ndim(self) -> int:
        return self.codes.ndim

    @property
    def nbytes_packed(self) -> int:
        return int(self.codes.size) + int(self.scales.size)

    def to_dense(self, dtype=None) -> jnp.ndarray:
        return kv_decode(self.codes, self.scales, self.fmt,
                         dtype if dtype is not None else
                         jnp.dtype(self.dtype))

    @classmethod
    def from_dense(cls, x: jnp.ndarray, fmt: str = "mxfp8") -> "PackedKV":
        c, s = kv_encode(x, fmt)
        return cls(c, s, fmt, str(jnp.asarray(x).dtype))

    @classmethod
    def zeros(cls, shape, fmt: str = "mxfp8",
              dtype=jnp.float32) -> "PackedKV":
        """Fresh cache of logical dense ``shape`` (*lead, S, D): center
        codes (which decode to 0.0) and unit E8M0 scales."""
        *lead, d = shape
        bits = kv_fmt_bits(fmt)
        if d % 32 != 0:
            raise ValueError(f"KV feature dim {d} not divisible by 32")
        center = _kv_center(fmt)
        cbyte = center | (center << 4) if bits == 4 else center
        codes = jnp.full(tuple(lead) + (d * bits // 8,), cbyte, jnp.uint8)
        scales = jnp.full(tuple(lead) + (d // 32,), 127, jnp.uint8)
        return cls(codes, scales, fmt, str(jnp.dtype(dtype)))


# ---------------------------------------------------------------------------
# Paged KV cache: a pool of fixed-size pages addressed through block tables
# ---------------------------------------------------------------------------
#
# The contiguous layouts above reserve one (max_len, D) lane per batch slot.
# The paged layout instead keeps ONE pool of N fixed-size pages (P tokens
# each) and addresses it through per-request *block tables* — (B, max_pages)
# int32 arrays of page ids — so memory tracks actual sequence lengths and
# identical prompt prefixes can share pages by reference (the serving
# engine's BlockAllocator owns the table bookkeeping; see docs/paged-kv.md).
# A page is a fixed run of MX 32-blocks whenever the cache is quantized:
# P tokens x (D * bits/8) code bytes + (D // 32) E8M0 scale bytes per token,
# exactly the PackedKV byte layout cut into page-sized runs.


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PagedKV:
    """A paged KV pool usable in place of a contiguous cache leaf.

    codes: (*lead, N, P, D*bits/8) uint8 MX codes (one per byte for 8-bit
    fmts, nibble-packed for 4-bit fmts) — or (*lead, N, P, D) *dense
    float* pages when ``fmt == 'none'`` (the unquantized paged cache).
    scales: (*lead, N, P, D//32) uint8 E8M0 bytes, or ``None`` for dense
    pages. Registered as a pytree (``None`` scales flatten to an empty
    subtree), so a cache of PagedKV leaves flows through jit / lax.scan
    layer slicing untouched; ``fmt``/``dtype`` are static aux data.

    Logical position ``t`` of a request lives at page
    ``block_table[t // P]``, row ``t % P`` — every reader/writer goes
    through that indirection (``models.layers`` write helpers, the paged
    flash-decode kernel's block-table grid, :meth:`gather_dense`)."""

    codes: jnp.ndarray
    scales: Optional[jnp.ndarray]
    fmt: str = "none"
    dtype: str = "float32"

    def tree_flatten(self):
        return (self.codes, self.scales), (self.fmt, self.dtype)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], *aux)

    @property
    def page_size(self) -> int:
        return self.codes.shape[-2]

    @property
    def n_pages(self) -> int:
        return self.codes.shape[-3]

    @property
    def feature_dim(self) -> int:
        """Logical dense feature width D."""
        if self.fmt == "none":
            return self.codes.shape[-1]
        return self.codes.shape[-1] * 8 // kv_fmt_bits(self.fmt)

    @property
    def ndim(self) -> int:
        return self.codes.ndim

    @classmethod
    def zeros(cls, shape, fmt: str = "none", dtype=jnp.float32) -> "PagedKV":
        """Fresh pool of logical dense ``shape`` (*lead, N, P, D)."""
        *lead, n, p, d = shape
        if fmt == "none":
            return cls(jnp.zeros((*lead, n, p, d), jnp.dtype(dtype)), None,
                       "none", str(jnp.dtype(dtype)))
        bits = kv_fmt_bits(fmt)
        if d % 32 != 0:
            raise ValueError(f"KV feature dim {d} not divisible by 32")
        center = _kv_center(fmt)
        cbyte = center | (center << 4) if bits == 4 else center
        codes = jnp.full((*lead, n, p, d * bits // 8), cbyte, jnp.uint8)
        scales = jnp.full((*lead, n, p, d // 32), 127, jnp.uint8)
        return cls(codes, scales, fmt, str(jnp.dtype(dtype)))

    def gather_dense(self, block_tables: jnp.ndarray,
                     dtype=None) -> jnp.ndarray:
        """Materialize the logical contiguous view of ``block_tables``
        (B, max_pages) int32: a dense (B, max_pages*P, D) array — page j
        of lane b supplies rows [j*P, (j+1)*P). The reference attention
        path reads the cache through this gather; rows past a lane's
        fill come from whatever page id sits in the unused table slot
        (the engine parks them on the scrap page) and stay masked by
        ``kv_len``. Pool must be layer-sliced (no lead dims)."""
        if self.codes.ndim != 3:
            raise ValueError("gather_dense expects a layer-sliced pool "
                             f"(N, P, ·); got ndim={self.codes.ndim}")
        B, maxp = block_tables.shape
        P = self.page_size
        dt = dtype if dtype is not None else jnp.dtype(self.dtype)
        c = jnp.take(self.codes, block_tables, axis=0)     # (B, maxp, P, ·)
        c = c.reshape(B, maxp * P, c.shape[-1])
        if self.fmt == "none":
            return c.astype(dt)
        s = jnp.take(self.scales, block_tables, axis=0)
        s = s.reshape(B, maxp * P, s.shape[-1])
        return kv_decode(c, s, self.fmt, dt)
