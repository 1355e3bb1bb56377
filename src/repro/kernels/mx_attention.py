"""Pallas TPU kernel: flash-decode attention over an MX-quantized KV cache.

The serving decode hot path after PR-2 moved every GEMM onto packed
weights: one query token per lane attends against the whole KV cache, so
decode cost is dominated by *streaming the cache out of HBM*. Storing the
cache as MX codes (per-32-block E8M0 scales along the feature axis, see
``packing.PackedKV``) cuts that traffic ~2x (mxfp8/mxint8) or ~4x
(mxfp4/mxint4) vs bf16 — and this kernel consumes the packed bytes
*directly*: codes + scale bytes are DMA'd to VMEM per KV chunk, decoded
in-tile, and fed to an online-softmax accumulation. No dense fp cache is
ever materialized.

Shape contract (the dispatch wrapper ``ops.mx_flash_decode`` enforces it
and falls back to the jnp reference off-contract):

  q         (B, H, Dh) float      — one decode token per lane
  k/v codes (B, S, D*bits/8) u8   — D = kvh*Dh, nibble-packed when 4-bit
  k/v scales(B, S, D//32)    u8   — E8M0 bytes
  q_pos     (B,) i32              — absolute query positions (per lane)
  kv_len    (B,) i32              — cache fill per lane (rows >= kv_len
                                    are stale and masked)
  window    static int            — sliding-window size (0 = full causal)

Grid: (B, S/BS) with the KV-chunk axis innermost, so the fp32 accumulator
and the running max / normalizer stay resident in VMEM scratch across
the KV sweep (the GEMM kernels' K-innermost discipline). ``q_pos`` and
``kv_len`` ride in as scalar-prefetch operands (SMEM), like the paged
kernels' block table.

Layout inside the kernel (what Mosaic accepts): the wrapper views q
head-leading as (B, kvh, G, Dh), so GQA is a leading batch axis of the
score and PV contractions. A decoded KV tile is built *feature-major*:
the (BS, D) bytes are transposed to (D, BS), whose sublane axis then
splits into 32-blocks for the E8M0 scales and into (kvh, Dh) heads —
Mosaic can split the sublane axis of a tile but not its lane axis.

Masking is per *row* (lane): causal ``kp <= q_pos``, fill ``kp < kv_len``
and window ``kp > q_pos - window`` — identical key selection to
``models.layers.attention``, so the kernel slots under the model's decode
step with no semantic change. Odd tails (kv_len not a multiple of BS) are
masked chunks, which are exact no-ops of the online softmax.

Off-TPU the kernels run in interpret mode (the CPU tests); on TPU they
compile through Mosaic (``tests/test_tpu_compile.py`` compiles them at
TinyLlama widths for a described v5e).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .mx_quant import (MXBLOCK, _decode_tile, _format_consts, _quant_tile,
                       e8m0_to_f32)
from . import packing

NEG_INF = -1e30
E8M0_BIAS = 127


def _pick_chunk(S: int, bs: int, explicit: bool = False) -> int:
    """KV-chunk width: shrink ``bs`` (halving) until it divides S.

    ``explicit=True`` marks a caller-chosen width: it is honored as-is
    (clamped only to S) and a non-dividing width raises instead of being
    silently halved — the override that lets tests drive the
    multi-chunk / block-table grid in CPU interpret mode, where the
    *default* collapses to a single chunk (the chunk grid exists for
    TPU VMEM)."""
    if explicit:
        bs = min(bs, S)
        if bs < 1 or S % bs:
            raise ValueError(
                f"explicit KV chunk width bs={bs} does not divide the "
                f"cache length S={S}; pick a divisor of S (or leave bs "
                f"unset for the backend default)")
        return bs
    bs = min(bs, S)
    while S % bs:
        bs //= 2
    return max(bs, 1)


def _decode_kv_tile(codes, scales, fmt, bits, kvh, dh):
    """(BS, D*bits/8) codes + (BS, D//32) E8M0 bytes -> (kvh, dh, BS) f32,
    feature-major (see the module docstring)."""
    c = codes.astype(jnp.int32).T                           # (D*bits/8, BS)
    if bits == 4:
        # pack_codes order: feature 2i in the low nibble of byte i
        c = jnp.stack([c & 0xF, (c >> 4) & 0xF], axis=1).reshape(
            -1, c.shape[1])
    vals = _decode_tile(c, fmt)                             # (D, BS)
    s = e8m0_to_f32(scales).T                               # (D//32, BS)
    d, bs = vals.shape
    out = vals.reshape(d // MXBLOCK, MXBLOCK, bs) * s[:, None, :]
    return out.reshape(kvh, dh, bs)


def _online_softmax_step(s, ok, v, m_sc, l_sc, acc_sc):
    """One online-softmax update over a KV tile. s (kvh, R, S) scores;
    ok a mask broadcastable to s; v (kvh, dh, S) feature-major values;
    m_sc / l_sc (kvh, R, 1) and acc_sc (kvh, R, dh) VMEM scratch. A
    fully masked tile is an exact no-op."""
    s = jnp.where(ok, s, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_sc[...] = m_new
    acc_sc[...] = acc_sc[...] * corr + jnp.einsum(
        "krs,kds->krd", p, v, preferred_element_type=jnp.float32)


def _softmax_init(m_sc, l_sc, acc_sc):
    m_sc[...] = jnp.full_like(m_sc, NEG_INF)
    l_sc[...] = jnp.zeros_like(l_sc)
    acc_sc[...] = jnp.zeros_like(acc_sc)


def _softmax_scratch(kvh, rows, dh):
    return [pltpu.VMEM((kvh, rows, 1), jnp.float32),
            pltpu.VMEM((kvh, rows, 1), jnp.float32),
            pltpu.VMEM((kvh, rows, dh), jnp.float32)]


def _flash_decode_kernel(pos_ref, len_ref, q_ref, kc_ref, ks_ref, vc_ref,
                         vs_ref, o_ref, m_sc, l_sc, acc_sc, *, fmt, bits,
                         window, kvh, dh, n_chunks):
    b = pl.program_id(0)
    c = pl.program_id(1)
    bs = kc_ref.shape[1]

    @pl.when(c == 0)
    def _init():
        _softmax_init(m_sc, l_sc, acc_sc)

    q = q_ref[0].astype(jnp.float32)                        # (kvh, G, Dh)
    scale = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    k = _decode_kv_tile(kc_ref[0], ks_ref[0], fmt, bits, kvh, dh)
    v = _decode_kv_tile(vc_ref[0], vs_ref[0], fmt, bits, kvh, dh)
    s = jnp.einsum("kgd,kds->kgs", q, k,
                   preferred_element_type=jnp.float32) * scale

    kp = c * bs + jax.lax.broadcasted_iota(jnp.int32, (1, 1, bs), 2)
    qp = pos_ref[b]
    ok = (kp <= qp) & (kp < len_ref[b])
    if window:
        ok = ok & (kp > qp - window)
    _online_softmax_step(s, ok, v, m_sc, l_sc, acc_sc)

    @pl.when(c == n_chunks - 1)
    def _finalize():
        o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


def _lanes(x, B):
    """Scalar-or-(B,) int operand -> (B,) int32."""
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32).reshape(-1), (B,))


def mx_flash_decode(q: jnp.ndarray, k_codes: jnp.ndarray,
                    k_scales: jnp.ndarray, v_codes: jnp.ndarray,
                    v_scales: jnp.ndarray, q_pos: jnp.ndarray,
                    kv_len: jnp.ndarray, fmt: str = "mxfp8", *,
                    window: int = 0, bs: int = 512,
                    explicit_bs: bool = False,
                    interpret: bool = True) -> jnp.ndarray:
    """Flash-decode attention over packed MX KV. Returns (B, H, Dh) f32.

    See the module docstring for the shape contract. ``bs`` is the KV
    chunk width (shrunk to divide S; ``explicit_bs=True`` honors it
    exactly and raises when it cannot divide S)."""
    B, H, Dh = q.shape
    bits = packing.kv_fmt_bits(fmt)
    S = k_codes.shape[1]
    D = k_codes.shape[2] * 8 // bits
    kvh = D // Dh
    assert H % kvh == 0 and kvh * Dh == D, (q.shape, k_codes.shape)
    assert D % MXBLOCK == 0, (D,)
    assert k_scales.shape == (B, S, D // MXBLOCK), k_scales.shape
    G = H // kvh
    bs = _pick_chunk(S, bs, explicit=explicit_bs)
    n_chunks = S // bs
    kern = functools.partial(_flash_decode_kernel, fmt=fmt, bits=bits,
                             window=window, kvh=kvh, dh=Dh,
                             n_chunks=n_chunks)
    db = k_codes.shape[2]
    ns = D // MXBLOCK
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_chunks),
        in_specs=[
            pl.BlockSpec((1, kvh, G, Dh), lambda i, c, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, bs, db), lambda i, c, *_: (i, c, 0)),
            pl.BlockSpec((1, bs, ns), lambda i, c, *_: (i, c, 0)),
            pl.BlockSpec((1, bs, db), lambda i, c, *_: (i, c, 0)),
            pl.BlockSpec((1, bs, ns), lambda i, c, *_: (i, c, 0)),
        ],
        out_specs=pl.BlockSpec((1, kvh, G, Dh),
                               lambda i, c, *_: (i, 0, 0, 0)),
        scratch_shapes=_softmax_scratch(kvh, G, Dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, G, Dh), jnp.float32),
        interpret=interpret,
    )(_lanes(q_pos, B), _lanes(kv_len, B), q.reshape(B, kvh, G, Dh),
      k_codes, k_scales, v_codes, v_scales)
    return out.reshape(B, H, Dh)


# ---------------------------------------------------------------------------
# Paged flash decode: block-table indirection over a shared page pool
# ---------------------------------------------------------------------------
#
# Same online-softmax body as the contiguous kernel — the only change is
# WHERE a KV chunk comes from. The contiguous grid slices lane b's own
# (S, ·) cache at chunk c; the paged grid reads page ``block_tables[b, c]``
# of one pool shared by every lane. The block table rides in as a
# *scalar-prefetch* operand (``pltpu.PrefetchScalarGridSpec``), so the
# BlockSpec index maps can address pages before the body runs — the DMA
# engine gathers the right page per grid step and no dense, contiguous
# copy of the cache is ever materialized. Chunk width == page size: a page
# holds positions [c*P, (c+1)*P) of its lane, so the position iota, the
# per-lane masks, and the accumulator discipline carry over unchanged.
# Table slots past a lane's fill may hold any valid page id (the engine
# parks them on the scrap page); their rows are masked by ``kv_len``
# exactly like the contiguous kernel's stale tail.


def _flash_decode_paged_kernel(bt_ref, *refs, **kw):
    # bt_ref (the prefetched block table) is consumed by the index maps;
    # the body is position-identical to the contiguous kernel because a
    # page IS chunk c of its lane's logical cache.
    del bt_ref
    _flash_decode_kernel(*refs, **kw)


def mx_flash_decode_paged(q: jnp.ndarray, k_codes: jnp.ndarray,
                          k_scales: jnp.ndarray, v_codes: jnp.ndarray,
                          v_scales: jnp.ndarray,
                          block_tables: jnp.ndarray, q_pos: jnp.ndarray,
                          kv_len: jnp.ndarray, fmt: str = "mxfp8", *,
                          window: int = 0,
                          interpret: bool = True) -> jnp.ndarray:
    """Flash-decode attention over a *paged* packed MX KV pool.

    q          (B, H, Dh) float    — one decode token per lane
    k/v codes  (N, P, D*bits/8) u8 — page pool shared by all lanes
    k/v scales (N, P, D//32)    u8 — E8M0 bytes
    block_tables (B, maxp) i32     — page id of lane b's chunk c
    q_pos/kv_len (B,) i32          — per-lane positions / fills

    Returns (B, H, Dh) f32. Grid (B, maxp) with the page axis innermost;
    page ``block_tables[b, c]`` supplies logical positions
    [c*P, (c+1)*P) of lane b."""
    B, H, Dh = q.shape
    bits = packing.kv_fmt_bits(fmt)
    N, P, db = k_codes.shape
    D = db * 8 // bits
    kvh = D // Dh
    maxp = block_tables.shape[1]
    assert H % kvh == 0 and kvh * Dh == D, (q.shape, k_codes.shape)
    assert D % MXBLOCK == 0, (D,)
    ns = D // MXBLOCK
    assert k_scales.shape == (N, P, ns), k_scales.shape
    G = H // kvh
    kern = functools.partial(_flash_decode_paged_kernel, fmt=fmt,
                             bits=bits, window=window, kvh=kvh, dh=Dh,
                             n_chunks=maxp)

    def page(i, c, bt, *_):
        return (bt[i, c], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, maxp),
        in_specs=[
            pl.BlockSpec((1, kvh, G, Dh), lambda i, c, *_: (i, 0, 0, 0)),
            pl.BlockSpec((1, P, db), page),
            pl.BlockSpec((1, P, ns), page),
            pl.BlockSpec((1, P, db), page),
            pl.BlockSpec((1, P, ns), page),
        ],
        out_specs=pl.BlockSpec((1, kvh, G, Dh),
                               lambda i, c, *_: (i, 0, 0, 0)),
        scratch_shapes=_softmax_scratch(kvh, G, Dh),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, kvh, G, Dh), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), _lanes(q_pos, B),
      _lanes(kv_len, B), q.reshape(B, kvh, G, Dh), k_codes, k_scales,
      v_codes, v_scales)
    return out.reshape(B, H, Dh)


# ---------------------------------------------------------------------------
# Paged flash prefill: (q_block x kv_block) grid + fused quantize-on-append
# ---------------------------------------------------------------------------
#
# Chunked prefill attends a (B, C) token chunk against (a) the lane's
# committed prefix, which lives as packed MX pages in the pool, and (b) the
# chunk itself (causal self-attention). The jnp path pays for that twice:
# it quantizes the chunk, scatters it into the pool, then gathers + decodes
# the WHOLE logical cache densely. This kernel reads the prefix pages
# through the same scalar-prefetch block-table ABI as
# ``mx_flash_decode_paged`` (decoded in-tile by ``_decode_kv_tile``) and
# handles the chunk itself by quantizing the dense K/V tile *inside the
# kernel* (``_quant_kv_tile`` — the ``mx_quant`` tile body followed by the
# packed-byte layout of ``packing.kv_encode``): the packed bytes stream out
# as extra kernel outputs for the caller to scatter into the pool, and the
# decode of those same bytes feeds the attention tile. Dense chunk K/V
# never round-trips HBM, and attending the roundtripped values keeps the
# kernel bit-identical to write-then-read of the fallback path.
#
# Grid (B, C/qb, maxp + C/kvb), KV axis innermost so the per-(lane, q-block)
# f32 accumulator + running max / normalizer stay VMEM-resident across the
# sweep. KV steps c < maxp read page ``block_tables[b, c]`` (positions
# [c*P, (c+1)*P), valid iff ``kp < start`` — the committed prefix — so a
# mid-page prefix-cache resume never double-counts rows the chunk re-fills);
# steps c >= maxp read kv-block c - maxp of the dense chunk at positions
# ``start + (c - maxp)*kvb + iota``. Causal / fill / window masks apply to
# both sources exactly as in ``models.layers.attention``. Steps whose keys
# are all masked for the q-block — pages with no committed row, chunk
# blocks wholly after the q-block — skip their attention work, which is an
# exact no-op of the online softmax anyway.
#
# Queries are laid out head-leading as (B, kvh, C*G, Dh): row r of a
# q-block is query r // G of the block, group head r % G.


def _quant_kv_tile(x, fmt, grid, mids, r_max, center, bits, kvh, dh):
    """In-kernel MX encode of a dense (bs, D) f32 tile.

    Returns (code bytes (bs, D*bits/8) u8, E8M0 scale bytes (bs, D//32)
    u8, roundtrip values (kvh, dh, bs) f32 feature-major). The bytes are
    bit-identical to ``packing.kv_encode`` (same ``_quant_tile`` snap,
    same nibble order, same E8M0 bias) and the roundtrip is computed by
    decoding those very bytes, so attending the roundtrip == writing the
    bytes to the pool and reading them back."""
    bs, d = x.shape
    xb = x.T.reshape(d // MXBLOCK, MXBLOCK, bs)
    codes, scale = _quant_tile(xb, grid, mids, r_max, center, axis=1)
    sbyte = (jnp.round(jnp.log2(scale)).astype(jnp.int32)
             + E8M0_BIAS)                          # == pack_scales_e8m0
    vals = _decode_tile(codes, fmt)
    rt = (vals * e8m0_to_f32(sbyte)[:, None, :]).reshape(kvh, dh, bs)
    codes = codes.reshape(d, bs)                   # feature-major
    if bits == 4:                                  # pack_codes nibble order
        cb = codes.reshape(d // 2, 2, bs)
        codes = cb[:, 0] | (cb[:, 1] << 4)
    return codes.T.astype(jnp.uint8), sbyte.T.astype(jnp.uint8), rt


def _flash_prefill_kernel(bt_ref, start_ref, len_ref, q_ref, kcp_ref,
                          ksp_ref, vcp_ref, vsp_ref, kd_ref, vd_ref,
                          o_ref, kc_ref, ks_ref, vc_ref, vs_ref,
                          m_sc, l_sc, acc_sc, *, fmt, bits, window, kvh,
                          dh, group, maxp, n_cb, qb, kvb, page):
    del bt_ref          # consumed by the index maps (scalar prefetch)
    grid, mids, r_max, center = _format_consts(fmt)
    b = pl.program_id(0)
    j = pl.program_id(1)
    c = pl.program_id(2)
    n_kv = maxp + n_cb

    @pl.when(c == 0)
    def _init():
        _softmax_init(m_sc, l_sc, acc_sc)

    q = q_ref[0].astype(jnp.float32)               # (kvh, qb*G, Dh)
    sm = 1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32))
    start = start_ref[b]
    kl = len_ref[b]
    q0 = start + j * qb                            # first query position
    qp = q0 + jax.lax.broadcasted_iota(
        jnp.int32, (1, qb * group, 1), 1) // group  # (1, qb*G, 1)

    def _update(k, v, kp, src_ok):
        # One online-softmax step over a (kvh, dh, s) KV tile at logical
        # positions kp (1, 1, s), with src_ok masking rows the source
        # doesn't own.
        s = jnp.einsum("krd,kds->krs", q, k,
                       preferred_element_type=jnp.float32) * sm
        ok = src_ok & (kp < kl) & (kp <= qp)
        if window:
            ok = ok & (kp > qp - window)
        _online_softmax_step(s, ok, v, m_sc, l_sc, acc_sc)

    @pl.when((c < maxp) & (c * page < start))
    def _prefix_page():
        k = _decode_kv_tile(kcp_ref[0], ksp_ref[0], fmt, bits, kvh, dh)
        v = _decode_kv_tile(vcp_ref[0], vsp_ref[0], fmt, bits, kvh, dh)
        kp = c * page + jax.lax.broadcasted_iota(jnp.int32, (1, 1, page), 2)
        _update(k, v, kp, kp < start)

    @pl.when(c >= maxp)
    def _chunk_block():
        k0 = start + (c - maxp) * kvb              # first key position
        kb, ksb, krt = _quant_kv_tile(kd_ref[0].astype(jnp.float32), fmt,
                                      grid, mids, r_max, center, bits,
                                      kvh, dh)
        vb, vsb, vrt = _quant_kv_tile(vd_ref[0].astype(jnp.float32), fmt,
                                      grid, mids, r_max, center, bits,
                                      kvh, dh)
        kc_ref[0] = kb
        ks_ref[0] = ksb
        vc_ref[0] = vb
        vs_ref[0] = vsb

        @pl.when(k0 < q0 + qb)                     # some key is causal
        def _attend():
            kp = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, 1, kvb), 2)
            _update(krt, vrt, kp, True)

    @pl.when(c == n_kv - 1)
    def _finalize():
        o_ref[0] = acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)


def mx_flash_prefill(q: jnp.ndarray, k_chunk: jnp.ndarray,
                     v_chunk: jnp.ndarray, k_codes: jnp.ndarray,
                     k_scales: jnp.ndarray, v_codes: jnp.ndarray,
                     v_scales: jnp.ndarray, block_tables: jnp.ndarray,
                     q_start: jnp.ndarray, kv_len: jnp.ndarray,
                     fmt: str = "mxfp8", *, window: int = 0,
                     qb: int | None = None, kvb: int | None = None,
                     explicit_qb: bool = False, explicit_kvb: bool = False,
                     interpret: bool = True):
    """Flash-prefill attention over a paged packed MX KV pool, fused with
    the quantize-on-append of the current chunk.

    q          (B, C, H, Dh) float  — chunk queries (C tokens per lane)
    k/v chunk  (B, C, D) float      — dense chunk K/V (D = kvh*Dh)
    k/v codes  (N, P, D*bits/8) u8  — page pool shared by all lanes
    k/v scales (N, P, D//32)    u8  — E8M0 bytes
    block_tables (B, maxp) i32      — page id of lane b's page c
    q_start    (B,) i32             — chunk start position per lane (pool
                                      rows ``kp < q_start`` are the
                                      committed prefix; rows the chunk
                                      covers come from the in-tile encode)
    kv_len     (B,) i32             — valid-key bound per lane (typically
                                      q_start + C)

    Returns ``(out (B, C, H, Dh) f32, k_code_bytes (B, C, D*bits/8) u8,
    k_scale_bytes (B, C, D//32) u8, v_code_bytes, v_scale_bytes)`` — the
    byte outputs are exactly ``packing.kv_encode`` of the chunk, for the
    caller to scatter into the pool. ``qb``/``kvb`` tile the chunk's query
    and self-KV axes (``explicit_*=True`` honors them exactly and raises
    on non-divisors — the override that drives the multi-block grid in
    CPU interpret mode)."""
    B, C, H, Dh = q.shape
    bits = packing.kv_fmt_bits(fmt)
    N, P, db = k_codes.shape
    D = db * 8 // bits
    kvh = D // Dh
    maxp = block_tables.shape[1]
    assert H % kvh == 0 and kvh * Dh == D, (q.shape, k_codes.shape)
    assert D % MXBLOCK == 0, (D,)
    ns = D // MXBLOCK
    assert k_scales.shape == (N, P, ns), k_scales.shape
    assert k_chunk.shape == (B, C, D), (k_chunk.shape, (B, C, D))
    assert maxp >= 1, "prefill needs at least one table slot per lane"
    G = H // kvh
    qb = _pick_chunk(C, C if qb is None else qb, explicit=explicit_qb)
    kvb = _pick_chunk(C, C if kvb is None else kvb, explicit=explicit_kvb)
    n_qb = C // qb
    n_cb = C // kvb
    kern = functools.partial(_flash_prefill_kernel, fmt=fmt, bits=bits,
                             window=window, kvh=kvh, dh=Dh, group=G,
                             maxp=maxp, n_cb=n_cb, qb=qb, kvb=kvb, page=P)

    # Index-map clamps: pool specs only matter on steps c < maxp (chunk
    # steps clamp to the last table slot — any valid page id, rows unused);
    # chunk specs only matter on steps c >= maxp (pool steps clamp to
    # chunk block 0, unread). The chunk-byte output blocks are fully
    # written on every chunk step, and the last grid step visiting each
    # block is a chunk step, so revisiting is flush-safe.
    def pool_page(i, j, c, bt, *_):
        return (bt[i, jnp.minimum(c, maxp - 1)], 0, 0)

    def chunk_block(i, j, c, *_):
        return (i, jnp.maximum(c - maxp, 0), 0)

    def q_block(i, j, c, *_):
        return (i, 0, j, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, n_qb, maxp + n_cb),
        in_specs=[
            pl.BlockSpec((1, kvh, qb * G, Dh), q_block),
            pl.BlockSpec((1, P, db), pool_page),
            pl.BlockSpec((1, P, ns), pool_page),
            pl.BlockSpec((1, P, db), pool_page),
            pl.BlockSpec((1, P, ns), pool_page),
            pl.BlockSpec((1, kvb, D), chunk_block),
            pl.BlockSpec((1, kvb, D), chunk_block),
        ],
        out_specs=(
            pl.BlockSpec((1, kvh, qb * G, Dh), q_block),
            pl.BlockSpec((1, kvb, db), chunk_block),
            pl.BlockSpec((1, kvb, ns), chunk_block),
            pl.BlockSpec((1, kvb, db), chunk_block),
            pl.BlockSpec((1, kvb, ns), chunk_block),
        ),
        scratch_shapes=_softmax_scratch(kvh, qb * G, Dh),
    )
    # (B, C, H, Dh) -> head-leading (B, kvh, C*G, Dh)
    qh = q.reshape(B, C, kvh, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, kvh, C * G, Dh)
    out, kc, ks, vc, vs = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=(
            jax.ShapeDtypeStruct((B, kvh, C * G, Dh), jnp.float32),
            jax.ShapeDtypeStruct((B, C, db), jnp.uint8),
            jax.ShapeDtypeStruct((B, C, ns), jnp.uint8),
            jax.ShapeDtypeStruct((B, C, db), jnp.uint8),
            jax.ShapeDtypeStruct((B, C, ns), jnp.uint8),
        ),
        interpret=interpret,
    )(jnp.asarray(block_tables, jnp.int32), _lanes(q_start, B),
      _lanes(kv_len, B), qh, k_codes, k_scales, v_codes, v_scales,
      k_chunk, v_chunk)
    out = out.reshape(B, kvh, C, G, Dh).transpose(0, 2, 1, 3, 4).reshape(
        B, C, H, Dh)
    return out, kc, ks, vc, vs
