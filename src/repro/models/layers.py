"""Shared neural-net layers: norms, RoPE, GQA attention (online-softmax
chunked), gated MLPs. Pure functions; params are plain dict pytrees."""
from __future__ import annotations

import functools
from typing import Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quantize import QuantMode, qlinear
from repro.kernels import ops
from repro.kernels.packing import PackedKV, PagedKV, kv_encode
from repro.launch import pcontext as pctx

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# KV-cache leaves: dense (B, S, kv_dim) arrays or MX-packed ``PackedKV``
# (codes + E8M0 scale bytes). The write helpers quantize at append time —
# the only lossy point of the quantized-cache path; reads decode in place
# (ref) or in-kernel (fused flash-decode).
# ---------------------------------------------------------------------------

def kv_write_rows(cache, new: jnp.ndarray, rows: jnp.ndarray):
    """Scatter one token per lane: lane b writes row ``rows[b]``.
    cache: (B, S, kv_dim) dense or PackedKV; new: (B, 1, kv_dim) dense."""
    bidx = jnp.arange(new.shape[0], dtype=jnp.int32)
    if isinstance(cache, PackedKV):
        c, s = kv_encode(new, cache.fmt)
        return PackedKV(cache.codes.at[bidx, rows].set(c[:, 0]),
                        cache.scales.at[bidx, rows].set(s[:, 0]),
                        cache.fmt, cache.dtype)
    return cache.at[bidx, rows].set(new[:, 0])


def kv_write_slice(cache, new: jnp.ndarray, start):
    """Contiguous write of ``new`` (B, C, kv_dim) at row ``start`` (traced
    scalar) across all lanes — the scalar-decode / chunked-prefill path."""
    if isinstance(cache, PackedKV):
        c, s = kv_encode(new, cache.fmt)
        return PackedKV(
            jax.lax.dynamic_update_slice(cache.codes, c, (0, start, 0)),
            jax.lax.dynamic_update_slice(cache.scales, s, (0, start, 0)),
            cache.fmt, cache.dtype)
    return jax.lax.dynamic_update_slice(cache, new, (0, start, 0))


# ---------------------------------------------------------------------------
# Paged-cache writes: every position goes through the block-table
# indirection — logical position t of lane b lives at pool page
# ``block_tables[b, t // P]``, row ``t % P`` (see ``packing.PagedKV`` and
# ``docs/paged-kv.md``). The engine guarantees writable pages are private
# to their lane (shared prefix pages are read-only), so the scatters below
# never race across lanes. The two quantizing writers run under the
# ``kv_write`` name scope, which a profile's ops carry in their op_name
# metadata; it covers the encode and the scatter, which XLA may fuse.
# ---------------------------------------------------------------------------

@jax.named_scope("kv_write")
def kv_write_token_paged(pool: PagedKV, new: jnp.ndarray,
                         pages: jnp.ndarray, offs: jnp.ndarray) -> PagedKV:
    """Scatter one token per lane into a layer-sliced page pool.
    pool: PagedKV (N, P, ·); new: (B, 1, D) dense; pages/offs: (B,) i32 —
    lane b writes pool[pages[b], offs[b]]. Quantizes at append time when
    the pool is MX-packed (the decode scatter path, page-relative)."""
    if pool.fmt == "none":
        return PagedKV(pool.codes.at[pages, offs].set(
            new[:, 0].astype(pool.codes.dtype)), None, "none", pool.dtype)
    c, s = kv_encode(new, pool.fmt)
    return PagedKV(pool.codes.at[pages, offs].set(c[:, 0]),
                   pool.scales.at[pages, offs].set(s[:, 0]),
                   pool.fmt, pool.dtype)


@jax.named_scope("kv_write")
def kv_write_chunk_paged(pool: PagedKV, new: jnp.ndarray,
                         block_tables: jnp.ndarray, start) -> PagedKV:
    """Write a C-token chunk at absolute positions start..start+C-1
    through the block tables (the chunked-prefill append path).
    pool: PagedKV (N, P, ·); new: (B, C, D) dense; block_tables:
    (B, maxp) i32; start: traced i32 scalar shared by all lanes, or a
    (B,) vector of per-lane starts (batched prefill admission). Each
    token lands at its page-relative row — chunks may straddle page
    boundaries."""
    B, C = new.shape[0], new.shape[1]
    pages, offs = _chunk_pages_offs(block_tables, B, C, pool.page_size,
                                    start)
    if pool.fmt == "none":
        return PagedKV(pool.codes.at[pages, offs].set(
            new.astype(pool.codes.dtype)), None, "none", pool.dtype)
    c, s = kv_encode(new, pool.fmt)
    return PagedKV(pool.codes.at[pages, offs].set(c),
                   pool.scales.at[pages, offs].set(s),
                   pool.fmt, pool.dtype)


def _chunk_pages_offs(block_tables, B: int, C: int, P: int, start):
    """(pages, offs) (B, C) i32 for a C-token chunk at ``start`` (traced
    scalar, broadcast — or (B,) per-lane vector) through the tables."""
    st = jnp.asarray(start, jnp.int32)
    if st.ndim == 1:                         # per-lane starts
        pos = st[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        return jnp.take_along_axis(block_tables, pos // P, axis=1), pos % P
    pos = st + jnp.arange(C, dtype=jnp.int32)               # (C,)
    pages = jnp.take_along_axis(
        block_tables, jnp.broadcast_to((pos // P)[None, :], (B, C)),
        axis=1)                                             # (B, C)
    return pages, jnp.broadcast_to((pos % P)[None, :], (B, C))


def kv_scatter_chunk_paged(pool: PagedKV, codes: jnp.ndarray,
                           scales: jnp.ndarray, block_tables: jnp.ndarray,
                           start) -> PagedKV:
    """Scatter *pre-encoded* chunk bytes into a packed page pool — the
    commit half of the fused prefill kernel's quantize-on-append:
    ``ops.mx_flash_prefill`` returns the chunk's MX code + E8M0 scale
    bytes (bit-identical to ``packing.kv_encode``), and this placement is
    byte-identical to :func:`kv_write_chunk_paged` of the dense chunk.
    pool: PagedKV (N, P, ·), quantized fmt; codes: (B, C, D*bits/8) u8;
    scales: (B, C, D//32) u8; start: scalar or (B,) per-lane i32."""
    if pool.fmt == "none":
        raise ValueError("kv_scatter_chunk_paged commits packed bytes; a "
                         "dense (fmt='none') pool has none — use "
                         "kv_write_chunk_paged")
    B, C = codes.shape[0], codes.shape[1]
    pages, offs = _chunk_pages_offs(block_tables, B, C, pool.page_size,
                                    start)
    return PagedKV(pool.codes.at[pages, offs].set(codes),
                   pool.scales.at[pages, offs].set(scales),
                   pool.fmt, pool.dtype)


def kv_write_spec(cache, new: jnp.ndarray, rows: jnp.ndarray):
    """Per-lane multi-token scatter for the speculative verify step:
    lane b token j writes row ``rows[b, j]``; rows >= S drop (the masked
    write of slots past a lane's draft count — ``mode='drop'`` because a
    clamped index would corrupt a live row instead).
    cache: (B, S, kv_dim) dense or PackedKV; new: (B, C, kv_dim) dense."""
    bidx = jnp.arange(new.shape[0], dtype=jnp.int32)[:, None]
    if isinstance(cache, PackedKV):
        c, s = kv_encode(new, cache.fmt)
        return PackedKV(cache.codes.at[bidx, rows].set(c, mode="drop"),
                        cache.scales.at[bidx, rows].set(s, mode="drop"),
                        cache.fmt, cache.dtype)
    return cache.at[bidx, rows].set(new, mode="drop")


def kv_write_spec_paged(pool: PagedKV, new: jnp.ndarray,
                        block_tables: jnp.ndarray, pos: jnp.ndarray,
                        n_valid: jnp.ndarray) -> PagedKV:
    """Per-lane multi-token write through block tables: lane b token j
    lands at logical position ``pos[b] + j`` when ``j < n_valid[b]``.
    Invalid slots are dropped by forcing their page offset to P (out of
    the page, ``mode='drop'``); their page *gather* index is clipped
    instead, because gathers clamp rather than drop and an unclipped
    ``t // P`` could read past a short lane's table row.
    pool: PagedKV (N, P, ·); new: (B, C, D); pos/n_valid: (B,) i32."""
    B, C = new.shape[0], new.shape[1]
    P = pool.page_size
    t = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]   # (B, C)
    valid = jnp.arange(C, dtype=jnp.int32)[None, :] < n_valid[:, None]
    maxp = block_tables.shape[1]
    pages = jnp.take_along_axis(
        block_tables, jnp.clip(t // P, 0, maxp - 1), axis=1)     # (B, C)
    offs = jnp.where(valid, t % P, P)
    if pool.fmt == "none":
        return PagedKV(pool.codes.at[pages, offs].set(
            new.astype(pool.codes.dtype), mode="drop"), None, "none",
            pool.dtype)
    c, s = kv_encode(new, pool.fmt)
    return PagedKV(pool.codes.at[pages, offs].set(c, mode="drop"),
                   pool.scales.at[pages, offs].set(s, mode="drop"),
                   pool.fmt, pool.dtype)


def attention_paged(q: jnp.ndarray, k_pool: PagedKV, v_pool: PagedKV,
                    block_tables: jnp.ndarray, *, causal: bool,
                    q_pos: jnp.ndarray, window: int = 0,
                    kv_len: Optional[jnp.ndarray] = None,
                    chunk: int = 1024, backend: str = "ref") -> jnp.ndarray:
    """Attention over a paged KV pool addressed through block tables.

    Under ``backend='fused'`` the single-token decode contract (Sq == 1,
    a quantized pool, a known per-lane fill) dispatches to the paged
    Pallas flash-decode kernel, which resolves the block-table
    indirection in its grid — pages stream from HBM without a contiguous
    copy. Everything else (chunked prefill with Sq > 1, dense pools, the
    'ref' backend) gathers each lane's pages into the logical contiguous
    layout and runs the existing :func:`attention` on the same values,
    so the paged path is value-identical position-for-position to the
    contiguous cache."""
    B, Sq, H, Dh = q.shape
    if (backend == "fused" and Sq == 1 and causal
            and k_pool.fmt != "none" and kv_len is not None):
        qp = jnp.asarray(q_pos, jnp.int32)
        qpv = qp[:, 0] if qp.ndim == 2 else qp.reshape(-1)
        out = ops.mx_flash_decode_paged(
            q.reshape(B, H, Dh), k_pool.codes, k_pool.scales,
            v_pool.codes, v_pool.scales, block_tables, qpv,
            jnp.asarray(kv_len, jnp.int32).reshape(-1), k_pool.fmt,
            window=window)
        return out.reshape(B, Sq, H, Dh).astype(q.dtype)
    kvh = k_pool.feature_dim // Dh
    # gather in the pool's storage dtype — identical read semantics to the
    # contiguous cache (attention casts q to the cache dtype, not vice
    # versa), which is what keeps paged/contiguous bitwise-equal
    kd = kv_heads_view(k_pool.gather_dense(block_tables), kvh, Dh)
    vd = kv_heads_view(v_pool.gather_dense(block_tables), kvh, Dh)
    return attention(q, kd, vd, causal=causal, q_pos=q_pos, window=window,
                     kv_len=kv_len, chunk=chunk)


def shard_kv(c, *names):
    """pctx.shard over a cache leaf; a PackedKV shards its children (the
    divisibility guard drops axes the packed widths cannot honor)."""
    if isinstance(c, PackedKV):
        return PackedKV(pctx.shard(c.codes, *names),
                        pctx.shard(c.scales, *names), c.fmt, c.dtype)
    return pctx.shard(c, *names)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def rms_norm(x: jnp.ndarray, gamma: jnp.ndarray, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


def rms_norm_gated(x, z, gamma, eps: float = 1e-5):
    """Mamba2 gated norm: rmsnorm(x * silu(z)) * gamma."""
    return rms_norm(x * jax.nn.silu(z.astype(jnp.float32)).astype(x.dtype),
                    gamma, eps)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _rope_inv_freq(theta: float, half: int) -> np.ndarray:
    """Cached RoPE inverse-frequency table keyed on (theta, head_dim/2) —
    a host constant, so every trace folds the same array instead of
    re-deriving the power series per call (the ``hadamard_matrix``
    treatment)."""
    return (1.0 / (theta ** (np.arange(0, half, dtype=np.float32) / half))
            ).astype(np.float32)


def apply_rope(x: jnp.ndarray, pos: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (B, S, N, Dh); pos: (S,) int32 positions shared across the batch,
    or (B, S) per-row positions (continuous-batching decode, where each
    batch lane sits at its own sequence position). Rotates pairs
    (x[..., :half], x[..., half:]) — llama convention. Per-row positions
    compute the identical rotation a shared-position call with that row's
    position would."""
    dh = x.shape[-1]
    half = dh // 2
    inv_freq = jnp.asarray(_rope_inv_freq(float(theta), half))
    if pos.ndim == 2:  # (B, S) per-row positions
        freqs = pos.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]
        cos = jnp.cos(freqs)[:, :, None, :]            # (B, S, 1, half)
        sin = jnp.sin(freqs)[:, :, None, :]
    else:
        freqs = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
        cos = jnp.cos(freqs)[None, :, None, :]         # (1, S, 1, half)
        sin = jnp.sin(freqs)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention — grouped-query, online-softmax over KV chunks.
# ---------------------------------------------------------------------------

def kv_heads_view(c, kvh: int, dh: int):
    """(B, S, kv_dim) cache leaf -> the (B, S, K, Dh) view ``attention``
    expects. A ``PackedKV`` passes through unsplit — attention derives
    the head view from q and dispatches on the packed layout."""
    if isinstance(c, PackedKV):
        return c
    return c.reshape(c.shape[0], c.shape[1], kvh, dh)


def _attention_packed(q, k: PackedKV, v: PackedKV, *, causal, q_pos,
                      k_start, window, kv_len, k_positions, chunk,
                      backend):
    """Attention over an MX-quantized KV cache (see ``docs/kv-cache.md``).

    Under ``backend='fused'`` the single-token decode contract (Sq == 1,
    contiguous keys, a known fill) dispatches to the Pallas flash-decode
    kernel, which consumes the packed codes + E8M0 scale bytes straight
    from HBM. Everything else — chunked prefill (Sq > 1), ring-buffer
    caches (k_positions), the 'ref' backend — decodes the cache in place
    (one LUT gather, the PackedWeight fallback posture) and runs the
    dense jnp path on the same values."""
    B, Sq, H, Dh = q.shape
    qp = jnp.asarray(q_pos, jnp.int32)
    if (backend == "fused" and Sq == 1 and causal and k_positions is None
            and k_start == 0 and kv_len is not None):
        qpv = qp[:, 0] if qp.ndim == 2 else qp.reshape(-1)
        out = ops.mx_flash_decode(
            q.reshape(B, H, Dh), k.codes, k.scales, v.codes, v.scales,
            qpv, jnp.asarray(kv_len, jnp.int32).reshape(-1), k.fmt,
            window=window)
        return out.reshape(B, Sq, H, Dh).astype(q.dtype)
    kvh = k.shape[-1] // Dh
    kd = kv_heads_view(k.to_dense(), kvh, Dh)
    vd = kv_heads_view(v.to_dense(), kvh, Dh)
    return attention(q, kd, vd, causal=causal, q_pos=q_pos,
                     k_start=k_start, window=window, kv_len=kv_len,
                     k_positions=k_positions, chunk=chunk)


def attention(q: jnp.ndarray, k, v, *,
              causal: bool, q_pos: jnp.ndarray, k_start: int = 0,
              window: int = 0, kv_len: Optional[jnp.ndarray] = None,
              k_positions: Optional[jnp.ndarray] = None,
              chunk: int = 1024, backend: str = "ref") -> jnp.ndarray:
    """Memory-bounded attention.

    q: (B, Sq, H, Dh);  k, v: (B, Sk, K, Dh) with H % K == 0 — or
    ``PackedKV`` leaves of logical shape (B, Sk, K*Dh) (MX-quantized
    cache; see :func:`_attention_packed` for the dispatch rules —
    ``backend='fused'`` engages the Pallas flash-decode kernel on the
    single-token decode contract).
    q_pos: (Sq,) absolute positions of the queries, shared across the
            batch — or (B, Sq) per-row positions (continuous-batching
            decode, every lane at its own position).
    k_start: absolute position of k[:, 0] (keys are contiguous).
    window: if > 0, keys with pos <= q_pos - window are masked (local attn).
    kv_len: optional traced scalar — keys at index >= kv_len are invalid
            (decode with a partially-filled cache). May be a (B,) vector
            when q_pos is per-row (each lane has its own cache fill).
    k_positions: optional (Sk,) explicit key positions (ring-buffer caches);
            overrides k_start, and entries < 0 are invalid.
    Output: (B, Sq, H, Dh).

    Every mask variant selects the same key set a shared-position call
    would select per row, so per-row calls are value-identical per lane to
    the scalar path (the engine's scheduler-parity tests pin this down).
    """
    if isinstance(k, PackedKV):
        return _attention_packed(q, k, v, causal=causal, q_pos=q_pos,
                                 k_start=k_start, window=window,
                                 kv_len=kv_len, k_positions=k_positions,
                                 chunk=chunk, backend=backend)
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, Sq, K, G, Dh).astype(k.dtype)
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dh, jnp.float32))

    if Sk % chunk != 0 or Sk <= chunk:
        chunk = Sk
    nc = Sk // chunk

    qp = q_pos.astype(jnp.int32)  # (Sq,) shared or (B, Sq) per-row
    per_row = qp.ndim == 2

    def mask_for(kp):
        if per_row:                      # (B, Sq, chunk) boolean
            kpb = kp[None, None, :]
            ok = kpb >= 0
            if causal:
                ok = ok & (kpb <= qp[:, :, None])
            if window:
                ok = ok & (kpb > qp[:, :, None] - window)
            if kv_len is not None:
                kl = jnp.reshape(jnp.asarray(kv_len, jnp.int32), (-1, 1, 1))
                ok = ok & (kpb - k_start < kl)
            return ok
        ok = kp[None, :] >= 0
        if causal:
            ok &= kp[None, :] <= qp[:, None]
        if window:
            ok &= kp[None, :] > qp[:, None] - window
        if kv_len is not None:
            ok &= kp[None, :] - k_start < kv_len
        return ok

    # fori_loop + dynamic_slice (not scan over a moveaxis'd copy): the
    # cache is read in place, once, in its storage dtype — no hoisted f32
    # conversion and no reordered copy of the whole KV cache (§Perf).
    def body(i, carry):
        m, l, acc = carry
        kci = jax.lax.dynamic_slice_in_dim(k, i * chunk, chunk, 1)
        vci = jax.lax.dynamic_slice_in_dim(v, i * chunk, chunk, 1)
        if jax.default_backend() == "cpu":
            # block XLA-CPU from hoisting its f32-emulation converts of
            # bf16 dots above the loop (whole-cache phantom copies)
            kci, vci = jax.lax.optimization_barrier((kci, vci))
        if k_positions is not None:
            kp = jax.lax.dynamic_slice_in_dim(
                k_positions.astype(jnp.int32), i * chunk, chunk, 0)
        else:
            kp = (k_start + i * chunk
                  + jnp.arange(chunk, dtype=jnp.int32))
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg, kci,
                       preferred_element_type=jnp.float32) * scale
        okm = mask_for(kp)
        ok = (okm[:, :, None, None, :] if per_row
              else okm[None, :, None, None, :])
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p.astype(k.dtype), vci,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc)

    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, Dh), jnp.float32)
    if nc == 1:
        m, l, acc = body(0, (m0, l0, a0))
    else:
        m, l, acc = jax.lax.fori_loop(0, nc, body, (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Flash-style differentiable attention (custom VJP).
#
# A plain lax.scan over KV chunks is memory-efficient *forward*, but its
# backward stacks the per-chunk score residuals — the full S×S attention
# matrix in f32. The custom VJP below recomputes scores per chunk from the
# saved (q, k, v, out, lse), which keeps the training-time footprint at
# O(S·d) like FlashAttention.
# ---------------------------------------------------------------------------

def _fa_masks(q_pos, k_pos, causal, window):
    # no in-place ops: operands may be host numpy constants
    ok = k_pos[None, :] >= 0
    if causal:
        ok = ok & (k_pos[None, :] <= q_pos[:, None])
    if window:
        ok = ok & (k_pos[None, :] > q_pos[:, None] - window)
    return ok


def _fa_forward(qg, kc, vc, kpos_c, q_pos, causal, window, scale):
    """qg: (B,Sq,K,G,D); kc/vc: (nc,B,chunk,K,D). Returns (out, lse)."""
    B, Sq, K, G, Dh = qg.shape
    m0 = jnp.full((B, Sq, K, G), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, K, G), jnp.float32)
    a0 = jnp.zeros((B, Sq, K, G, Dh), jnp.float32)

    def body(carry, inp):
        m, l, acc = carry
        kci, vci, kp = inp
        s = jnp.einsum("bqkgd,bckd->bqkgc", qg.astype(jnp.float32),
                       kci.astype(jnp.float32)) * scale
        ok = _fa_masks(q_pos, kp, causal, window)[None, :, None, None, :]
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(ok, jnp.exp(s - m_new[..., None]), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bqkgc,bckd->bqkgd", p, vci.astype(jnp.float32))
        return (m_new, l, acc), None

    if kc.shape[0] == 1:
        (m, l, acc), _ = body((m0, l0, a0), (kc[0], vc[0], kpos_c[0]))
    else:
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (kc, vc, kpos_c))
    lse = m + jnp.log(jnp.maximum(l, 1e-30))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out, lse


def flash_attention(q, k, v, *, causal, window, chunk, q_pos=None):
    """Differentiable memory-efficient attention. Keys are contiguous from
    position 0; positions are host-side numpy constants (a custom_vjp may
    not close over tracers), so this path is for full-sequence train /
    prefill — decode uses :func:`attention`."""
    del q_pos  # positions are always 0..Sq-1 here
    B, Sq, H, Dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = H // K
    if Sk % chunk != 0 or Sk <= chunk:
        chunk = Sk
    nc = Sk // chunk
    scale = float(1.0 / np.sqrt(Dh))
    q_pos = np.arange(Sq, dtype=np.int32)
    kpos_c = (np.arange(nc, dtype=np.int32)[:, None] * chunk
              + np.arange(chunk, dtype=np.int32)[None, :])

    @jax.custom_vjp
    def fa(qg, kk, vv):
        kc = jnp.moveaxis(kk.reshape(B, nc, chunk, K, Dh), 1, 0)
        vc = jnp.moveaxis(vv.reshape(B, nc, chunk, K, Dh), 1, 0)
        out, _ = _fa_forward(qg, kc, vc, kpos_c, q_pos, causal, window,
                             scale)
        return out

    def fa_fwd(qg, kk, vv):
        kc = jnp.moveaxis(kk.reshape(B, nc, chunk, K, Dh), 1, 0)
        vc = jnp.moveaxis(vv.reshape(B, nc, chunk, K, Dh), 1, 0)
        out, lse = _fa_forward(qg, kc, vc, kpos_c, q_pos, causal, window,
                               scale)
        return out, (qg, kk, vv, out, lse)

    def fa_bwd(res, dout):
        qg, kk, vv, out, lse = res
        qf = qg.astype(jnp.float32)
        do = dout.astype(jnp.float32)
        delta = jnp.sum(do * out, axis=-1)            # (B,Sq,K,G)
        kc = jnp.moveaxis(kk.reshape(B, nc, chunk, K, Dh), 1, 0)
        vc = jnp.moveaxis(vv.reshape(B, nc, chunk, K, Dh), 1, 0)

        def body(dq, inp):
            kci, vci, kp = inp
            s = jnp.einsum("bqkgd,bckd->bqkgc", qf,
                           kci.astype(jnp.float32)) * scale
            ok = _fa_masks(q_pos, kp, causal, window)[None, :, None,
                                                      None, :]
            p = jnp.where(ok, jnp.exp(s - lse[..., None]), 0.0)
            dv_c = jnp.einsum("bqkgc,bqkgd->bckd", p, do)
            dp = jnp.einsum("bqkgd,bckd->bqkgc", do,
                            vci.astype(jnp.float32))
            ds = p * (dp - delta[..., None]) * scale
            dq = dq + jnp.einsum("bqkgc,bckd->bqkgd", ds,
                                 kci.astype(jnp.float32))
            dk_c = jnp.einsum("bqkgc,bqkgd->bckd", ds, qf)
            return dq, (dk_c, dv_c)

        dq0 = jnp.zeros(qg.shape, jnp.float32)
        if nc == 1:
            dq, (dk_c, dv_c) = body(dq0, (kc[0], vc[0], kpos_c[0]))
            dk, dv = dk_c, dv_c
        else:
            dq, (dks, dvs) = jax.lax.scan(body, dq0, (kc, vc, kpos_c))
            dk = jnp.moveaxis(dks, 0, 1).reshape(B, Sk, K, Dh)
            dv = jnp.moveaxis(dvs, 0, 1).reshape(B, Sk, K, Dh)
        return (dq.astype(qg.dtype), dk.astype(kk.dtype),
                dv.astype(vv.dtype))

    fa.defvjp(fa_fwd, fa_bwd)
    out = fa(q.reshape(B, Sq, K, G, Dh), k, v)
    return out.reshape(B, Sq, H, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Gated MLPs
# ---------------------------------------------------------------------------

def gated_mlp(x: jnp.ndarray, wg, wu, wd, qm: QuantMode,
              act: str = "silu", bg=None, bu=None, bd=None) -> jnp.ndarray:
    """SwiGLU / GeGLU: down( act(x@wg) * (x@wu) ). Optional biases appear
    after transformation folding (Eq. 30).

    Weights may be PackedWeight leaves: under ``qm.backend='fused'`` all
    three projections run packed-native, and the down projection's online
    T3 block-Hadamard is folded into the GEMM kernel's activation-quantize
    prologue instead of a separate rotate pass over the d_ff stream."""
    g = qlinear(x, wg, bg, qm, "ffn_in")
    u = qlinear(x, wu, bu, qm, "ffn_in")
    fn = jax.nn.silu if act == "silu" else jax.nn.gelu
    h = fn(g.astype(jnp.float32)).astype(x.dtype) * u
    return qlinear(h, wd, bd, qm, "ffn_down")


# ---------------------------------------------------------------------------
# Depthwise causal conv (Mamba/Griffin temporal conv)
# ---------------------------------------------------------------------------

def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray,
                  b: Optional[jnp.ndarray]) -> jnp.ndarray:
    """x: (B, L, C); w: (C, K) depthwise; left-pad K-1 (causal)."""
    K = w.shape[-1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    # gather K shifted views and contract — avoids conv lowering pitfalls
    views = jnp.stack([xp[:, i:i + x.shape[1], :] for i in range(K)], axis=-1)
    y = jnp.einsum("blck,ck->blc", views, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(x.dtype)
    return y


def conv1d_step(conv_state: jnp.ndarray, x_t: jnp.ndarray, w: jnp.ndarray,
                b: Optional[jnp.ndarray]):
    """Single decode step. conv_state: (B, C, K-1) previous inputs,
    x_t: (B, C). Returns (y_t (B, C), new_state)."""
    K = w.shape[-1]
    full = jnp.concatenate([conv_state, x_t[:, :, None]], axis=-1)  # (B,C,K)
    y = jnp.einsum("bck,ck->bc", full, w.astype(x_t.dtype))
    if b is not None:
        y = y + b.astype(x_t.dtype)
    return y, full[:, :, 1:]


# ---------------------------------------------------------------------------
# Parameter init helpers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 1.0):
    std = scale / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * std).astype(dtype)


def shard_batch(x, *rest):
    """Annotate (B, ...) activation with batch sharding."""
    return pctx.shard(x, "batch", *rest)


@jax.custom_jvp
def _grad_transparent_barrier(xs):
    return jax.lax.optimization_barrier(xs)


@_grad_transparent_barrier.defjvp
def _barrier_jvp(primals, tangents):
    # identity semantics: the barrier only pins XLA scheduling, so the
    # tangent passes straight through (optimization_barrier itself has no
    # differentiation rule, which would break transform learning on CPU)
    return _grad_transparent_barrier(primals[0]), tangents[0]


def scan_layers(body, carry, xs, use_scan: bool = True):
    """lax.scan or an unrolled python loop (identical semantics).

    The unrolled form exists for roofline analysis: XLA's cost_analysis
    counts a while-loop body once, so per-layer FLOPs/bytes/collectives are
    measured from unrolled 1- and 2-layer lowerings and extrapolated.

    On the CPU backend the per-layer slices are wrapped in an
    optimization_barrier: XLA-CPU emulates bf16 dots by converting operands
    to f32 and hoists the converts above the while loop, materializing
    f32 copies of *all* layers' weights/caches — phantom buffers that do
    not exist on TPU (native bf16 MXU). The barrier keeps the dry-run
    memory_analysis faithful to the TPU target."""
    if use_scan:
        if jax.default_backend() == "cpu":
            def body_b(c, x):
                return body(c, _grad_transparent_barrier(x))
            return jax.lax.scan(body_b, carry, xs)
        return jax.lax.scan(body, carry, xs)
    L = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(L):
        xi = jax.tree.map(lambda a: a[i], xs)
        carry, y = body(carry, xi)
        ys.append(y)
    if ys and ys[0] is not None:
        stacked = jax.tree.map(lambda *e: jnp.stack(e), *ys)
    else:
        stacked = None
    return carry, stacked
