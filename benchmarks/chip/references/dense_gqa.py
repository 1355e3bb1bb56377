"""Plain reference of the dense GQA decoder in LATMiX's serving form.

Straight ``jax.numpy``, one layer at a time, over whole sequences: no
kernel, no cache, no paging, no batching. It imports nothing of the
system under test and takes nothing the system made: it rebuilds every
weight from the seed (``families.dense_gqa.make_layer``) and decodes the
packed bytes itself. What it computes, per layer (x is the residual):

    h  = rmsnorm(x) * g1
    q,k,v = Q4(h) @ Wq,k,v + b                Q4: MXFP4, 32-blocks, E8M0
    q,k   = rope(q), rope(k)                  half-split pairs, theta
    k,v   = Q8(k), Q8(v)                      MXFP8 (E4M3) KV cache
    x  = x + Q4(causal_gqa_softmax(q,k,v)) @ Wo + bo
    h  = rmsnorm(x) * g2
    a  = silu(Q4(h) @ Wg + bg) * (Q4(h) @ Wu + bu)
    x  = x + Q4(a · blockdiag(H32)) @ Wd      T3: orthonormal Sylvester H32
    logits = rmsnorm(x) * gf @ head + bhead   (f32 embedding, bf16 head)

At ``act_dtype="float32"`` every product runs under
``jax.default_matmul_precision("highest")``, but for the products of two
MX-grid operands (values exact in bf16), whose one bf16 pass with f32
accumulation is already exact. ``act_dtype="bfloat16"`` is
the control: the same computation with the stream, every activation and
every product's inputs in bf16 (f32 accumulation), the precision step
below the float32 that the configuration states.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from families import dense_gqa as fam

FP4 = np.array(fam.FP4_GRID, np.float32)


def _e4m3_grid() -> np.ndarray:
    """Positive E4M3 values (OCP, max 448): subnormals m/8 * 2^-6, normals
    (1 + m/8) * 2^(e-7) for e = 1..15 (e = 15, m = 7 is NaN)."""
    vals = {m / 8 * 2.0 ** -6 for m in range(8)}
    vals |= {(1 + m / 8) * 2.0 ** (e - 7) for e in range(1, 16)
             for m in range(8)}
    return np.array(sorted(v for v in vals if v <= 448.0), np.float32)


FP8 = _e4m3_grid()
# 2^r_max is the largest power of two on each grid: the block scale is
# 2^(floor(log2 amax) - r_max)
R_MAX = {"fp4": 2, "fp8": 8}
GRIDS = {"fp4": FP4, "fp8": FP8}
Q_BLOCK = 512            # query rows per attention block


def mx_round(x, kind: str):
    """MX fake-quantization along the last axis in 32-blocks: power-of-two
    block scale from the block's largest magnitude, each element to the
    nearest grid value (a value on a midpoint goes up), the top clipped."""
    g = jnp.asarray(GRIDS[kind])
    mids = (g[1:] + g[:-1]) / 2
    *lead, n = x.shape
    xb = x.astype(jnp.float32).reshape(*lead, n // 32, 32)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    _, e = jnp.frexp(jnp.where(amax > 0, amax, 1.0))
    scale = jnp.where(amax > 0, jnp.ldexp(jnp.ones_like(amax),
                                           e - 1 - R_MAX[kind]), 1.0)
    z = jnp.abs(xb) / scale
    q = g[jnp.searchsorted(mids, z, side="right")]
    return (jnp.sign(xb) * q * scale).reshape(*lead, n).astype(x.dtype)


def dequant(codes, scales):
    """(K/2, N) packed nibbles + (K/32, N) E8M0 bytes -> (K, N) f32."""
    c = codes.astype(jnp.int32)
    k2, n = c.shape
    nib = jnp.stack([c & 15, c >> 4], axis=1).reshape(2 * k2, n)
    rel = nib - 7                         # code 7 is zero; 15 is no code
    mag = jnp.abs(rel)
    vals = jnp.zeros(nib.shape, jnp.float32)
    for i, g in enumerate(fam.FP4_GRID):
        vals = jnp.where(mag == i, g, vals)
    vals = jnp.where(nib == 15, jnp.nan, jnp.sign(rel) * vals)
    s = jnp.exp2(scales.astype(jnp.float32) - 127.0)
    return (vals.reshape(2 * k2 // 32, 32, n) * s[:, None, :]).reshape(
        2 * k2, n)


@jax.jit
def _dequant_layer(lw):
    out = dict(lw)
    for name, *_ in fam.MATRICES:
        out[name] = dequant(*lw[name])
    return out


def _hadamard32() -> np.ndarray:
    h = np.array([[1.0]])
    while h.shape[0] < 32:
        h = np.block([[h, h], [h, -h]])
    return (h / np.sqrt(32)).astype(np.float32)


def _rmsnorm(x, g, eps, dt):
    xf = x.astype(jnp.float32)
    y = xf / jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * g.astype(jnp.float32)).astype(dt)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    # inverse frequencies in float32, as the published models compute them
    inv = (1.0 / (np.float32(theta) ** (np.arange(half, dtype=np.float32)
                                        / np.float32(half)))
           ).astype(np.float32)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(
        jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def _mx_dot(xq, w, dt):
    """Product of MX-grid operands. Their values are exact in bf16, so a
    single bf16 pass with f32 accumulation is already the f32 product."""
    return jnp.dot(xq.astype(dt), w.astype(dt),
                   preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.DEFAULT)


def _linear(x, w, b, dt):
    """Q4(x) @ W + b with f32 accumulation; inputs in dt."""
    return (_mx_dot(mx_round(x, "fp4"), w, dt) + b).astype(dt)


def _attention(q, k, v, dm, dt):
    """Causal GQA softmax attention over whole sequences, in query
    blocks. q (T, H, Dh); k, v (T, KVH, Dh)."""
    T = q.shape[0]
    G = dm.H // dm.KVH
    qg = q.reshape(T, dm.KVH, G, dm.Dh)
    outs = []
    for s0 in range(0, T, Q_BLOCK):
        qb = qg[s0:s0 + Q_BLOCK]
        n = qb.shape[0]
        s = jnp.einsum("qkgd,tkd->kgqt", qb.astype(dt), k.astype(dt),
                       preferred_element_type=jnp.float32) / np.sqrt(dm.Dh)
        ok = (jnp.arange(T)[None, :] <= (s0 + jnp.arange(n))[:, None])
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("kgqt,tkd->qkgd", p.astype(dt), v.astype(dt),
                       preferred_element_type=jnp.float32)
        outs.append(o.reshape(n, dm.H * dm.Dh))
    return jnp.concatenate(outs, axis=0).astype(dt)


@functools.partial(jax.jit, static_argnames=("dm", "dt"))
def _layer(x, lw, dm, dt):
    pos = jnp.arange(x.shape[0])
    h = _rmsnorm(x, lw["ln1"], dm.eps, dt)
    w = lw
    q = _linear(h, w["wq"], lw["bq"], dt).reshape(-1, dm.H, dm.Dh)
    k = _linear(h, w["wk"], lw["bk"], dt).reshape(-1, dm.KVH, dm.Dh)
    v = _linear(h, w["wv"], lw["bv"], dt)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    k = mx_round(k.reshape(-1, dm.kd), "fp8").reshape(-1, dm.KVH, dm.Dh)
    v = mx_round(v, "fp8").reshape(-1, dm.KVH, dm.Dh)
    kv = (k.reshape(-1, dm.kd), v.reshape(-1, dm.kd))
    o = _attention(q, k, v, dm, dt)
    x = (x.astype(dt) + _linear(o, w["wo"], lw["bo"], dt)).astype(dt)
    h = _rmsnorm(x, lw["ln2"], dm.eps, dt)
    g = _linear(h, w["wg"], lw["bg"], dt)
    u = _linear(h, w["wu"], lw["bu"], dt)
    a = (jax.nn.silu(g.astype(jnp.float32)).astype(dt) * u).astype(dt)
    hb = a.reshape(-1, dm.f // 32, 32).astype(jnp.float32)
    a = jnp.einsum("tbi,ij->tbj", hb, jnp.asarray(_hadamard32())
                   ).reshape(-1, dm.f).astype(dt)
    y = _mx_dot(mx_round(a, "fp4"), w["wd"], dt)
    return (x + y.astype(dt)).astype(dt), kv


@functools.partial(jax.jit, static_argnames=("dm", "dt"))
def _head(x, gl, rows, dm, dt):
    h = _rmsnorm(x[rows], gl["ln_f"], dm.eps, dt)
    return jnp.dot(h.astype(dt), gl["head"].astype(dt),
                   preferred_element_type=jnp.float32) + gl["bhead"]


def bucket(n: int, step: int = 1024) -> int:
    return -(-n // step) * step


def forward(cfg: dict, seed: int, seqs, rows, act_dtype: str = "float32",
            keep_kv: bool = False):
    """Reference logits of each sequence at its ``rows``, and with
    ``keep_kv`` every layer's MXFP8 K and V values.

    seqs: list of int token arrays; rows: list of int index arrays (the
    positions whose next-token distribution is wanted). Sequences are
    padded to a multiple of 1024 (causal: the pad never reaches a real
    row). Returns (list of (len(rows_i), V) float32 numpy arrays, list
    per sequence of [(K, V) (len, kv_dim) float32 per layer] or None)."""
    dm = fam.dims(cfg)
    dt = jnp.dtype(act_dtype)
    prec = "highest" if dt == jnp.float32 else "default"
    kvs = [[] for _ in seqs] if keep_kv else None
    with jax.default_matmul_precision(prec):
        gl = fam.make_globals(cfg, seed)
        xs = []
        for s in seqs:
            t = np.zeros(bucket(len(s)), np.int32)
            t[:len(s)] = s
            xs.append(gl["embed"][jnp.asarray(t)].astype(dt))
        for layer in range(dm.L):
            lw = _dequant_layer(fam.make_layer(cfg, seed, layer))
            for i, s in enumerate(seqs):
                xs[i], (k, v) = _layer(xs[i], lw, dm, dt)
                if keep_kv:
                    kvs[i].append((np.asarray(k[:len(s)], np.float32),
                                   np.asarray(v[:len(s)], np.float32)))
            del lw
        return ([np.asarray(_head(x, gl, jnp.asarray(r, jnp.int32), dm, dt))
                 for x, r in zip(xs, rows)], kvs)


def logits(cfg: dict, seed: int, seqs, rows, act_dtype: str = "float32"):
    """Reference logits only (see :func:`forward`)."""
    return forward(cfg, seed, seqs, rows, act_dtype)[0]


def decode_kv(codes, scales):
    """MXFP8 codes (one byte per value, the index into the full symmetric
    E4M3 grid) and E8M0 scale bytes per 32 -> values."""
    c = np.asarray(codes, np.int64)
    center = len(FP8) - 1
    rel = c - center
    vals = np.sign(rel) * FP8[np.minimum(np.abs(rel), center)]
    s = np.exp2(np.asarray(scales, np.float64) - 127.0)
    *lead, n = c.shape
    return (vals.reshape(*lead, n // 32, 32) * s[..., None]).reshape(
        *lead, n).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("dm", "dt"))
def _kv0(x, lw, dm, dt):
    pos = jnp.arange(x.shape[0])
    h = _rmsnorm(x, lw["ln1"], dm.eps, dt)
    k = _linear(h, lw["wk"], lw["bk"], dt).reshape(-1, dm.KVH, dm.Dh)
    v = _linear(h, lw["wv"], lw["bv"], dt)
    k = mx_round(_rope(k, pos, dm.theta).reshape(-1, dm.kd), "fp8")
    return k, mx_round(v, "fp8")


def kv_layer0(cfg: dict, seed: int, seqs, act_dtype: str = "float32"):
    """The first layer's MXFP8 K and V values of each sequence:
    [(K, V) (len, kv_dim) float32]. They depend on nothing but the
    sequence's own tokens, so they lie below the rounding chaos that MX
    activation quantization starts in every later layer."""
    dm = fam.dims(cfg)
    dt = jnp.dtype(act_dtype)
    prec = "highest" if dt == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        gl = fam.make_globals(cfg, seed)
        lw = _dequant_layer(fam.make_layer(cfg, seed, 0))
        out = []
        for s in seqs:
            t = np.zeros(bucket(len(s)), np.int32)
            t[:len(s)] = s
            k, v = _kv0(gl["embed"][jnp.asarray(t)].astype(dt), lw, dm, dt)
            out.append((np.asarray(k[:len(s)], np.float32),
                        np.asarray(v[:len(s)], np.float32)))
        return out
