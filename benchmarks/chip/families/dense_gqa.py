"""Dense GQA decoder (pre-RMSNorm, RoPE, SwiGLU) in LATMiX's serving form.

Seeded weights, made on the device, and the hand-over of those weights to
the system under test. Every quantized matrix is an MXFP4 weight in its
deployable layout: two 4-bit codes per byte along the contraction axis
(code ``2i`` in the low nibble of byte ``i``) and one E8M0 scale byte per
32-block. The LM head and the norms are bf16; the affine biases that
LATMiX folds into the linears (and the LM head's) are f32. The embedding
is f32: the system's layer scan carries the residual stream in the
embedding's dtype, and its linears return f32.

Weights are made one layer at a time from ``fold_in(seed, matrix, layer)``,
so the whole stack (one jitted ``lax.map`` for the system) and a single
layer (for the reference, which never holds the stack) carry the same
bytes. Nothing here imports the system except :func:`to_system`.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

FP4_GRID = (0.0, 0.5, 1.0, 1.5, 2.0, 3.0, 4.0, 6.0)
# the 15 codes of the full symmetric E2M1 grid: code c means
# sign(c - 7) * FP4_GRID[|c - 7|]; generated nibbles never hold 15
CODE_VALUES = np.array([-FP4_GRID[7 - c] if c < 7 else FP4_GRID[c - 7]
                        for c in range(15)], np.float64)

# (name, contraction dim, output dim, residual-branch output?)
MATRICES = (("wq", "d", "qd", False), ("wk", "d", "kd", False),
            ("wv", "d", "kd", False), ("wo", "qd", "d", True),
            ("wg", "d", "f", False), ("wu", "d", "f", False),
            ("wd", "f", "d", True))
BIASES = (("bq", "qd"), ("bk", "kd"), ("bv", "kd"), ("bo", "d"),
          ("bg", "f"), ("bu", "f"))


@dataclasses.dataclass(frozen=True)
class Dims:
    L: int
    d: int
    H: int
    KVH: int
    Dh: int
    f: int
    V: int
    eps: float
    theta: float

    @property
    def qd(self) -> int:
        return self.H * self.Dh

    @property
    def kd(self) -> int:
        return self.KVH * self.Dh

    def size(self, name: str) -> int:
        return getattr(self, name)


def dims(cfg: dict) -> Dims:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Dims(L=cfg["num_hidden_layers"], d=d, H=h,
                KVH=cfg["num_key_value_heads"],
                Dh=cfg.get("head_dim") or d // h,
                f=cfg["intermediate_size"], V=cfg["vocab_size"],
                eps=float(cfg["rms_norm_eps"]),
                theta=float(cfg["rope_theta"]))


def _code_rms() -> float:
    """RMS of a generated code's value: nibbles uniform over 0..15, with
    15 (no code) mapped to the zero code 7."""
    vals = np.concatenate([CODE_VALUES, [0.0]])
    return float(np.sqrt(np.mean(vals ** 2)))


def scale_exponent(k: int, gain: float) -> int:
    """E8M0 exponent that gives a K-row matrix an entry RMS near
    gain / sqrt(K), so a unit-RMS input maps to an output of RMS ~gain."""
    return int(round(math.log2(gain / (_code_rms() * math.sqrt(k)))))


def _nibbles(key, shape):
    b = jax.random.bits(key, shape, jnp.uint8)
    lo = b & jnp.uint8(15)
    hi = b >> jnp.uint8(4)
    lo = jnp.where(lo == 15, jnp.uint8(7), lo)
    hi = jnp.where(hi == 15, jnp.uint8(7), hi)
    return lo | (hi << jnp.uint8(4))


def gen_layer(cfg: dict, key, layer):
    """One layer's arrays: {matrix: (codes, scales)}, norms, biases."""
    dm, init = dims(cfg), cfg["init"]
    out = {}
    for mi, (name, kn, nn, branch) in enumerate(MATRICES):
        k, n = dm.size(kn), dm.size(nn)
        kk = jax.random.fold_in(jax.random.fold_in(key, mi), layer)
        kc, ks = jax.random.split(kk)
        e0 = scale_exponent(k, init["branch_gain"] if branch else 1.0)
        jit = init["scale_jitter"]
        sb = (127 + e0 + jax.random.randint(ks, (k // 32, n), -jit,
                                            jit + 1)).astype(jnp.uint8)
        out[name] = (_nibbles(kc, (k // 2, n)), sb)
    kb = jax.random.fold_in(jax.random.fold_in(key, 100), layer)
    for bi, (name, nn) in enumerate(BIASES):
        out[name] = init["bias_rms"] * jax.random.normal(
            jax.random.fold_in(kb, bi), (dm.size(nn),), jnp.float32)
    kn_ = jax.random.fold_in(jax.random.fold_in(key, 200), layer)
    for ni, name in enumerate(("ln1", "ln2")):
        out[name] = (1.0 + init["norm_jitter"] * jax.random.normal(
            jax.random.fold_in(kn_, ni), (dm.d,), jnp.float32)
                     ).astype(jnp.bfloat16)
    return out


def gen_embed(cfg: dict, key):
    dm, init = dims(cfg), cfg["init"]
    return init["embed_rms"] * jax.random.normal(
        jax.random.fold_in(key, 300), (dm.V, dm.d), jnp.float32)


def gen_head(cfg: dict, key):
    """LM head (d, V) bf16, its f32 bias (V,), and the final norm (d,)."""
    dm, init = dims(cfg), cfg["init"]
    k = jax.random.fold_in(key, 400)
    head = (init["head_gain"] / math.sqrt(dm.d) * jax.random.normal(
        jax.random.fold_in(k, 0), (dm.d, dm.V), jnp.float32)
            ).astype(jnp.bfloat16)
    bhead = init["bias_rms"] * jax.random.normal(
        jax.random.fold_in(k, 1), (dm.V,), jnp.float32)
    ln_f = (1.0 + init["norm_jitter"] * jax.random.normal(
        jax.random.fold_in(k, 2), (dm.d,), jnp.float32)).astype(jnp.bfloat16)
    return head, bhead, ln_f


@functools.lru_cache(maxsize=None)
def _stack_fn(cfg_key):
    cfg = _CFGS[cfg_key]

    def make(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
        layers = jax.lax.map(lambda l: gen_layer(cfg, key, l),
                             jnp.arange(dims(cfg).L))
        head, bhead, ln_f = gen_head(cfg, key)
        return {"layers": layers, "embed": gen_embed(cfg, key),
                "head": head, "bhead": bhead, "ln_f": ln_f}
    return jax.jit(make)


_CFGS: dict = {}


def _cfg_key(cfg: dict) -> str:
    import json
    k = json.dumps(cfg, sort_keys=True)
    _CFGS[k] = cfg
    return k


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight of the model, made on the device in one jitted call."""
    seed = int(seed)
    return _stack_fn(_cfg_key(cfg))(np.uint32(seed & 0xFFFFFFFF),
                                    np.uint32((seed >> 32) & 0xFFFFFFFF))


@functools.lru_cache(maxsize=None)
def _layer_fn(cfg_key):
    cfg = _CFGS[cfg_key]

    def make(seed_lo, seed_hi, layer):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
        return gen_layer(cfg, key, layer)
    return jax.jit(make)


@functools.lru_cache(maxsize=None)
def _globals_fn(cfg_key):
    cfg = _CFGS[cfg_key]

    def make(seed_lo, seed_hi):
        key = jax.random.fold_in(jax.random.PRNGKey(seed_lo), seed_hi)
        head, bhead, ln_f = gen_head(cfg, key)
        return {"embed": gen_embed(cfg, key), "head": head, "bhead": bhead,
                "ln_f": ln_f}
    return jax.jit(make)


def make_layer(cfg: dict, seed: int, layer: int) -> dict:
    """Layer ``layer`` alone: the same bytes as ``make_weights``' slice."""
    seed = int(seed)
    return _layer_fn(_cfg_key(cfg))(np.uint32(seed & 0xFFFFFFFF),
                                    np.uint32((seed >> 32) & 0xFFFFFFFF),
                                    np.int32(layer))


def make_globals(cfg: dict, seed: int) -> dict:
    seed = int(seed)
    return _globals_fn(_cfg_key(cfg))(np.uint32(seed & 0xFFFFFFFF),
                                      np.uint32((seed >> 32) & 0xFFFFFFFF))


def packed_weight_bytes(cfg: dict) -> int:
    """HBM bytes of the packed matrices: K*N/2 codes + K*N/32 scales."""
    dm = dims(cfg)
    n = sum(dm.size(kn) * dm.size(nn) for _, kn, nn, _ in MATRICES)
    return dm.L * (n // 2 + n // 32)


def float_weight_bytes(cfg: dict) -> int:
    """f32 embedding and bf16 LM head."""
    dm = dims(cfg)
    return (4 + 2) * dm.V * dm.d


def kv_bytes_per_token(cfg: dict) -> int:
    """MXFP8 K and V: one code byte per feature + one scale per 32."""
    dm = dims(cfg)
    return dm.L * 2 * (dm.kd + dm.kd // 32)


# ---------------------------------------------------------------------------
# Hand-over to the system under test
# ---------------------------------------------------------------------------

def to_system(cfg: dict, w: dict):
    """(params, ArchConfig, QuantMode) of the repo's serving path:
    PackedWeight leaves, the fused backend, LATMiX's MXFP4 QuantMode."""
    from repro.configs.base import ArchConfig
    from repro.core import mx
    from repro.core.quantize import QuantMode
    from repro.kernels.packing import PackedWeight

    dm, sv = dims(cfg), cfg["serving"]
    arch = ArchConfig(
        name=cfg.get("name", cfg["model_type"]), family="dense",
        n_layers=dm.L, d_model=dm.d, n_heads=dm.H, n_kv_heads=dm.KVH,
        head_dim=dm.Dh, d_ff=dm.f, vocab_size=dm.V, rope_theta=dm.theta,
        norm_eps=dm.eps, attn_chunk=sv["attn_chunk"],
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype="bfloat16")
    qm = QuantMode(enabled=True,
                   act_cfg=mx.MXConfig(fmt="mxfp4", block_size=32),
                   weight_cfg=None, t3_block=sv["t3_block"],
                   backend="fused")
    lay = w["layers"]
    blocks = {name: PackedWeight(lay[name][0], lay[name][1], "mxfp4",
                                 "float32")
              for name, *_ in MATRICES}
    blocks.update({name: lay[name] for name, _ in BIASES})
    blocks.update(ln1=lay["ln1"], ln2=lay["ln2"])
    params = {"embed": w["embed"], "head": w["head"], "bhead": w["bhead"],
              "ln_f": w["ln_f"], "blocks": blocks}
    return params, arch, qm


def snapshot_kv(eng, reqs) -> list:
    """What the timed path wrote to the first layer of the KV pool for
    each request in flight: (cache fill, K codes, K scales, V codes,
    V scales), the codes (pages, P, kv_dim) and scales (pages, P,
    kv_dim / 32) of the request's pages, copied to the host."""
    out = []
    for req in reqs:
        slot = next(i for i, sl in enumerate(eng._slots)
                    if sl is not None and sl.req is req)
        pages = jnp.asarray(eng._slot_pages[slot])
        out.append((eng._slots[slot].pos,) + tuple(
            np.asarray(getattr(eng._cache[n], f)[0, pages])
            for n in ("k", "v") for f in ("codes", "scales")))
    return out
