"""Block-wise GPTQ (Frantar et al. 2023) adapted to the MX format
(MR-GPTQ-style): error-compensated weight quantization with per-MX-block
scales recomputed from the *current* (compensated) weights at each block
boundary along the input dimension.

Stage 2 of the PTQ pipeline — applied to the transform-folded weights.
Hessians H = Σ x xᵀ are accumulated from calibration activations captured
at every linear's input (post-transform, post-T3 for the down projection).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core import mx as mxlib
from repro.core import transforms as tfm
from repro.core.quantize import QuantMode
from repro.models.layers import rms_norm
from repro.models import transformer as dense


# ---------------------------------------------------------------------------
# Core GPTQ on one matrix
# ---------------------------------------------------------------------------

def gptq_matrix(w: np.ndarray, hess: np.ndarray, cfg: mxlib.MXConfig,
                damp: float = 0.01) -> np.ndarray:
    """Quantize ``w`` (d_in, d_out) along d_in with MX blocks, compensating
    error through the Hessian (d_in, d_in) of the layer inputs."""
    w = np.array(w, dtype=np.float64)
    d_in, d_out = w.shape
    B = cfg.block_size
    H = np.array(hess, dtype=np.float64)
    # dead inputs
    dead = np.diag(H) == 0
    H[dead, dead] = 1.0
    w[dead, :] = 0.0
    H += np.eye(d_in) * damp * np.mean(np.diag(H))
    # Hinv = Uᵀ U with U upper-triangular — the GPTQ propagation factors
    Hinv = np.linalg.inv(H)
    U = _upper_cholesky(Hinv)

    q = np.zeros_like(w)
    grid = np.asarray(cfg.element.grid, dtype=np.float64)
    mids = (grid[1:] + grid[:-1]) / 2.0

    for b0 in range(0, d_in, B):
        b1 = min(b0 + B, d_in)
        # MX scales from the *current* compensated weights of this block
        amax = np.max(np.abs(w[b0:b1, :]), axis=0)          # (d_out,)
        if cfg.scale_mode == "pow2":
            safe = np.where(amax > 0, amax, 1.0)
            s = np.exp2(np.floor(np.log2(safe)) - cfg.element.r_max)
            s = np.where(amax > 0, s, 1.0)
        else:
            s = np.where(amax > 0, amax / cfg.element.max_val, 1.0)
        err_block = np.zeros((b1 - b0, d_out))
        for i in range(b0, b1):
            z = w[i, :] / s
            idx = np.searchsorted(mids, np.abs(z), side="right")
            qi = np.sign(z) * grid[idx] * s
            q[i, :] = qi
            e = (w[i, :] - qi) / U[i, i]
            if i + 1 < b1:
                w[i + 1:b1, :] -= np.outer(U[i, i + 1:b1], e)
            err_block[i - b0, :] = e
        if b1 < d_in:
            w[b1:, :] -= U[b0:b1, b1:].T @ err_block
    return q.astype(np.float32)


def _upper_cholesky(m: np.ndarray) -> np.ndarray:
    """Upper-triangular U with m = Uᵀ U (the GPTQ propagation factors):
    the transpose of the standard lower Cholesky factor."""
    return np.linalg.cholesky(m).T


def rtn_matrix(w: np.ndarray, cfg: mxlib.MXConfig) -> np.ndarray:
    """Round-to-nearest along d_in (no compensation)."""
    wq = mxlib.quantize(jnp.asarray(w).T, cfg, ste=False).T
    return np.asarray(wq, dtype=np.float32)


# ---------------------------------------------------------------------------
# Hessian capture for the dense-transformer family
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class HessianStats:
    """Per-layer input Hessians keyed by role."""
    h_attn_in: np.ndarray     # (L, d, d)  — input of wq/wk/wv
    h_attn_out: np.ndarray    # (L, qd, qd)
    h_ffn_in: np.ndarray      # (L, d, d)
    h_ffn_down: np.ndarray    # (L, f, f)  — includes online T3


def capture_hessians(params, cfg: ArchConfig, batches: List[dict],
                     qm: QuantMode) -> HessianStats:
    """Unrolled dense forward capturing Σ xᵀx at each linear input.

    The activations are the *quantized-path* inputs (act quant on), matching
    what the deployed GEMMs see."""
    L, d, f, qd = cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.q_dim
    hs = HessianStats(
        h_attn_in=np.zeros((L, d, d)), h_attn_out=np.zeros((L, qd, qd)),
        h_ffn_in=np.zeros((L, d, d)), h_ffn_down=np.zeros((L, f, f)))

    @jax.jit
    def layer_io(x, pl, pos):
        h1 = rms_norm(x, pl["ln1"], cfg.norm_eps)
        x2, _, _ = dense.attn_sublayer(x, pl, cfg, qm, pos,
                                       window=cfg.window)
        h2 = rms_norm(x2, pl["ln2"], cfg.norm_eps)
        x3 = dense.ffn_sublayer(x2, pl, cfg, qm)
        # recompute attention output input & down-proj input
        import jax.numpy as jnp2
        from repro.core.quantize import qlinear
        g = qlinear(h2, pl["wg"], pl.get("bg"), qm, "ffn_in")
        u = qlinear(h2, pl["wu"], pl.get("bu"), qm, "ffn_in")
        hmid = jax.nn.silu(g.astype(jnp2.float32)).astype(x.dtype) * u
        if qm.t3_block:
            hmid = tfm.apply_blockwise(
                hmid, tfm.hadamard_matrix(qm.t3_block, dtype=hmid.dtype))
        return x3, h1, h2, hmid

    for b in batches:
        x = dense.embed_inputs(params, cfg, jnp.asarray(b["inputs"]))
        S = x.shape[1]
        pos = jnp.arange(S, dtype=jnp.int32)
        for l in range(L):
            pl = jax.tree.map(lambda a: a[l], params["blocks"])
            xn, h1, h2, hmid = layer_io(x, pl, pos)
            # attention-out input: recompute q/k/v path output pre-wo
            flat = lambda t: np.asarray(
                t.astype(jnp.float32)).reshape(-1, t.shape[-1])
            a1 = flat(h1)
            hs.h_attn_in[l] += a1.T @ a1
            a2 = flat(h2)
            hs.h_ffn_in[l] += a2.T @ a2
            am = flat(hmid)
            hs.h_ffn_down[l] += am.T @ am
            x = xn
    return hs


def quantize_weights_gptq(params, cfg: ArchConfig, stats: HessianStats,
                          mxcfg: mxlib.MXConfig, t3_block: int = 32):
    """GPTQ the dense-family weights using captured Hessians; weights with
    no Hessian (wo — cheap to add, embeddings, head) fall back to RTN."""
    p = dict(params)
    b = dict(p["blocks"])
    L = cfg.n_layers

    def per_layer(name, hess_key):
        ws = np.asarray(b[name], dtype=np.float32)
        out = np.empty_like(ws)
        for l in range(L):
            hess = getattr(stats, hess_key)[l]
            out[l] = gptq_matrix(ws[l], hess, mxcfg)
        b[name] = jnp.asarray(out, dtype=b[name].dtype)

    per_layer("wq", "h_attn_in")
    per_layer("wk", "h_attn_in")
    per_layer("wv", "h_attn_in")
    per_layer("wg", "h_ffn_in")
    per_layer("wu", "h_ffn_in")
    per_layer("wd", "h_ffn_down")
    b["wo"] = jnp.asarray(
        np.stack([rtn_matrix(np.asarray(b["wo"][l], np.float32), mxcfg)
                  for l in range(L)]), dtype=b["wo"].dtype)
    p["blocks"] = b
    return p


# ---------------------------------------------------------------------------
# RTN for any family (generic tree traversal)
# ---------------------------------------------------------------------------

WEIGHT_KEYS = {"wq", "wk", "wv", "wo", "wg", "wu", "wd", "router",
               "eg", "eu", "ed", "sg", "su", "sd", "in_proj", "out_proj",
               "wx", "wy", "wor"}
_WEIGHT_KEYS = WEIGHT_KEYS  # back-compat alias


def quantize_weights_rtn(params, cfg: ArchConfig, mxcfg: mxlib.MXConfig):
    """Fake-quantize every linear weight along its input axis (axis -2)."""
    def rtn(w):
        return mxlib.quantize(w.T, mxcfg, ste=False).T.astype(w.dtype)

    def visit(path, leaf):
        name = str(path[-1].key) if hasattr(path[-1], "key") else ""
        if name in _WEIGHT_KEYS and leaf.ndim >= 2:
            return mxlib.map_matrices(rtn, leaf)
        return leaf
    return jax.tree_util.tree_map_with_path(visit, params)
