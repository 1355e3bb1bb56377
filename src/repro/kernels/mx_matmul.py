"""Pallas TPU kernels: fused activation-quantizing MX GEMMs.

  y = Q_mx(x) @ dequant(w)

— the deployment hot-spot after LATMiX folding: activations arrive bf16,
are MX-quantized on the fly (per-row 32-blocks along K), the weight tile is
decoded from its stored codes + power-of-two column scales, and the MXU
accumulates fp32 over the K grid axis.

Two weight layouts:

  :func:`mx_matmul`          — interpreter layout: one uint8 code per byte,
                               f32 scales ((K, N) + (K//32, N)).
  :func:`mx_matmul_packed`   — the HBM/artifact layout consumed *directly*:
                               two 4-bit codes per byte ((K//2, N) uint8)
                               + E8M0 scale bytes ((K//32, N) uint8),
                               decoded inside the kernel tile. No dense fp
                               weight is ever materialized, and the weight
                               VMEM/HBM traffic is half the uint8-per-code
                               layout (9 bits/param total vs 17).

``mx_matmul_packed(t3=True)`` additionally fuses the online T3
block-Hadamard into the activation-quantize prologue (the ``ffn_down``
call-site), saving the separate rotate pass over the widest activation
stream in the network.

Tiling: grid (M/BM, N/BN, K/BK), K innermost so the (BM, BN) fp32
accumulator tile stays resident in VMEM across the K sweep. BM/BN/BK are
multiples of 128 (MXU-aligned) when shapes allow; BK a multiple of 32 keeps
whole MX blocks inside one tile so scales never straddle instances.

VMEM per instance (BM=BN=256, BK=512, packed layout): x 512K + w codes 64K
+ w scales 4K + acc 256K ≈ 0.82 MiB « 16 MiB.

On CPU these run in interpret mode for correctness only; the roofline
memory term uses the 4-bit packed byte count (see DESIGN.md §2).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import mx as mxlib
from repro.core import transforms as tfm
from .mx_quant import (MXBLOCK, _decode_tile, _format_consts, _quant_tile,
                       e8m0_to_f32)


def _pick_blocks(M: int, N: int, K: int, bm: int, bn: int, bk: int):
    """Shrink requested block sizes until they divide the problem. K is
    always a multiple of 32 for MX operands, and every halving of 512
    stays a multiple of 32, so bk lands on a whole number of MX blocks."""
    bm, bn, bk = min(bm, M), min(bn, N), min(bk, K)
    while M % bm:
        bm //= 2
    while N % bn:
        bn //= 2
    while K % bk:
        bk //= 2
    return bm, bn, bk


def _mx_matmul_kernel(x_ref, wc_ref, ws_ref, out_ref, *, fmt, n_k):
    grid, mids, r_max, center = _format_consts(fmt)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    x = x_ref[...].astype(jnp.float32)            # (BM, BK)
    bm, bk = x.shape
    xb = x.reshape(bm, bk // MXBLOCK, MXBLOCK)
    codes, scale = _quant_tile(xb, grid, mids, r_max, center)
    xq = (_decode_tile(codes, grid, center)
          * scale[..., None]).reshape(bm, bk)

    wc = wc_ref[...]                              # (BK, BN) uint8
    ws = ws_ref[...]                              # (BK//32, BN) f32
    wvals = _decode_tile(wc, grid, center)
    bn = wc.shape[1]
    w = (wvals.reshape(bk // MXBLOCK, MXBLOCK, bn)
         * ws[:, None, :]).reshape(bk, bn)

    out_ref[...] += jnp.dot(xq, w, preferred_element_type=jnp.float32)


def mx_matmul(x: jnp.ndarray, w_codes: jnp.ndarray, w_scales: jnp.ndarray,
              fmt: str = "mxfp4", *, bm: int = 256, bn: int = 256,
              bk: int = 512, interpret: bool = True,
              out_dtype=jnp.float32) -> jnp.ndarray:
    """x: (M, K); w_codes: (K, N) uint8; w_scales: (K//32, N) f32."""
    M, K = x.shape
    K2, N = w_codes.shape
    assert K == K2 and w_scales.shape == (K // MXBLOCK, N)
    bm, bn, bk = _pick_blocks(M, N, K, bm, bn, bk)
    assert bk % MXBLOCK == 0, (bk,)
    kern = functools.partial(_mx_matmul_kernel, fmt=fmt, n_k=K // bk)
    out = pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((bk // MXBLOCK, bn), lambda i, j, k: (k, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(x, w_codes, w_scales)
    return out.astype(out_dtype)


# ---------------------------------------------------------------------------
# Packed-native layout: nibble-packed codes + E8M0 scale bytes in, fp out
# ---------------------------------------------------------------------------

def _unpack_tile(wp):
    """(BK//2, BN) nibble-packed uint8 -> (BK, BN) uint8 codes.

    ``pack_codes`` puts code 2i in the low nibble and 2i+1 in the high
    nibble of byte i (along the contraction axis), so the interleave is a
    sublane-axis stack+reshape — no gather."""
    wp = wp.astype(jnp.int32)
    lo = wp & 0xF
    hi = (wp >> 4) & 0xF
    bk2, bn = wp.shape
    return jnp.stack([lo, hi], axis=1).reshape(bk2 * 2, bn)


def _quant_act_tile(x, ht, grid, mids, r_max, center):
    """(BM, BK) f32 activations -> their MX roundtrip values (BM, BK).

    The 32-blocks run along the lane axis, which Mosaic cannot reshape,
    so the tile is transposed to (BK, BM) and blocked along sublanes as
    (BK//32, 32, BM). ``ht`` (H₃₂ᵀ, or None) applies the T3 rotation to
    each block first: (x_b · H)ᵀ = Hᵀ · x_bᵀ."""
    bm, bk = x.shape
    nb = bk // MXBLOCK
    xb = x.T.reshape(nb, MXBLOCK, bm)
    if ht is not None:
        hb = jnp.broadcast_to(ht, (nb, MXBLOCK, MXBLOCK))
        xb = jnp.einsum("bij,bjm->bim", hb, xb,
                        preferred_element_type=jnp.float32)
    codes, scale = _quant_tile(xb, grid, mids, r_max, center, axis=1)
    xq = _decode_tile(codes, grid, center) * scale[:, None, :]
    return xq.reshape(bk, bm).T


def _mx_matmul_packed_kernel(*refs, fmt, t3):
    if t3:
        x_ref, ht_ref, wp_ref, ws_ref, out_ref = refs
    else:
        x_ref, wp_ref, ws_ref, out_ref = refs
    grid, mids, r_max, center = _format_consts(fmt)
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # fused T3 prologue (t3): rotate each 32-block before quantizing
    xq = _quant_act_tile(x_ref[...].astype(jnp.float32),
                         ht_ref[...] if t3 else None,
                         grid, mids, r_max, center)          # (BM, BK)
    bk = xq.shape[1]

    wc = _unpack_tile(wp_ref[...])                # (BK, BN) int32 codes
    wvals = _decode_tile(wc, grid, center)
    bn = wc.shape[1]
    ws = e8m0_to_f32(ws_ref[...])                 # (BK//32, BN)
    w = (wvals.reshape(bk // MXBLOCK, MXBLOCK, bn)
         * ws[:, None, :]).reshape(bk, bn)

    out_ref[...] += jnp.dot(xq, w, preferred_element_type=jnp.float32)


def mx_matmul_packed(x: jnp.ndarray, w_packed: jnp.ndarray,
                     w_scales_e8m0: jnp.ndarray, fmt: str = "mxfp4", *,
                     t3: bool = False, bm: int = 256, bn: int = 256,
                     bk: int = 512, interpret: bool = True,
                     out_dtype=jnp.float32) -> jnp.ndarray:
    """Packed-native fused MX GEMM: y = Q_mx([x·blockdiag(H₃₂)]) @ deq(w).

    x: (M, K) float; w_packed: (K//2, N) uint8, two 4-bit codes per byte
    along K; w_scales_e8m0: (K//32, N) uint8 E8M0 scale bytes — i.e. the
    exact HBM/artifact layout of :class:`repro.kernels.packing.PackedWeight`.
    The dense fp weight exists only as per-tile VMEM values inside the
    kernel. ``t3=True`` applies the online block-Hadamard (T3) to each
    activation 32-block before quantization (the ``ffn_down`` role).
    """
    M, K = x.shape
    K2, N = w_packed.shape
    assert K == 2 * K2, (x.shape, w_packed.shape)
    assert w_scales_e8m0.shape == (K // MXBLOCK, N), w_scales_e8m0.shape
    assert K % MXBLOCK == 0, (K,)
    bm, bn, bk = _pick_blocks(M, N, K, bm, bn, bk)
    assert bk % MXBLOCK == 0, (bk,)
    kern = functools.partial(_mx_matmul_packed_kernel, fmt=fmt, t3=t3)
    in_specs = [pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))]
    args = [x]
    if t3:
        in_specs.append(pl.BlockSpec((MXBLOCK, MXBLOCK),
                                     lambda i, j, k: (0, 0)))
        args.append(tfm.hadamard_matrix(MXBLOCK, dtype=jnp.float32).T)
    in_specs += [
        pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j)),
        pl.BlockSpec((bk // MXBLOCK, bn), lambda i, j, k: (k, j)),
    ]
    args += [w_packed, w_scales_e8m0]
    out = pl.pallas_call(
        kern,
        grid=(M // bm, N // bn, K // bk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
    )(*args)
    return out.astype(out_dtype)
