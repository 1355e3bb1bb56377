"""Operations and bytes the algorithm needs, from shapes alone.

A kernel's roofline time is the larger of its operations over the peak
rate and its bytes over the HBM bandwidth: the least time the chip could
take. Bytes count each operand once, at its stored width: packed MXFP4
weights at half a byte per value plus one E8M0 byte per 32, MXFP8 KV at
one byte per value plus one per 32, f32 activations at 4.
"""
from __future__ import annotations

from families.dense_gqa import MATRICES, Dims

MX8 = 1 + 1 / 32          # bytes per MXFP8 value with its scale
MX4 = 0.5 + 1 / 32        # bytes per packed MXFP4 value with its scale


def gemm_packed(m: int, k: int, n: int, x_bytes: int = 4) -> tuple:
    """(ops, bytes) of Q4(x) (m, k) @ W (k, n) -> f32 (m, n)."""
    return 2 * m * k * n, m * k * x_bytes + k * n * MX4 + m * n * 4


def decode_attention(dm: Dims, ctx: int) -> tuple:
    """(ops, bytes) of one lane's decode attention over ``ctx`` keys, all
    layers: QK^T and PV, the MXFP8 K and V read once, q in, out out."""
    ops = 4 * dm.qd * ctx
    byt = 2 * ctx * dm.kd * MX8 + 2 * dm.qd * 4
    return dm.L * ops, dm.L * byt


def prefill_attention(dm: Dims, start: int, rows: int) -> tuple:
    """(ops, bytes) of one prefill chunk's attention, all layers: ``rows``
    queries at positions start.. attend causally; the prefix's MXFP8 K/V
    are read, the chunk's f32 q/k/v come in and its f32 out and MXFP8
    K/V go out."""
    pairs = rows * start + rows * (rows + 1) // 2
    ops = 4 * dm.qd * pairs
    byt = (2 * start * dm.kd * MX8 + rows * (2 * dm.qd + 2 * dm.kd) * 4
           + 2 * rows * dm.kd * MX8)
    return dm.L * ops, dm.L * byt


def matmul_params(dm: Dims) -> int:
    """Parameters that a token multiplies: every layer's seven matrices
    and the LM head."""
    per = sum(dm.size(kn) * dm.size(nn) for _, kn, nn, _ in MATRICES)
    return dm.L * per + dm.d * dm.V


def token_flops(dm: Dims, pos: int) -> int:
    """Model FLOPs of the token at position ``pos``: 2 per multiplied
    parameter plus causal attention over pos + 1 keys."""
    return 2 * matmul_params(dm) + 4 * dm.L * dm.qd * (pos + 1)


def prompt_flops(dm: Dims, n: int) -> int:
    """Model FLOPs of a whole n-token prompt."""
    return 2 * matmul_params(dm) * n + 4 * dm.L * dm.qd * n * (n + 1) // 2


def roofline_s(ops: float, byt: float, pk: dict) -> tuple:
    """(seconds, 'compute' | 'memory') the chip needs at best."""
    tc, tm = ops / pk["flops_per_s"], byt / pk["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
