"""Shared arithmetic of the metric readers: window tokens, phase times,
model FLOPs. Each reader in ``metrics/`` is a few lines over these."""
from __future__ import annotations

import math

import numpy as np

from chipbench import costs
from chipbench import trace as tr


def decode_positions(run) -> list:
    """Positions of the tokens that decode steps delivered in the window
    (every served token but a request's first, which prefill makes)."""
    return [pos for pos, _t, i in run.window_tokens() if i > 0]


def prefilled(run) -> list:
    """Records whose prefill ended in the window (first token in it)."""
    t0, t1 = run.win.t0, run.win.t1
    return [r for r in run.recs if r.times and t0 <= r.times[0] < t1]


def phase_time_s(run, phase: str) -> float:
    """Device seconds of the program executions of a phase."""
    return sum(e - s for _n, s, e, ph in run.view.modules
               if ph == phase) * 1e-9


def kernel_time_s(run, kernel: str, phase: str = None) -> float:
    return sum(o.end - o.start for o in run.view.ops
               if o.kernel == kernel and (phase is None or o.phase == phase)
               ) * 1e-9


def gemm_roofline_share(run, phase: str):
    """Share of the packed GEMMs' least time in their device time, over
    every ``mx_gemm_packed`` call of the phase, each call's operations and
    bytes from the operand shapes the trace gives it: the activations
    first, the packed codes (K/2, N) and their scales last (a T3 call
    holds its Hadamard block between them; its rotation, under 1% of the
    product's operations, is not counted). None when the trace holds no
    such call."""
    least = spent = 0.0
    for o in run.view.ops:
        if o.kernel == "mx_gemm_packed" and o.phase == phase:
            shapes = tr.operand_shapes(o.name)
            x, codes = shapes[0], shapes[-2]
            ops, byt = costs.gemm_packed(x[-2], x[-1], codes[-1])
            least += costs.roofline_s(ops, byt, run.pk)[0]
            spent += (o.end - o.start) * 1e-9
    return 100 * least / spent if spent else None


def percentile_ms(values, q: float):
    if not values:
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q)) * 1e3
    return v if math.isfinite(v) else None


def decode_mfu(run):
    """Model FLOPs of the tokens decode steps delivered in the window over
    the decode programs' device time times the peak, %."""
    t = phase_time_s(run, "decode")
    if not t:
        return None
    fl = sum(costs.token_flops(run.dm, p - 1) for p in decode_positions(run))
    return 100 * fl / (t * run.pk["flops_per_s"])


def decode_attention_share(run):
    """Least time of the decode attention the window's decoded tokens
    needed (each layer reads its MXFP8 K and V over the token's context
    once) over ``mx_flash_decode_paged``'s device time in decode steps, %."""
    t = kernel_time_s(run, "mx_flash_decode_paged", "decode")
    if not t:
        return None
    least = sum(costs.roofline_s(*costs.decode_attention(run.dm, p),
                                 run.pk)[0]
                for p in decode_positions(run))
    return 100 * least / t
