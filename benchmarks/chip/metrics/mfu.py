"""Whole-window MFU, %: model FLOPs of every token computed in the window
(decoded tokens delivered in it, prompts whose prefill ended in it) over
the window's seconds times the chip's bf16 peak."""
from chipbench import costs
from chipbench.measure import decode_positions, prefilled


def read(run):
    fl = sum(costs.token_flops(run.dm, p - 1) for p in decode_positions(run))
    fl += sum(costs.prompt_flops(run.dm, len(r.plan.prompt))
              for r in prefilled(run))
    return 100 * fl / (run.window_s * run.pk["flops_per_s"])
