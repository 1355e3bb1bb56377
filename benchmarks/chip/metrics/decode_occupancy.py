"""Decode occupancy, %: tokens that decode steps delivered in the window
(harness, ``Request.on_token``) over the decode slot-steps the engine ran
in it (lanes x steps, its ``slot_steps`` counter at the window's ends).
The engine's own ``useful_decode_tokens`` counts a token only when its
request finishes, which a long request may not do inside the window."""
from chipbench.measure import decode_positions


def read(run):
    s0, s1 = run.win.stats0, run.win.stats1
    slots = s1["slot_steps"] - s0["slot_steps"]
    if slots <= 0:
        return None
    return 100 * len(decode_positions(run)) / slots
