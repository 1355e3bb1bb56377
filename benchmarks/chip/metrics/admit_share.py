"""Admission share, %: host seconds the engine spent admitting in the
window (its ``admit_seconds`` counter, ``Engine.stats()`` at the
window's ends: prefix match, page allocation, the prefill chunks and the
first token's fetch, while no lane decodes) over the window's seconds.
None where the engine keeps no such counter."""


def read(run):
    s0, s1 = run.win.stats0, run.win.stats1
    if "admit_seconds" not in s0:
        return None
    return 100 * (s1["admit_seconds"] - s0["admit_seconds"]) / run.window_s
