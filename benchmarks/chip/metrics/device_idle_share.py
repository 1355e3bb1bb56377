"""Device idle share, %: one minus the union of the device's operation
intervals over the traced window (device trace)."""
from chipbench.trace import busy_ns


def read(run):
    v = run.view
    if not v.ops:
        return None
    return 100 * (1 - busy_ns((o.start, o.end) for o in v.ops) * 1e-9
                  / v.window_s)
