"""Inter-token latency, 99th percentile, ms (host clock).

Every gap between two consecutive token deliveries to one request
(``Request.on_token``) whose later token lands in the window, over all
requests. Decode runs in bursts with one host fetch per burst, so a
streaming client gets a burst's tokens together and then waits for the
next burst, and for any admission that runs before it. In a batch of 64
lanes about one gap in twenty is such a wait and the rest are
microseconds, so the 95th percentile falls on the step between the two
and swings from one to the other; the 99th reads the waits.
"""
from chipbench.measure import percentile_ms


def read(run):
    t0, t1 = run.win.t0, run.win.t1
    return percentile_ms([b - a for r in run.recs
                          for a, b in zip(r.times, r.times[1:])
                          if t0 <= b < t1], 99)
