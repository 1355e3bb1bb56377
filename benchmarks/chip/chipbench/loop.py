"""Drive the system's streaming entry (``Engine.submit`` / ``Engine.step``)
with a planned load, and record what a client sees.

Every token's delivery time comes from ``Request.on_token`` on the host
clock. Time to first token is timed from when the request was due on the
arrival schedule, not from its submit, so a stall shows in every request
it delays. The harness's own calls are wrapped in profiler annotations
(``submit``, ``step``, ``wait_arrival``) so that a device trace can say
what the host was doing in an idle gap.
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import jax
import numpy as np

from chipbench.traffic import Planned


@dataclasses.dataclass(eq=False)
class Rec:
    plan: Planned
    t_due: float                      # perf_counter the request was due
    t_submit: float = 0.0
    times: List[float] = dataclasses.field(default_factory=list)
    toks: List[int] = dataclasses.field(default_factory=list)
    req: object = None                # the engine's Request
    in_window: bool = False           # due (open) / sent (closed) in it

    @property
    def done(self) -> bool:
        return self.req is not None and self.req.state.value != "queued" \
            and self.req.state.value != "running"

    @property
    def finished(self) -> bool:
        return (self.req is not None and self.req.state.value == "finished"
                and len(self.times) == self.plan.max_new)


@dataclasses.dataclass
class Window:
    """The measured window. ``t0``/``t1`` start as the planned times and
    become the times it really opened and closed: both fall between
    steps, since a step cannot be cut."""
    t0: float
    t1: float
    stats0: dict = None
    stats1: dict = None
    queue_wait: List[float] = dataclasses.field(default_factory=list)
    pages_in_use: List[float] = dataclasses.field(default_factory=list)
    compiles: int = 0


class Client:
    def __init__(self, eng, annotate: bool):
        self.eng = eng
        self.recs: List[Rec] = []
        self._ann = annotate
        self._pages = eng.metrics.get("serving_blocks_in_use")
        self._qwait = eng.metrics.get("serving_queue_wait_seconds")
        # called at the window's opening and closing, and after each step
        # of the drain that follows it (the last until it returns True)
        self.on_open = self.on_close = self.on_drain = None
        self.deadline_ms = None       # the mix's per-request deadline

    def annotation(self, name: str):
        if self._ann:
            return jax.profiler.TraceAnnotation(name)
        return _NULL

    def submit(self, plan: Planned, t_due: float, in_window: bool) -> Rec:
        from repro.serving.engine import Request
        rec = Rec(plan, t_due, in_window=in_window)
        times, toks = rec.times, rec.toks

        def on_token(tok):
            times.append(time.perf_counter())
            toks.append(tok)
        rec.req = Request(prompt=plan.prompt, max_new=plan.max_new,
                          on_token=on_token, deadline_ms=self.deadline_ms)
        with self.annotation("submit"):
            rec.t_submit = time.perf_counter()
            self.eng.submit(rec.req)
        self.recs.append(rec)
        return rec

    def step(self, win: Optional[Window]) -> list:
        with self.annotation("step"):
            done = self.eng.step()
        if win is not None:
            self._pages_sample(win)
        return done

    def _pages_sample(self, win: Window) -> None:
        if win.stats0 is not None and win.stats1 is None:
            win.pages_in_use.append(self._pages.value)

    def open_window(self, win: Window) -> None:
        if self.on_open:
            self.on_open()
        win.t0 = time.perf_counter()
        win.stats0 = self.eng.stats()
        self._q0 = self._qwait.count

    def close_window(self, win: Window) -> None:
        win.t1 = time.perf_counter()
        win.stats1 = self.eng.stats()
        win.queue_wait = list(self._qwait._samples[self._q0:])
        if self.on_close:
            self.on_close()


_NULL = type("_Null", (), {"__enter__": lambda s: s,
                           "__exit__": lambda s, *a: False})()


def run_open(drv: Client, plans: List[Planned], mix: dict,
             seconds: float, compile_count) -> Window:
    """Submit each planned request when it is due; step the engine
    whenever it has work; sleep to the next arrival otherwise. After the
    window, keep stepping until every request due in it has finished, or
    ``drain_cap_s`` has passed."""
    origin = time.perf_counter()
    start, end = origin + mix["ramp_s"], origin + mix["ramp_s"] + seconds
    win = Window(start, end)
    i, n = 0, len(plans)
    while True:
        now = time.perf_counter()
        while i < n and origin + plans[i].due <= now:
            due = origin + plans[i].due
            drv.submit(plans[i], due, start <= due < end)
            i += 1
        if win.stats0 is None and now >= start:
            drv.open_window(win)
            c0 = compile_count()
        if now >= end:
            break
        if drv.eng.busy:
            drv.step(win)
        else:
            nxt = origin + plans[i].due if i < n else end
            with drv.annotation("wait_arrival"):
                time.sleep(max(0.0, min(nxt, end) - now))
    win.compiles = compile_count() - c0
    drv.close_window(win)
    cap = end + mix["drain_cap_s"]
    pending = [r for r in drv.recs if r.in_window]
    hook = drv.on_drain
    while time.perf_counter() < cap and not all(r.done for r in pending):
        drv.step(None)
        if hook is not None and hook():
            hook = None
    return win


def run_closed(drv: Client, plans: List[Planned], mix: dict,
               seconds: float, compile_count) -> Window:
    """One client per lane: each sends its next request as soon as its
    last one finishes. The ramp fills every lane before the window."""
    origin = time.perf_counter()
    start, end = origin + mix["ramp_s"], origin + mix["ramp_s"] + seconds
    win = Window(start, end)
    it = iter(plans)

    def send(now):
        drv.submit(next(it), now, start <= now < end)

    for _ in range(mix["batch"]):
        send(origin)
    while True:
        now = time.perf_counter()
        if win.stats0 is None and now >= start:
            drv.open_window(win)
            c0 = compile_count()
        if now >= end:
            break
        for _ in drv.step(win):
            send(time.perf_counter())
    win.compiles = compile_count() - c0
    drv.close_window(win)
    return win


def warm_up(eng, plans: List[Planned], mix: dict) -> None:
    """Compile every program the window will run: the one-lane prefill
    chunk and the full-batch decode step (one short request), and the
    host-side stack of each decode burst length up to the mix's
    ``warm_bursts`` (default: its longest output)."""
    import jax.numpy as jnp
    from repro.serving.engine import Request
    p = plans[0]
    reqs = [Request(prompt=p.prompt[:eng.cfg.attn_chunk + 1], max_new=3)]
    eng.generate(reqs)
    if reqs[0].state.value != "finished":
        raise RuntimeError(f"warm-up request ended {reqs[0].state.value}: "
                           f"{reqs[0].error}")
    # the engine stacks a burst's committed per-step outputs on the host
    # side of the device; one small program per burst length
    dev = jax.devices()[0]
    B, top = eng.B, mix.get("warm_bursts", mix["output"]["max"])
    toks = [jax.device_put(jnp.zeros(B, jnp.int32), dev)] * top
    oks = [jax.device_put(jnp.ones(B, bool), dev)] * top
    for n in range(1, top + 1):
        np.asarray(jnp.stack(toks[:n], axis=1))
        np.asarray(jnp.stack(oks[:n], axis=1))
    np.asarray(toks[0] + 1)
