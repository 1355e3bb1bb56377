"""From a profiler trace of the measured window to what the metrics read.

``jax.profiler`` writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.
The device's plane (``/device:TPU:0``) holds a line of program executions
(``XLA Modules``: one event per run of a jitted program) and a line of the
operations inside them (``XLA Ops``). The host's plane holds the
harness's annotations (``submit``, ``step``, ``wait_arrival``). Both are
on one clock, in nanoseconds.

An operation's name is its HLO text. A Pallas kernel runs as a custom
call named after the jitted wrapper that launched it
(``%_mx_gemm_packed_jit.49 = f32[256,4096] custom-call(...)``), with its
operands' shapes in ``operand_layout_constraints``. Loop and call ops
contain their bodies' ops and are left out of device time. The device
clock is not the host's: the host's annotations are shifted by the
median gap between each program launch on the host
(``PJRT_LoadedExecutable_Execute``) and its execution on the device.
"""
from __future__ import annotations

import dataclasses
import pathlib
import re
from typing import Dict, List, Optional, Tuple

# kernel as the metrics name it -> the custom call's name in the trace
KERNELS = {"mx_gemm_packed": "%_mx_gemm_packed_jit",
           "mx_flash_decode_paged": "%_mx_flash_decode_paged_jit",
           "mx_flash_prefill": "%_mx_flash_prefill_jit"}
CONTAINERS = ("%while", "%conditional", "%call")
LAUNCH = "PJRT_LoadedExecutable_Execute"
# jitted programs of the serving engine -> the phase they serve
PROGRAMS = {"decode_paged": "decode", "prefill_chunk_paged": "prefill"}
HOST_SPANS = ("submit", "step", "wait_arrival")


@dataclasses.dataclass
class Op:
    name: str
    start: int
    end: int
    kernel: Optional[str]       # a KERNELS key, or None
    phase: Optional[str]        # a PROGRAMS value, or None


@dataclasses.dataclass
class View:
    ops: List[Op]
    modules: List[Tuple[str, int, int, Optional[str]]]
    host: List[Tuple[str, int, int]]
    t0: int
    t1: int

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9


def xplane_file(trace_dir) -> pathlib.Path:
    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _kernel_of(name: str) -> Optional[str]:
    head = name.split(" = ")[0].split(".")[0]
    for k, prefix in KERNELS.items():
        if head == prefix:
            return k
    return None


def operand_shapes(name: str) -> List[Tuple[int, ...]]:
    """Operand shapes of an op, from its ``operand_layout_constraints``."""
    m = re.search(r"operand_layout_constraints=\{(.*?\})\}", name)
    if not m:
        return []
    return [tuple(int(x) for x in dims.split(",") if x)
            for dims in re.findall(r"\w+\[([\d,]*)\]", m.group(1))]


def _phase_of(module_name: str) -> Optional[str]:
    for prog, phase in PROGRAMS.items():
        if f"jit_{prog}" in module_name or f"jit({prog})" in module_name:
            return phase
    return None


def raw(trace_dir, device: str = "/device:TPU:0") -> dict:
    """The parts of the trace the metrics read, as plain data: the
    device's program and op lines (with each op's stats) and the host's
    harness annotations. ``view`` reduces it; ``trim`` cuts it short."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(xplane_file(trace_dir)))
    out = {"modules": [], "ops": [], "host": []}
    for plane in pd.planes:
        if plane.name == device:
            for line in plane.lines:
                if line.name == "XLA Modules":
                    out["modules"] += [[ev.name, ev.start_ns, ev.end_ns]
                                       for ev in line.events]
                elif line.name == "XLA Ops":
                    out["ops"] += [[ev.name, ev.start_ns, ev.end_ns]
                                   for ev in line.events
                                   if not ev.name.startswith(CONTAINERS)]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                out["host"] += [[ev.name, ev.start_ns, ev.end_ns]
                                for ev in line.events
                                if ev.name in HOST_SPANS + (LAUNCH,)]
    return out


def trim(data: dict, t0: int, t1: int) -> dict:
    """Only what starts in [t0, t1) of its own clock; the host's events
    are cut at the same distance from their first launch."""
    h0 = min((h[1] for h in data["host"] if h[0] == LAUNCH), default=0)
    d0 = min((m[1] for m in data["modules"]), default=t0)
    hs, he = h0 + (t0 - d0), h0 + (t1 - d0)
    return {"modules": [m for m in data["modules"] if t0 <= m[1] < t1],
            "ops": [o for o in data["ops"] if t0 <= o[1] < t1],
            "host": [h for h in data["host"] if hs <= h[1] < he]}


def host_offset(modules, launches) -> int:
    """Host time to device time: the median of device start minus host
    launch over the first executions, paired in order."""
    pairs = [m[1] - h[1] for m, h in zip(modules[:64], launches[:64])]
    return int(sorted(pairs)[len(pairs) // 2]) if pairs else 0


def view(data: dict) -> View:
    modules = sorted(((n, s, e, _phase_of(n)) for n, s, e in
                      data["modules"]), key=lambda m: m[1])
    ops = sorted((Op(n, s, e, _kernel_of(n), None)
                  for n, s, e, *_ in data["ops"]), key=lambda o: o.start)
    launches = sorted((h for h in data["host"] if h[0] == LAUNCH),
                      key=lambda h: h[1])
    off = host_offset(modules, launches)
    host = [(n, s + off, e + off) for n, s, e in data["host"]
            if n in HOST_SPANS]
    _assign_phases(ops, modules)
    if ops:
        t0 = min([o.start for o in ops] + [h[1] for h in host])
        t1 = max([o.end for o in ops] + [h[2] for h in host])
    else:
        t0 = t1 = 0
    return View(ops, modules, host, t0, t1)


def load(trace_dir) -> View:
    return view(raw(trace_dir))


def _assign_phases(ops: List[Op], modules) -> None:
    """Each op belongs to the program execution that encloses it."""
    j = 0
    for op in ops:
        while j < len(modules) and modules[j][2] < op.start:
            j += 1
        if j < len(modules) and modules[j][1] <= op.start:
            op.phase = modules[j][3]


def busy_ns(intervals) -> int:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(view: View, top: int = 10) -> list:
    """The longest stretches with no device op, each named by the host
    annotation that overlaps it most ('none' when the host was in none)."""
    gaps, last = [], view.t0
    for s, e in sorted((o.start, o.end) for o in view.ops):
        if s > last:
            gaps.append((last, s))
        last = max(last, e)
    if view.t1 > last:
        gaps.append((last, view.t1))
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:top]:
        best, name = 0, "none"
        for hn, hs, he in view.host:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, hn
        out.append([name, (e - s) * 1e-9])
    return out


def top_ops(view: View, top: int = 10) -> list:
    """Device time by kernel, or by HLO op kind, and program phase."""
    acc: Dict[str, int] = {}
    for o in view.ops:
        key = o.kernel or re.sub(r"\.\d+$", "", o.name.split(" = ")[0])
        if o.phase:
            key = f"{key}@{o.phase}"
        acc[key] = acc.get(key, 0) + (o.end - o.start)
    return [[k, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:top]]
