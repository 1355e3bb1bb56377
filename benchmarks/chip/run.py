"""One run of one benchmark cell, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json``. The run makes
the model's weights on the device from the seed, builds the serving
engine (fused Pallas backend, continuous scheduler, paged MXFP8 KV),
warms up every program the window runs, plays the cell's traffic mix for
a ramp and then the measured window, checks what was served against the
plain reference, and prints one JSON line last. ``--trace 1`` records a
device trace of the window and reports the per-layer metrics instead of
the end-to-end ones. With no TPU, or fewer chips than the cell asks for,
it prints no result and exits 3.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

EXIT_NO_CHIP = 3
QUANT_ROLES = ("qkv", "attn_out", "ffn_in", "ffn_down")
SERVED_KERNELS = ("mx_gemm_packed", "mx_flash_prefill",
                  "mx_flash_decode_paged")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Run:
    """What a metric reader reads: the cell, the window, every request's
    record, engine counters at the window's ends, the device trace."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_tokens(self):
        """(position, delivery time) of every token delivered in the
        window; position is the token's index in its sequence."""
        t0, t1 = self.win.t0, self.win.t1
        for r in self.recs:
            p = len(r.plan.prompt)
            for i, t in enumerate(r.times):
                if t0 <= t < t1:
                    yield p + i, t, i


def check_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        log(f"run.py: needs {chips} TPU chip(s); JAX has {len(devs)} "
            f"{devs[0].platform} device(s) ({devs[0].device_kind})")
        return None
    return devs


def dispatch_check(registry) -> dict:
    """Did the timed path run fused: every quantized role through the
    packed kernel, none on the reference path, all served kernels?"""
    fused, ref, calls = set(), set(), {}
    for m in registry:
        if m.name == "quant_dispatch_total" and m.value > 0:
            (fused if m.labels["path"] == "fused" else ref).add(
                m.labels["role"])
        elif m.name == "kernel_dispatch_calls_total":
            calls[m.labels["op"]] = calls.get(m.labels["op"], 0) + m.value
    return {"roles_off_kernel": len(set(QUANT_ROLES) - fused
                                    | (ref - {"head"})),
            "kernels_missing": sum(calls.get(k, 0) <= 0
                                   for k in SERVED_KERNELS)}


def run_cell(cell, seed: int, seconds: float, trace: bool,
             keep: dict = None) -> dict:
    """The whole run on whatever device JAX has; returns the result.
    ``keep``, when given, receives the sampled requests and their gaps."""
    import jax
    import numpy as np

    from chipbench import correct, loop, peaks, spec, traffic
    from chipbench import trace as tr
    from repro.kernels import ops
    from repro.obs import MetricsRegistry
    from repro.serving.engine import Engine
    from repro.serving.policy import SchedulingPolicy

    cfg, mix = cell.cfg, cell.mix
    fam = spec.load_module("families", cfg["family"])
    ref = spec.load_module("references", cfg["reference"])
    dm = fam.dims(cfg)
    dev = jax.devices()[0]
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, *_a, **_k: compiles.__setitem__(
            0, compiles[0] + (name == "/jax/core/compile/"
                              "backend_compile_duration")))

    w = fam.make_weights(cfg, seed)
    jax.block_until_ready(w)
    params, arch, qm = fam.to_system(cfg, w)
    sv = cfg["serving"]
    page_bytes = sv["page_size"] * fam.kv_bytes_per_token(cfg)
    registry = MetricsRegistry()
    ops.instrument(registry)
    eng = Engine(params, arch, qm, batch_size=mix["batch"],
                 max_len=traffic.max_len(mix), scheduler="continuous",
                 kv_cache=sv["kv_cache"], kv_layout="paged",
                 page_size=sv["page_size"],
                 n_pages=1 + sv["kv_pool_bytes"] // page_bytes,
                 metrics=registry,
                 # serial admission: batched admission pads every chunk
                 # to all of the engine's lanes
                 policy=SchedulingPolicy(max_prefill_lanes_per_step=1))
    if mix["loop"] == "open":
        plans = traffic.open_schedule(mix, seed, seconds, dm.V)
    else:
        plans = traffic.closed_requests(mix, seed, dm.V)
    loop.warm_up(eng, plans, mix)
    t_warm = time.perf_counter()
    log(f"set-up to warm: {t_warm - T_START:.1f} s; pool "
        f"{eng._alloc.n_pages} pages of {sv['page_size']}; "
        f"{traffic.describe(mix, seconds)}")

    drv = loop.Client(eng, annotate=trace)
    drv.deadline_ms = mix.get("deadline_ms")
    tdir, snap = None, {}
    if trace:
        tdir = tempfile.mkdtemp(prefix="chipbench_trace_")
        # host annotations without Python call tracing, which would slow
        # the host that drives the chip
        popt = jax.profiler.ProfileOptions()
        popt.python_tracer_level, popt.host_tracer_level = 0, 1

    def on_open():
        if trace:
            jax.profiler.start_trace(tdir, profiler_options=popt)

    def capture() -> bool:
        # what the timed path wrote to the pool for requests in flight,
        # copied before they finish and free their pages: at the window's
        # close, or at the first step of the drain that has one
        snap["live"] = correct.live_sample(drv.recs, seed)
        snap["kv"] = fam.snapshot_kv(eng, [r.req for r in snap["live"]])
        return bool(snap["live"])

    def on_close():
        if trace:
            jax.profiler.stop_trace()
        drv.on_drain = None if capture() else capture
    drv.on_open, drv.on_close = on_open, on_close
    runner = loop.run_open if mix["loop"] == "open" else loop.run_closed
    win = runner(drv, plans, mix, seconds, lambda: compiles[0])
    t_end = time.perf_counter()
    mem = dev.memory_stats() or {}
    view = None
    if trace:
        view = tr.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
    dispatch = dispatch_check(registry)
    ops.uninstrument()

    run = Run(cell=cell, cfg=cfg, mix=mix, dm=dm,
              window_s=win.t1 - win.t0, win=win, recs=drv.recs, t_end=t_end,
              setup_s=win.t0 - T_START, pk=peaks.peaks(dev.device_kind),
              view=view, n_pages=eng._alloc.n_pages)
    metrics = {}
    for m in cell.metrics(trace):
        v = spec.load_module("metrics", m["name"]).read(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    attempted = [r for r in drv.recs if r.in_window]
    failed = [r for r in attempted
              if not r.times or r.req.state.value not in ("finished",
                                                          "running",
                                                          "queued")]
    chosen = correct.sample(drv.recs, seed)
    # free the system's state before the reference runs: a process's
    # peak never falls again, and the KV pool is most of the chip
    eng._cache = eng.params = None
    del drv, eng, params, w
    gc.collect()
    t_ref = time.perf_counter()
    gaps = correct.served_gaps(ref, cfg, seed, chosen) if chosen else None
    gap = float(np.max(gaps)) if chosen else None
    kvm = (correct.kv_mismatch(ref, cfg, seed, snap["live"], snap["kv"])
           if snap["live"] else None)
    if keep is not None:
        keep.update(chosen=chosen, gaps=gaps, ref=ref, kv_mismatch=kvm,
                    snap=snap)
    log(f"reference over {len(chosen)} requests "
        f"({sum(len(r.toks) for r in chosen)} served tokens) in "
        f"{time.perf_counter() - t_ref:.1f} s")
    chk = cfg["check"]
    compared = {
        "logit_gap": [gap, chk["max_logit_gap"]],
        "kv_l0_mismatch": [kvm, chk["max_kv_l0_mismatch"]],
        "roles_off_kernel": [dispatch["roles_off_kernel"], 0],
        "kernels_missing": [dispatch["kernels_missing"], 0],
    }
    ok = all(v is not None and lim is not None and v <= lim
             for v, lim in compared.values())
    log(f"compiles in the window: {win.compiles}")
    result = {"correct": bool(ok), "attempted": len(attempted),
              "failed": len(failed), "metrics": metrics,
              "device": {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(
                             mem.get("peak_bytes_in_use", 0))}}
    if trace:
        result["device"]["busy_s"] = tr.busy_ns(
            (o.start, o.end) for o in view.ops) * 1e-9
        result["device"]["window_s"] = view.window_s
        result["breakdown"] = {"device_ops": tr.top_ops(view),
                               "idle_gaps": tr.idle_gaps(view)}
    result["compared"] = compared
    for k, (v, lim) in compared.items():
        log(f"compared {k}: {v} limit {lim}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    root = pathlib.Path.cwd()
    sys.path.insert(0, str(root / "src"))
    from chipbench import spec
    cell = spec.load_cell(root, args.workload)
    import repro  # noqa: F401  (the system under test: fail without it)
    if check_device(cell.chips) is None:
        return EXIT_NO_CHIP
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    log(f"compile cache {cache}")
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
