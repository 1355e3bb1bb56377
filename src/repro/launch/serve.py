"""Serving entry point: PTQ a model (or load a checkpoint) and serve
batched requests with the MX-quantized engine.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --reduced --method latmix-lu --fmt mxfp4 --requests 8

Artifact workflow (calibrate once, serve many times): add --export DIR
to persist the packed quantized checkpoint after PTQ, and start future
runs with --artifact DIR to skip calibration/quantization entirely.

Scheduling: --scheduler wave (static batching, default) or continuous
(slot-pool continuous batching — per-request outputs are token-identical,
decode-step utilization is much higher on mixed-length traffic; see
docs/serving.md).

Sampling: --temperature / --top-k / --top-p switch decode from greedy
argmax to seeded stochastic sampling (--sample-seed; reruns replay
token-for-token). --spec-k K turns on self-drafting speculative
decoding — prompt-lookup drafts up to K tokens per step, one batched
verify forward scores them all; outputs are unchanged (docs/sampling.md).

Observability: --trace OUT.json exports a Chrome trace of the run
(request lifecycles + engine steps, open in Perfetto); --metrics
instruments kernel dispatches and prints the Prometheus metrics
snapshot at exit (docs/observability.md).

HTTP serving: --http HOST:PORT skips the synthetic throughput run and
starts the asyncio HTTP/SSE front end over the built engine instead
(POST /v1/generate, /healthz, /readyz, /metrics; admission shedding via
--max-queue-depth / --admit-token-budget; SIGTERM drains gracefully —
docs/server.md). ``examples/client.py`` is the matching client.
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--method", default="latmix-lu")
    ap.add_argument("--fmt", default="mxfp4")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--artifact", default="",
                    help="serve a packed artifact directory (skips PTQ)")
    ap.add_argument("--export", default="",
                    help="export the PTQ result as a packed artifact")
    ap.add_argument("--eager", action="store_true",
                    help="with --artifact: dequantize weights at load")
    ap.add_argument("--backend", default="ref", choices=("ref", "fused"),
                    help="matmul execution backend: 'fused' routes packed "
                         "weights through the Pallas MX kernels "
                         "(interpret-mode off-TPU: correctness only)")
    ap.add_argument("--scheduler", default="wave",
                    choices=("wave", "continuous"),
                    help="request scheduler: 'wave' = static batching; "
                         "'continuous' = slot-pool continuous batching "
                         "(chunked prefill, per-slot decode positions; "
                         "see docs/serving.md)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop a request at (and including) this token id")
    ap.add_argument("--kv-cache", default="none",
                    choices=("none", "mxfp8", "mxint8", "mxfp4", "mxint4"),
                    help="store the KV cache MX-quantized (codes + E8M0 "
                         "scale bytes; ~4x less decode KV traffic for "
                         "mxfp4 vs bf16, ~2x for mxfp8 — see "
                         "docs/kv-cache.md). 'none' keeps the dense cache")
    ap.add_argument("--kv-layout", default="contiguous",
                    choices=("contiguous", "paged"),
                    help="KV-cache layout: 'paged' addresses a pool of "
                         "fixed-size pages through block tables with "
                         "ref-counted prefix caching (continuous "
                         "scheduler only; see docs/paged-kv.md)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="tokens per KV page under --kv-layout paged "
                         "(multiple of 32 and of the attention chunk; "
                         "default: smallest attn_chunk multiple >= 64)")
    ap.add_argument("--n-pages", type=int, default=None,
                    help="KV pool size in pages under --kv-layout paged "
                         "(default: scrap + batch * ceil(max_len/page))")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="end-to-end TTL per request in milliseconds; "
                         "expired requests end TIMED_OUT instead of "
                         "queueing unboundedly (docs/robustness.md)")
    ap.add_argument("--ttft-deadline-ms", type=float, default=None,
                    help="time-to-first-token bound in milliseconds "
                         "(expires requests still waiting for a lane)")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="preemptions a request survives before the "
                         "terminal PREEMPTED state (default 3)")
    ap.add_argument("--no-preemption", dest="preemption",
                    action="store_false", default=True,
                    help="disable evicting lower-priority running "
                         "requests under KV-pool pressure")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature; 0 = greedy argmax "
                         "(docs/sampling.md)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest-probability tokens "
                         "(0 = no top-k filter)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling: keep the smallest prefix of "
                         "tokens whose probability mass reaches p")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base RNG seed; request i samples with seed+i, "
                         "so reruns replay token-for-token")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: draft up to K tokens per "
                         "step via prompt-lookup and verify them in one "
                         "batched forward (0 = off; continuous scheduler "
                         "only; outputs unchanged — docs/sampling.md)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest context n-gram the prompt-lookup "
                         "drafter matches (with --spec-k)")
    ap.add_argument("--http", default="", metavar="HOST:PORT",
                    help="serve over HTTP/SSE instead of the synthetic "
                         "throughput run (PORT 0 = ephemeral; SIGTERM "
                         "drains gracefully — docs/server.md)")
    ap.add_argument("--max-queue-depth", type=int, default=None,
                    help="admission cap: shed (429 + Retry-After) past "
                         "this queue depth instead of queueing unboundedly")
    ap.add_argument("--admit-token-budget", type=int, default=None,
                    help="admission cap: shed when queued prompt+max_new "
                         "tokens would exceed this budget")
    ap.add_argument("--drain-timeout-s", type=float, default=30.0,
                    help="with --http: how long SIGTERM waits for "
                         "in-flight requests before cancelling stragglers")
    ap.add_argument("--trace", default="", metavar="OUT.json",
                    help="export a Chrome trace of the run — open in "
                         "https://ui.perfetto.dev "
                         "(docs/observability.md)")
    ap.add_argument("--metrics", action="store_true",
                    help="instrument kernel dispatches and print the "
                         "Prometheus metrics snapshot at exit")
    args = ap.parse_args()
    if args.spec_k > 0:
        args.scheduler = "continuous"  # spec decoding is continuous-only

    import jax
    import jax.numpy as jnp

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from repro import configs
    from repro.core import ptq
    from repro.data import synthetic
    from repro.kernels import ops
    from repro.models import api
    from repro.obs import MetricsRegistry, Tracer
    from repro.serving.engine import Engine
    from repro.serving.policy import SchedulingPolicy, SpecConfig
    from repro.serving.sampling import SamplingParams
    from repro.training import checkpoint as ckpt

    policy = SchedulingPolicy(deadline_ms=args.deadline_ms,
                              ttft_deadline_ms=args.ttft_deadline_ms,
                              preemption=args.preemption,
                              max_retries=args.max_retries,
                              max_queue_depth=args.max_queue_depth,
                              admit_token_budget=args.admit_token_budget)
    sampling = (SamplingParams(temperature=args.temperature,
                               top_k=args.top_k, top_p=args.top_p,
                               seed=args.sample_seed)
                if (args.temperature > 0 or args.top_k > 0
                    or args.top_p < 1.0) else None)
    spec = (SpecConfig(k=args.spec_k, ngram_max=args.spec_ngram)
            if args.spec_k > 0 else None)
    tracer = Tracer() if args.trace else None
    metrics = MetricsRegistry() if args.metrics else None
    if metrics is not None:          # kernel-dispatch hooks (ops.py)
        ops.instrument(metrics)

    if args.artifact:
        t0 = time.time()
        eng = Engine.from_artifact(
            args.artifact, batch_size=args.batch,
            max_len=args.prompt_len + args.max_new + 16, eager=args.eager,
            backend=args.backend, scheduler=args.scheduler,
            eos_id=args.eos_id, kv_cache=args.kv_cache,
            kv_layout=args.kv_layout, page_size=args.page_size,
            n_pages=args.n_pages, metrics=metrics, tracer=tracer,
            policy=policy, spec=spec)
        print(f"loaded artifact {args.artifact} in {time.time()-t0:.1f}s "
              f"({'eager' if args.eager else 'packed-lazy'} weights, "
              f"backend={args.backend}, scheduler={args.scheduler}, "
              f"kv_cache={args.kv_cache}, kv_layout={args.kv_layout}, "
              f"no re-quantization)")
        if args.http:
            return _serve_http(eng, args)
        stats = eng.throughput(n_requests=args.requests,
                               prompt_len=args.prompt_len,
                               max_new=args.max_new, sampling=sampling)
        print(f"served {stats['tokens']} tokens in {stats['seconds']:.2f}s "
              f"-> {stats['tok_per_s']:.1f} tok/s "
              f"({stats['prefill_compiles']} prefill compiles, "
              f"{stats['prefill_chunk_compiles']} chunk compiles, "
              f"decode utilization {stats['decode_utilization']:.2f})")
        if args.kv_layout == "paged":
            print(f"paged KV: {stats['prefix_hit_tokens']} prefix-hit "
                  f"tokens, {stats['blocks_in_use']} blocks in use, "
                  f"{stats['blocks_evicted']} evicted, "
                  f"{eng.kv_bytes_resident()} KV bytes resident")
        _obs_finish(eng, args)
        return

    cfg = (configs.get_reduced(args.arch) if args.reduced
           else configs.get(args.arch))
    if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
        aparams = jax.eval_shape(
            lambda k: api.init(k, cfg), jax.random.PRNGKey(0))
        params, man = ckpt.restore(args.ckpt_dir,
                                   {"params": aparams, "opt": None})
        params = params["params"]
        print(f"loaded checkpoint step {man['step']}")
    else:
        params = api.init(jax.random.PRNGKey(0), cfg)
        print("no checkpoint — random init (demo mode)")

    src = synthetic.make_source(cfg, 8, 64, 0)
    calib = [{k: jnp.asarray(v) for k, v in src.batch(i).items()}
             for i in range(3)]
    t0 = time.time()
    res = ptq.apply_method(args.method, params, cfg, calib, fmt=args.fmt,
                           steps=args.steps)
    print(f"PTQ [{args.method} / {args.fmt}] in {time.time()-t0:.0f}s")
    if args.export:
        out = res.export(cfg, args.export)
        print(f"exported artifact -> {out}")

    eng = Engine(res.params, cfg, res.qm, batch_size=args.batch,
                 max_len=args.prompt_len + args.max_new + 16,
                 backend=args.backend, scheduler=args.scheduler,
                 eos_id=args.eos_id, kv_cache=args.kv_cache,
                 kv_layout=args.kv_layout, page_size=args.page_size,
                 n_pages=args.n_pages, metrics=metrics, tracer=tracer,
                 policy=policy, spec=spec)
    if args.http:
        return _serve_http(eng, args)
    stats = eng.throughput(n_requests=args.requests,
                           prompt_len=args.prompt_len,
                           max_new=args.max_new, sampling=sampling)
    print(f"served {stats['tokens']} tokens in {stats['seconds']:.2f}s "
          f"-> {stats['tok_per_s']:.1f} tok/s "
          f"(scheduler={stats['scheduler']}, "
          f"decode utilization {stats['decode_utilization']:.2f})")
    if args.kv_layout == "paged":
        print(f"paged KV: {stats['prefix_hit_tokens']} prefix-hit "
              f"tokens, {stats['blocks_in_use']} blocks in use, "
              f"{stats['blocks_evicted']} evicted, "
              f"{eng.kv_bytes_resident()} KV bytes resident")
    _obs_finish(eng, args)


def _serve_http(eng, args) -> None:
    """--http epilogue: run the asyncio front end until SIGTERM/SIGINT,
    then print the drain report and exit by its verdict."""
    import json as _json
    import sys as _sys

    from repro.serving.server import ServerConfig, serve

    host, _, port = args.http.rpartition(":")
    report = serve(eng, ServerConfig(
        host=host or "127.0.0.1", port=int(port or 8100),
        drain_timeout_s=args.drain_timeout_s))
    print("drain report: " + _json.dumps(report), flush=True)
    _obs_finish(eng, args)
    if not report["clean"]:
        _sys.exit(1)


def _obs_finish(eng, args) -> None:
    """--trace/--metrics epilogue: export the Chrome trace and print the
    Prometheus exposition of the engine's registry (which also carries
    the kernel-dispatch metrics when --metrics instrumented ops)."""
    if stats := eng.stats():
        if args.spec_k > 0:
            print(f"speculative decoding: "
                  f"{stats['spec_proposed_tokens']} drafted, "
                  f"{stats['spec_accepted_tokens']} accepted "
                  f"(acceptance {stats['spec_acceptance']:.2f})")
        if stats.get("ttft_p50") is not None:
            print(f"latency: ttft p50={stats['ttft_p50']*1e3:.1f}ms "
                  f"p99={stats['ttft_p99']*1e3:.1f}ms"
                  + (f", tpot p50={stats['tpot_p50']*1e3:.1f}ms"
                     if stats.get("tpot_p50") is not None else ""))
    if args.trace:
        print(f"trace -> {eng.tracer.export(args.trace)} "
              f"({len(eng.tracer.events())} events)")
    if args.metrics:
        print(eng.metrics.render_prometheus())


if __name__ == "__main__":
    main()
