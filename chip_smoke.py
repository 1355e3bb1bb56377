"""Chip smoke test: serve TinyLlama-1.1B at full width on one TPU.

    python chip_smoke.py [--seed 0] [--out DIR]

Drives the served path once, end to end, through the entry points a user
calls (the same sequence as ``python -m repro.launch.serve``):

  1. random-init ``configs.get("tinyllama-1.1b")`` from ``--seed`` (22
     layers, d_model 2048, GQA 32/4, vocab 32000), quantize it with
     ``ptq.apply_method("rtn", fmt="mxfp4")`` and export a packed artifact
     into ``--out``;
  2. ``Engine.from_artifact(backend="fused", scheduler="continuous",
     kv_layout="paged", kv_cache="mxfp8")`` and serve a few requests of a
     few hundred prompt tokens, through ``mx_flash_prefill``,
     ``mx_gemm_packed`` and ``mx_flash_decode_paged``.

It fails (non-zero exit) unless:

  (a) JAX's first device is a TPU — checked before any work, with no CPU
      path;
  (b) the fused path really ran: every quantized role dispatched to the
      packed kernel and none fell back to the reference path, and the three
      served kernels were dispatched;
  (c) the fused prefill's last-position logits agree with the same
      artifact served with ``backend="ref"`` on the same chip, within
      ``LOGIT_REL_BOUND``;
  (d) every request ends FINISHED and the page allocator is consistent.

Everything runs in this one process (a chip belongs to one process). The
last line of standard output is a JSON object naming the device; it is
printed only when every check passed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "tinyllama-1.1b"
# Relative L2 error of the fused prefill logits against the reference
# backend. The two paths differ only in rounding, but MX activation
# quantization turns a last-bit difference into whole code steps.
# Measured on the CPU at this model's full width cut to 2 / 6 layers,
# with this script's prompts: fused vs ref is exactly 0 (the CPU runs the
# same sums in the same order), while the reference against itself with
# each embedding entry moved by a relative 1e-6 differs by 0.27 / 0.35,
# and by 0.38 / 0.42 for a move of 1e-3 (bf16 rounding) — a floor that
# grows slowly with depth. On the TPU the two paths round differently
# and land on that floor. Logits that have nothing to do with the
# reference are sqrt(2) ~ 1.41 apart; a wrong kernel lands there.
LOGIT_REL_BOUND = 0.75
# every role qlinear quantizes in a dense-family model; the LM head stays
# in floating point (QuantMode.quantize_head is off) and takes the
# reference path by design
QUANT_ROLES = ("qkv", "attn_out", "ffn_in", "ffn_down")
SERVED_KERNELS = ("mx_gemm_packed", "mx_flash_prefill",
                  "mx_flash_decode_paged")


def log(msg: str) -> None:
    print(msg, flush=True)


def check_dispatch(registry) -> None:
    """(b): the fused path ran for every quantized role, no quantized role
    fell back to the reference path, and the served kernels dispatched."""
    fused, ref, calls = set(), set(), {}
    for m in registry:
        if m.name == "quant_dispatch_total" and m.value > 0:
            (fused if m.labels["path"] == "fused" else ref).add(
                m.labels["role"])
        elif m.name == "kernel_dispatch_calls_total":
            op = m.labels["op"]
            calls[op] = calls.get(op, 0) + m.value
    missing = set(QUANT_ROLES) - fused
    fell_back = ref - {"head"}
    if missing or fell_back:
        raise AssertionError(
            f"fused dispatch incomplete: roles without a fused call "
            f"{sorted(missing)}, quantized roles on the reference path "
            f"{sorted(fell_back)}")
    absent = [k for k in SERVED_KERNELS if calls.get(k, 0) <= 0]
    if absent:
        raise AssertionError(f"served kernels never dispatched: {absent}")
    log(f"quant dispatch: fused roles {sorted(fused)}, reference roles "
        f"{sorted(ref)}")
    log("kernel dispatch calls (per compiled call site): "
        + ", ".join(f"{k}={int(v)}" for k, v in sorted(calls.items())))


def make_requests(cfg, n, lo, hi, max_new, seed):
    import numpy as np

    from repro.serving.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, int(s))
                    .astype(np.int32), max_new=max_new)
            for s in rng.integers(lo, hi + 1, n)]


def serve(eng, reqs) -> int:
    """(d): serve ``reqs``; every one must end FINISHED with its full
    budget of tokens, and the page allocator must be consistent."""
    from repro.serving.policy import RequestState
    eng.generate(reqs)
    bad = [(r.request_id, r.state.value, r.error) for r in reqs
           if r.state is not RequestState.FINISHED]
    if bad:
        raise AssertionError(f"requests did not finish: {bad}")
    short = [(r.request_id, len(r.out)) for r in reqs
             if len(r.out) != r.max_new]
    if short:
        raise AssertionError(f"requests ended short of max_new: {short}")
    acct = eng._alloc.check()
    if acct["in_use"]:
        raise AssertionError(f"pages still referenced after the wave: "
                             f"{acct}")
    return sum(len(r.out) for r in reqs)


def prefill_logits(params, cfg, qm, prompts, page_size, kv_fmt):
    """Last-position logits of a chunked paged prefill of ``prompts`` into
    a fresh MX page pool: ``attn_chunk``-wide chunks, every chunk after the
    first attending the pages the earlier ones wrote."""
    import jax
    import numpy as np

    from repro.core.quantize import KVCacheQuant
    from repro.models import api
    B, C = len(prompts), cfg.attn_chunk
    lens = np.asarray([len(p) for p in prompts])
    n_chunks = -(-int(lens.max()) // C)
    toks = np.zeros((B, n_chunks * C), np.int32)
    for i, p in enumerate(prompts):
        toks[i, :len(p)] = p
    maxp = -(-n_chunks * C // page_size)
    tables = 1 + np.arange(B * maxp, dtype=np.int32).reshape(B, maxp)
    cache = api.init_cache_paged(cfg, 1 + B * maxp, page_size,
                                 params["embed"].dtype,
                                 kv_quant=KVCacheQuant(kv_fmt))
    step = jax.jit(lambda p, c, bt, t, s, li: api.prefill_chunk_paged(
        p, cfg, c, bt, t, s, li, qm))
    out = np.zeros((B, cfg.vocab_size), np.float32)
    for k in range(n_chunks):
        last = np.clip(lens - 1 - k * C, 0, C - 1).astype(np.int32)
        logits, cache = step(params, cache, tables,
                             toks[:, k * C:(k + 1) * C], np.int32(k * C),
                             last)
        mine = (lens - 1) // C == k            # lanes whose last token is here
        out[mine] = np.asarray(logits, np.float32)[mine]
    return out


def check_logits(eng, prompts) -> float:
    """(c): fused vs reference prefill logits on the same weights.

    Also prints the rounding floor: the reference against itself with
    each embedding entry moved by a relative 1e-6 (seeded noise). MX
    activation quantization turns any rounding difference into whole
    code steps, so this is how far two correct paths can drift apart at
    this depth."""
    import jax
    import numpy as np
    kv = eng.kv_quant.fmt
    ref_qm = eng.qm.with_backend("ref")
    fused = prefill_logits(eng.params, eng.cfg, eng.qm, prompts,
                           eng.page_size, kv)
    ref = prefill_logits(eng.params, eng.cfg, ref_qm, prompts,
                         eng.page_size, kv)
    emb = eng.params["embed"]
    noise = jax.random.normal(jax.random.PRNGKey(1), emb.shape, emb.dtype)
    nudged = dict(eng.params, embed=emb * (1 + 1e-6 * noise))
    ref2 = prefill_logits(nudged, eng.cfg, ref_qm, prompts, eng.page_size,
                          kv)
    if not all(np.isfinite(a).all() for a in (fused, ref, ref2)):
        raise AssertionError("non-finite prefill logits")

    def rel_err(a):
        return float(np.linalg.norm(a - ref) / np.linalg.norm(ref))

    rel = rel_err(fused)
    log(f"fused vs ref prefill logits: rel L2 error {rel:.6e} "
        f"(bound {LOGIT_REL_BOUND:.2e}), shape {fused.shape}; rounding "
        f"floor (ref vs ref, embed nudged by 1e-6) {rel_err(ref2):.6e}")
    if not rel <= LOGIT_REL_BOUND:
        raise AssertionError(f"fused logits off the reference: {rel:.3e} "
                             f"> {LOGIT_REL_BOUND:.1e}")
    return rel


def smoke(cfg, out_dir, *, seed=0, n_requests=4, prompt_lo=200,
          prompt_hi=320, max_new=32) -> dict:
    """The checked served path for ``cfg``; raises on any failure."""
    import jax
    import jax.numpy as jnp

    from repro.core import ptq
    from repro.data import synthetic
    from repro.kernels import ops
    from repro.models import api
    from repro.obs import MetricsRegistry
    from repro.serving.engine import Engine

    out_dir = pathlib.Path(out_dir)
    t0 = time.perf_counter()
    params = api.init(jax.random.PRNGKey(seed), cfg)
    src = synthetic.make_source(cfg, 8, 64, seed)
    calib = [{k: jnp.asarray(v) for k, v in src.batch(i).items()}
             for i in range(3)]
    res = ptq.apply_method("rtn", params, cfg, calib, fmt="mxfp4")
    art = res.export(cfg, out_dir / "artifact")
    del params, res, calib
    log(f"set-up: init + RTN mxfp4 + export -> {art} in "
        f"{time.perf_counter() - t0:.1f} s")

    registry = MetricsRegistry()
    ops.instrument(registry)
    t0 = time.perf_counter()
    eng = Engine.from_artifact(
        art, batch_size=n_requests, max_len=prompt_hi + max_new + 16,
        backend="fused", scheduler="continuous", kv_layout="paged",
        kv_cache="mxfp8", metrics=registry)
    log(f"set-up: artifact loaded in {time.perf_counter() - t0:.1f} s "
        f"(page size {eng.page_size}, {eng._alloc.n_pages} pages)")

    t0 = time.perf_counter()
    warm = serve(eng, make_requests(cfg, n_requests, prompt_lo, prompt_hi,
                                    max_new, seed + 1))
    log(f"set-up: first wave (compiles included) served {warm} tokens in "
        f"{time.perf_counter() - t0:.1f} s")
    reqs = make_requests(cfg, n_requests, prompt_lo, prompt_hi, max_new,
                         seed + 2)
    t0 = time.perf_counter()
    toks = serve(eng, reqs)
    dt = time.perf_counter() - t0
    prompt_toks = sum(len(r.prompt) for r in reqs)
    log(f"served {len(reqs)} requests: {prompt_toks} prompt tokens, "
        f"{toks} generated tokens in {dt:.2f} s (compiled steps)")
    st = eng.stats()
    log(f"engine: {st['prefill_chunk_steps']} prefill chunk steps, "
        f"{st['decode_steps']} decode steps, compiles "
        f"prefill_chunk={st['prefill_chunk_compiles']} "
        f"decode={st['decode_compiles']}")
    check_dispatch(registry)
    ops.uninstrument()

    rel = check_logits(eng, [r.prompt for r in reqs])
    return {"tokens": toks, "prompt_tokens": prompt_toks, "logit_rel": rel}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    ap.add_argument("--out", default=str(ROOT / "chip_smoke_out"),
                    help="directory for the exported artifact")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — JAX's first device is "
              f"{dev.platform!r} ({dev.device_kind}); this smoke test runs "
              f"only on a TPU", file=sys.stderr)
        return 2

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {enable_compile_cache()}")
    cfg = configs.get(ARCH)
    log(f"model: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab_size}")
    smoke(cfg, args.out, seed=args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
