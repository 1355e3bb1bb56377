"""Kernel-dispatch execution backend: fused (packed-native Pallas) path
must match the reference path across formats, roles, T3, and weight
stackings; ineligible calls must fall back cleanly; the fused lowering
must never materialize a dense fp weight; artifact serving with
backend='fused' must reproduce reference-engine logits."""
import dataclasses

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ArchConfig
from repro.core import mx as mxlib
from repro.core import ptq
from repro.core.quantize import QuantMode, qeinsum, qlinear
from repro.data import synthetic
from repro.kernels.packing import PackedWeight
from repro.models import api
from repro.serving.engine import Engine, Request

FMTS = ["mxfp4", "mxint4"]


def _packed(shape, fmt="mxfp4", seed=0, scale=0.3):
    """A PackedWeight whose dense values sit exactly on the MX grid."""
    rng = np.random.default_rng(seed)
    w = jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)
    cfg = mxlib.MXConfig(fmt=fmt, block_size=32)
    wq = jnp.swapaxes(mxlib.quantize(jnp.swapaxes(w, -1, -2), cfg,
                                     ste=False), -1, -2)
    return PackedWeight.from_dense(wq, fmt), wq


def _modes(fmt, t3):
    qm = QuantMode.mxfp4(t3=t3) if fmt == "mxfp4" else \
        QuantMode.mxint4(t3=t3)
    return qm, qm.with_backend("fused")


# ---------------------------------------------------------------------------
# qlinear / qeinsum parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("t3", [False, True])
@pytest.mark.parametrize("role", ["ffn_in", "ffn_down", "qkv"])
def test_qlinear_fused_matches_ref_2d(fmt, t3, role):
    pw, _ = _packed((64, 48), fmt)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((2, 5, 64)),
                    jnp.float32)
    b = jnp.asarray(np.random.default_rng(2).standard_normal(48),
                    jnp.float32)
    qm_ref, qm_fused = _modes(fmt, t3)
    yr = qlinear(x, pw, b, qm_ref, role)
    yf = qlinear(x, pw, b, qm_fused, role)
    assert yf.dtype == yr.dtype and yf.shape == yr.shape
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("fmt", FMTS)
def test_qlinear_fused_matches_ref_stacked(fmt):
    """Layer-stacked (L, K, N) weights: leading axis becomes a vmap axis."""
    pw, _ = _packed((3, 64, 32), fmt, seed=3)
    x = jnp.asarray(np.random.default_rng(4).standard_normal((3, 6, 64)),
                    jnp.float32)
    qm_ref, qm_fused = _modes(fmt, t3=False)
    yr = qlinear(x, pw, None, qm_ref, "ffn_in")
    yf = qlinear(x, pw, None, qm_fused, "ffn_in")
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                               atol=1e-4, rtol=1e-5)


@pytest.mark.parametrize("fmt", FMTS)
@pytest.mark.parametrize("t3", [False, True])
@pytest.mark.parametrize("spec", ["gecd,edf->gecf", "gecf,efd->gecd"])
def test_qeinsum_expert_fused_matches_ref(fmt, t3, spec):
    role = "ffn_down" if t3 else "ffn_in"
    pw, _ = _packed((3, 64, 32), fmt, seed=5)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 3, 4, 64)),
                    jnp.float32)
    qm_ref, qm_fused = _modes(fmt, t3)
    yr = qeinsum(spec, x, pw, qm_ref, role)
    yf = qeinsum(spec, x, pw, qm_fused, role)
    assert yf.shape == yr.shape
    np.testing.assert_allclose(np.asarray(yf), np.asarray(yr),
                               atol=1e-4, rtol=1e-5)


def test_qlinear_fused_bf16_activation():
    pw, _ = _packed((64, 32))
    x = jnp.asarray(np.random.default_rng(7).standard_normal((4, 64)),
                    jnp.bfloat16)
    qm_ref, qm_fused = _modes("mxfp4", t3=False)
    yr = qlinear(x, pw, None, qm_ref, "ffn_in")
    yf = qlinear(x, pw, None, qm_fused, "ffn_in")
    assert yf.dtype == yr.dtype
    np.testing.assert_allclose(np.asarray(yf, np.float32),
                               np.asarray(yr, np.float32),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# Fallbacks: ineligible calls take the reference path, identically
# ---------------------------------------------------------------------------

def test_fused_falls_back_cleanly():
    rng = np.random.default_rng(8)
    qm = QuantMode.mxfp4(backend="fused")
    # dense weight -> reference path
    w = jnp.asarray(rng.standard_normal((64, 32)) * 0.3, jnp.float32)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    np.testing.assert_array_equal(
        np.asarray(qlinear(x, w, None, qm, "ffn_in")),
        np.asarray(qlinear(x, w, None, qm.with_backend("ref"), "ffn_in")))
    # head stays fp unless quantize_head
    pw, wq = _packed((64, 32))
    np.testing.assert_array_equal(
        np.asarray(qlinear(x, pw, None, qm, "head")),
        np.asarray(x @ wq))
    # act fmt mismatching the packed fmt -> reference path (no crash)
    pw_int, _ = _packed((64, 32), "mxint4")
    y = qlinear(x, pw_int, None, qm, "ffn_in")
    yr = qlinear(x, pw_int, None, qm.with_backend("ref"), "ffn_in")
    np.testing.assert_array_equal(np.asarray(y), np.asarray(yr))
    # odd activation batch sizes still kernel-eligible (block shrink), and
    # rank-mismatched stacked shapes fall back instead of erroring
    x3 = jnp.asarray(rng.standard_normal((2, 7, 64)), jnp.float32)
    pw3, _ = _packed((3, 64, 32))
    yr = qlinear(x3[:, :, :], pw, None, qm.with_backend("ref"), "ffn_in")
    np.testing.assert_allclose(
        np.asarray(qlinear(x3, pw, None, qm, "ffn_in")), np.asarray(yr),
        atol=1e-4, rtol=1e-5)
    with pytest.raises(Exception):
        # ref batched-matmul can't broadcast (2,7,64)@(3,64,32) either;
        # the dispatcher must not invent semantics the ref path lacks
        qlinear(x3, pw3, None, qm, "ffn_in")


def test_qeinsum_fused_rejects_rank_mismatch_like_ref():
    """A rank-mismatched activation must error under both backends, not
    silently compute under 'fused'."""
    pw, _ = _packed((3, 64, 32))
    bad = jnp.zeros((2, 3, 4, 7, 64), jnp.float32)  # spec demands rank 4
    for backend in ("ref", "fused"):
        with pytest.raises(Exception):
            qeinsum("gecd,edf->gecf", bad, pw,
                    QuantMode.mxfp4(backend=backend), "ffn_in")


def test_nvfp4_never_fuses():
    """NVFP4 (block 16, fp8 scales) has no packed layout — backend='fused'
    must leave it on the reference path."""
    qm = dataclasses.replace(QuantMode.nvfp4(t3=False), backend="fused")
    pw, _ = _packed((64, 32))
    x = jnp.asarray(np.random.default_rng(9).standard_normal((4, 64)),
                    jnp.float32)
    yr = qlinear(x, pw, None, dataclasses.replace(qm, backend="ref"),
                 "ffn_in")
    np.testing.assert_array_equal(
        np.asarray(qlinear(x, pw, None, qm, "ffn_in")), np.asarray(yr))


def test_unknown_backend_rejected():
    with pytest.raises(ValueError, match="backend"):
        QuantMode.mxfp4(backend="cuda")


def test_skip_requant_matches_explicit_requant():
    """The reference path's decode->encode->decode skip for on-grid
    PackedWeights is bit-exact (MX pow2 quantization is idempotent)."""
    for fmt in FMTS:
        pw, wq = _packed((96, 32), fmt, seed=10)
        cfg = mxlib.MXConfig(fmt=fmt, block_size=32)
        requant = jnp.swapaxes(
            mxlib.quantize(jnp.swapaxes(pw.to_dense(), -1, -2), cfg,
                           ste=False), -1, -2)
        np.testing.assert_array_equal(np.asarray(requant),
                                      np.asarray(pw.to_dense()))
        qm = QuantMode.mxfp4() if fmt == "mxfp4" else QuantMode.mxint4()
        x = jnp.asarray(np.random.default_rng(11).standard_normal((4, 96)),
                        jnp.float32)
        np.testing.assert_array_equal(
            np.asarray(qlinear(x, pw, None, qm, "ffn_in")),
            np.asarray(qlinear(x, wq, None, qm, "ffn_in")))


# ---------------------------------------------------------------------------
# Lowering: the fused path must not materialize a dense fp weight
# ---------------------------------------------------------------------------

def _float_avals_of_size(fn, args, size, skip=("pallas_call",)):
    """Collect float intermediates of a given element count from the
    jaxpr of fn(*args), recursing through call primitives but NOT into
    the Pallas kernel body (in-kernel tiles are VMEM-resident by
    construction)."""
    found = []

    def visit(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name in skip:
                continue
            for v in eqn.outvars:
                aval = v.aval
                if (getattr(aval, "size", 0) == size
                        and jnp.issubdtype(aval.dtype, jnp.floating)):
                    found.append((eqn.primitive.name, aval))
            for p in eqn.params.values():
                for sub in (p if isinstance(p, (tuple, list)) else (p,)):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        visit(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        visit(sub)

    visit(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


def test_fused_lowering_has_no_dense_weight():
    K, N, M = 64, 96, 8
    pw, _ = _packed((K, N))
    x = jnp.asarray(np.random.default_rng(12).standard_normal((M, K)),
                    jnp.float32)

    def run(backend):
        qm = QuantMode.mxfp4(backend=backend)
        return lambda xx, c, s: qlinear(
            xx, PackedWeight(c, s, "mxfp4", "float32"), None, qm, "ffn_in")

    args = (x, pw.codes_packed, pw.scales_e8m0)
    dense_in_ref = _float_avals_of_size(run("ref"), args, K * N)
    assert dense_in_ref, "detector lost its reference signal"
    dense_in_fused = _float_avals_of_size(run("fused"), args, K * N)
    assert not dense_in_fused, (
        f"fused path materializes dense-weight-sized float buffers: "
        f"{dense_in_fused}")


# ---------------------------------------------------------------------------
# Engine / artifact integration
# ---------------------------------------------------------------------------

def _artifact(tmp_path, cfg, name, seed=0):
    from repro.artifacts import export_artifact
    params = api.init(jax.random.PRNGKey(seed), cfg)
    src = synthetic.make_source(cfg, 4, 32, 0)
    calib = [{k: jnp.asarray(v) for k, v in src.batch(i).items()}
             for i in range(2)]
    res = ptq.apply_method("rtn", params, cfg, calib, fmt="mxfp4")
    out = tmp_path / name
    export_artifact(res, cfg, out)
    toks = jnp.asarray(src.batch(50)["inputs"])[:, :16]
    return out, toks


def test_fused_forward_matches_ref_dense_artifact(tmp_path):
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     attn_chunk=64)
    out, toks = _artifact(tmp_path, cfg, "dense")
    from repro.artifacts import load_artifact
    params, cfg2, qm = load_artifact(out)
    assert qm.backend == "ref"
    ref = np.asarray(api.forward(params, cfg2, toks, qm))
    params_f, _, qm_f = load_artifact(out, backend="fused")
    assert qm_f.backend == "fused"
    got = np.asarray(api.forward(params_f, cfg2, toks, qm_f))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_fused_forward_matches_ref_moe_artifact(tmp_path):
    cfg = ArchConfig(name="tm", family="moe", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                     n_experts=4, top_k=2, n_shared_experts=1,
                     attn_chunk=64)
    out, toks = _artifact(tmp_path, cfg, "moe", seed=1)
    from repro.artifacts import load_artifact
    params, cfg2, qm = load_artifact(out)
    ref = np.asarray(api.forward(params, cfg2, toks, qm))
    params_f, _, qm_f = load_artifact(out, backend="fused")
    got = np.asarray(api.forward(params_f, cfg2, toks, qm_f))
    np.testing.assert_allclose(got, ref, atol=1e-4, rtol=1e-4)


def test_engine_from_artifact_fused_matches_ref(tmp_path):
    cfg = ArchConfig(name="t", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     attn_chunk=16)
    out, _ = _artifact(tmp_path, cfg, "eng")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, 16).astype(np.int32)
               for _ in range(2)]
    ref_eng = Engine.from_artifact(out, batch_size=2, max_len=64)
    fused_eng = Engine.from_artifact(out, batch_size=2, max_len=64,
                                     backend="fused")
    assert fused_eng.qm.backend == "fused"
    ref = ref_eng.generate([Request(prompt=p, max_new=6) for p in prompts])
    got = fused_eng.generate([Request(prompt=p, max_new=6) for p in prompts])
    for r, g in zip(ref, got):
        np.testing.assert_array_equal(g.out, r.out)


def test_wave_bucketing_counts_compiles():
    """Distinct prompt lengths inside one chunk bucket must reuse one
    prefill compile; the count is surfaced in throughput() output."""
    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     attn_chunk=16)
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, QuantMode.off(), batch_size=2, max_len=64)
    rng = np.random.default_rng(0)

    def wave(lengths):
        eng.generate([Request(prompt=rng.integers(
            0, 128, s).astype(np.int32), max_new=2) for s in lengths])

    wave([9, 12])    # bucket 16
    wave([13, 15])   # bucket 16 again -> no new compile
    assert eng.prefill_compiles == 1
    wave([17, 20])   # bucket 32
    assert eng.prefill_compiles == 2
    stats = eng.throughput(n_requests=2, prompt_len=8, max_new=2)
    assert stats["prefill_compiles"] == eng.prefill_compiles
    assert stats["backend"] == "ref"


def test_wave_bucketing_respects_cache_budget():
    """When rounding up would overflow max_len - max_new, the raw length
    is kept (old behavior) so decode never writes past the cache."""
    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     attn_chunk=64)
    params = api.init(jax.random.PRNGKey(0), cfg)
    eng = Engine(params, cfg, QuantMode.off(), batch_size=1, max_len=64)
    assert eng._bucket_len(12, max_new=6) == 12   # 64 + 6 > 64 -> raw
    assert eng._bucket_len(12, max_new=0) == 64   # fits -> bucket
    rng = np.random.default_rng(1)
    reqs = [Request(prompt=rng.integers(0, 128, 12).astype(np.int32),
                    max_new=6)]
    done = eng.generate(reqs)
    assert len(done[0].out) == 6


def test_bucketing_opt_out_preserves_unpadded_waves():
    cfg = ArchConfig(name="tiny", family="dense", n_layers=2, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=128,
                     attn_chunk=16)
    params = api.init(jax.random.PRNGKey(0), cfg)
    on = Engine(params, cfg, QuantMode.off(), batch_size=1, max_len=64)
    off = Engine(params, cfg, QuantMode.off(), batch_size=1, max_len=64,
                 bucket_prompts=False)
    assert on._bucket_len(9, max_new=2) == 16
    assert off._bucket_len(9, max_new=2) == 9
    # unbucketed single-prompt wave matches teacher forcing even for a
    # length off the chunk grid (no attended pad tokens)
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, 128, 9).astype(np.int32)
    done = off.generate([Request(prompt=prompt, max_new=4)])
    seq = list(prompt)
    for tok in done[0].out:
        logits = api.forward(params, cfg, jnp.asarray([seq], jnp.int32))
        assert int(jnp.argmax(logits[0, -1])) == int(tok)
        seq.append(int(tok))
