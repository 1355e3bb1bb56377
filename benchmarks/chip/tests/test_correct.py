"""``correct`` holds a sound run and fails a broken one.

Each test skips the harness's look for a chip and drives the rest of a
run (``run.run_cell``) at the tiny configuration on the CPU. The faults
are planted in the timed path underneath, in the engine's compiled
decode step, where a serving cell can break: a token altered where it is
produced, and a step that hands back its KV state unchanged. The control
is the reference in the next precision down (bf16): it must fail the
first layer's K/V limit on every seed, as it does on the chip at each
cell's size (PERF.md gives those readings).
"""
import numpy as np
import pytest

import run as runmod
from chipbench import correct
from repro.serving.engine import Engine

SEEDS = (1, 2, 3)


def _run(cell, seed, keep=None):
    return runmod.run_cell(cell, seed, 3.0, False, keep=keep)


def _break_decode(monkeypatch, fault):
    """Wrap every engine's paged decode step with ``fault``."""
    init = Engine.__init__

    def patched(self, *a, **k):
        init(self, *a, **k)
        step = self._decode_paged

        def broken(params, cache, toks, cur, tables, poison):
            nxt, ok, new = step(params, cache, toks, cur, tables, poison)
            return fault(nxt, ok, cache, new, self.cfg.vocab_size)
        self._decode_paged = broken
    monkeypatch.setattr(Engine, "__init__", patched)


def test_sound_run_is_correct(tiny_cell):
    keep = {}
    res = _run(tiny_cell, SEEDS[0], keep)
    assert res["correct"], res["compared"]
    assert keep["gaps"].size >= 30
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("seed", SEEDS)
def test_control_fails_the_limit(tiny_cell, seed):
    """The bf16 control fails ``kv_l0_mismatch`` on every seed, where the
    sound run passes both numbers."""
    keep = {}
    res = _run(tiny_cell, seed, keep)
    assert res["correct"], res["compared"]
    ctrl = correct.kv_mismatch(keep["ref"], tiny_cell.cfg, seed,
                               keep["snap"]["live"], keep["snap"]["kv"],
                               "bfloat16")
    assert ctrl > 3 * tiny_cell.cfg["check"]["max_kv_l0_mismatch"]


def _encode_kv(vals):
    """MXFP8 values (len, kv_dim) -> the pool's (codes, scales) bytes, as
    ``decode_kv`` reads them."""
    from references import dense_gqa as ref
    *lead, n = vals.shape
    xb = vals.reshape(*lead, n // 32, 32).astype(np.float64)
    amax = np.abs(xb).max(axis=-1)
    e = np.where(amax > 0, np.floor(np.log2(np.where(amax > 0, amax, 1)))
                 - ref.R_MAX["fp8"], 0).astype(np.int64)
    mag = np.abs(xb) / np.exp2(e)[..., None]
    idx = np.searchsorted(ref.FP8, mag)
    assert np.allclose(ref.FP8[idx], mag)
    center = len(ref.FP8) - 1
    codes = center + np.sign(xb).astype(np.int64) * idx
    return codes.reshape(*lead, n).astype(np.uint8), (e + 127).astype(
        np.uint8)


@pytest.mark.parametrize("seed", SEEDS)
def test_control_in_the_programs_place_is_not_correct(tiny_cell, seed,
                                                      monkeypatch):
    """The bf16 control put where the timed path writes its first-layer
    K/V: ``run_cell``'s own comparison reports ``correct`` false."""
    from chipbench import spec
    fam = spec.load_module("families", tiny_cell.cfg["family"])
    ref = spec.load_module("references", tiny_cell.cfg["reference"])
    snapshot = fam.snapshot_kv

    def control(eng, reqs):
        fills = [s[0] for s in snapshot(eng, reqs)]
        seqs = [np.concatenate([r.prompt, np.asarray(r._gen, np.int32)])
                [:f] for r, f in zip(reqs, fills)]
        out = []
        for f, (k, v) in zip(fills, ref.kv_layer0(
                tiny_cell.cfg, seed, seqs, "bfloat16")):
            out.append((f,) + _encode_kv(k) + _encode_kv(v))
        return out
    monkeypatch.setattr(fam, "snapshot_kv", control)
    res = _run(tiny_cell, seed)
    assert not res["correct"]
    kvm, lim = res["compared"]["kv_l0_mismatch"]
    assert kvm > 3 * lim


def test_token_altered_where_produced(tiny_cell, monkeypatch):
    _break_decode(monkeypatch, lambda nxt, ok, cache, new, v: (
        (nxt + 1) % v, ok, new))
    res = _run(tiny_cell, SEEDS[1])
    assert not res["correct"]
    assert res["compared"]["logit_gap"][0] > \
        tiny_cell.cfg["check"]["max_logit_gap"]


def test_step_returns_its_state_unchanged(tiny_cell, monkeypatch):
    _break_decode(monkeypatch, lambda nxt, ok, cache, new, v: (
        nxt, ok, cache))
    res = _run(tiny_cell, SEEDS[2])
    assert not res["correct"]
    assert res["compared"]["logit_gap"][0] > \
        tiny_cell.cfg["check"]["max_logit_gap"]


def test_gaps_read_the_reference_best():
    lg = [np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]])]
    np.testing.assert_allclose(correct.gaps(lg, [np.array([1, 1])]),
                               [0.0, 2.5])
