"""The trace reduction, on the head of a trace recorded on the chip.

``data/trace_head.json.gz`` is the first two seconds of a ``--trace 1``
window of ``mistral7b.chat`` on one TPU v5e (one prefill chunk and two
decode steps): what ``trace.raw`` read from the window's trace, cut by
``trace.trim`` from the first program execution on.
"""
import gzip
import json

import pytest

from chipbench import trace as tr
from conftest import HERE


@pytest.fixture(scope="module")
def data():
    with gzip.open(HERE / "data" / "trace_head.json.gz", "rt") as f:
        return json.load(f)


def test_programs_and_kernels_are_found(data):
    v = tr.view(data)
    phases = {m[3] for m in v.modules}
    assert "decode" in phases
    kernels = {o.kernel for o in v.ops if o.kernel}
    assert {"mx_gemm_packed", "mx_flash_decode_paged"} <= kernels
    # every kernel op lies inside a program execution of its phase
    assert all(o.phase for o in v.ops if o.kernel)


def test_decode_steps_hold_seven_gemms_a_layer(data):
    v = tr.view(data)
    n_layers = 32                                  # Mistral-7B
    gemm = sorted(o.start for o in v.ops
                  if o.kernel == "mx_gemm_packed" and o.phase == "decode")
    whole = [(s, e) for _n, s, e, ph in v.modules if ph == "decode"
             and sum(1 for g in gemm if s <= g < e) > 0]
    assert whole
    counts = {sum(1 for g in gemm if s <= g < e) for s, e in whole}
    assert 7 * n_layers in counts


def test_busy_is_a_union_and_idle_gaps_are_named(data):
    v = tr.view(data)
    busy = tr.busy_ns((o.start, o.end) for o in v.ops)
    assert 0 < busy <= v.t1 - v.t0
    assert busy <= sum(o.end - o.start for o in v.ops)
    gaps = tr.idle_gaps(v)
    assert gaps and all(g[0] in tr.HOST_SPANS + ("none",) and g[1] > 0
                        for g in gaps)
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)


def test_busy_ns_hand_count():
    assert tr.busy_ns([(0, 10), (5, 12), (20, 25), (24, 26)]) == 18
    assert tr.busy_ns([]) == 0


def test_trim_keeps_what_starts_inside():
    d = {"ops": [["a", 0, 5, {}], ["b", 10, 20, {}]], "modules": [],
         "host": [["step", 3, 30]]}
    t = tr.trim(d, 2, 15)
    assert [o[0] for o in t["ops"]] == ["b"] and t["host"] == [["step", 3, 30]]
