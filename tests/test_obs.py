"""Observability subsystem: histogram quantiles vs numpy, registry
label/type discipline, Chrome trace-event schema validation, and the
serving-engine integration (request-lifecycle spans populate the trace;
TTFT/TPOT histograms populate ``stats()`` without disturbing its
pre-existing keys)."""
import json

import numpy as np
import pytest

from repro.obs import (Counter, Gauge, Histogram, MetricsRegistry,
                       Tracer, validate_trace)
from repro.obs.metrics import DEFAULT_BUCKETS, log_buckets


# ---------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------

class TestMetrics:
    def test_counter_monotone(self):
        c = Counter("requests_total")
        c.inc()
        c.inc(3)
        assert c.value == 4
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_moves_both_ways(self):
        g = Gauge("queue_depth")
        g.set(5)
        g.dec(2)
        g.inc()
        assert g.value == 4

    def test_histogram_quantiles_match_numpy(self):
        # up to max_samples the reservoir holds every observation and
        # quantile() is np.percentile bit-for-bit
        rng = np.random.default_rng(0)
        xs = rng.lognormal(mean=-3.0, sigma=1.5, size=5000)
        h = Histogram("lat", unit="s")
        for x in xs:
            h.observe(x)
        assert h.exact
        for q in (0.5, 0.9, 0.99, 0.999):
            assert h.quantile(q) == float(np.percentile(xs, 100.0 * q))
        d = h.data()
        assert d["count"] == 5000 and d["exact"]
        assert d["p50"] == float(np.percentile(xs, 50))
        assert d["min"] == xs.min() and d["max"] == xs.max()

    def test_histogram_reservoir_degrades_gracefully(self):
        rng = np.random.default_rng(1)
        xs = rng.lognormal(mean=0.0, sigma=1.0, size=20000)
        h = Histogram("lat", max_samples=512)
        for x in xs:
            h.observe(x)
        assert not h.exact
        assert h.count == 20000
        # uniform reservoir: quantile estimates stay in the ballpark
        for q in (0.5, 0.9):
            true = float(np.percentile(xs, 100.0 * q))
            assert abs(h.quantile(q) - true) / true < 0.25
        # exact aggregates are unaffected by the reservoir cap
        assert h.sum == pytest.approx(xs.sum())
        assert h.min == xs.min() and h.max == xs.max()

    def test_histogram_buckets_partition_observations(self):
        h = Histogram("lat", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 0.5, 5.0, 50.0):
            h.observe(v)
        assert h._counts == [1, 2, 1, 1]          # last = +inf overflow
        assert sum(h._counts) == h.count

    def test_empty_histogram_quantile_nan(self):
        assert np.isnan(Histogram("lat").quantile(0.5))

    def test_log_buckets_validation(self):
        bs = log_buckets(1e-3, 1e3, per_decade=2)
        assert bs == sorted(bs) and bs[0] == 1e-3
        with pytest.raises(ValueError):
            log_buckets(0.0, 1.0)
        with pytest.raises(ValueError):
            Histogram("bad", buckets=(1.0, 0.5))
        assert list(DEFAULT_BUCKETS) == sorted(DEFAULT_BUCKETS)

    def test_registry_identity_per_name_and_labels(self):
        reg = MetricsRegistry()
        a = reg.counter("ops_total", {"op": "gemm"})
        b = reg.counter("ops_total", {"op": "gemm"})
        c = reg.counter("ops_total", {"op": "attn"})
        assert a is b and a is not c
        a.inc()
        assert reg.get("ops_total", {"op": "gemm"}).value == 1
        assert reg.get("ops_total", {"op": "attn"}).value == 0
        assert reg.get("nope") is None
        assert len(reg) == 2

    def test_registry_type_collision_raises(self):
        reg = MetricsRegistry()
        reg.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")

    def test_registry_label_keyset_collision_raises(self):
        # same name with a different label *keyset* is a collision;
        # different label *values* are just new series
        reg = MetricsRegistry()
        reg.counter("ops_total", {"op": "gemm"})
        with pytest.raises(ValueError, match="label keys"):
            reg.counter("ops_total", {"path": "fused"})
        reg.counter("ops_total", {"op": "other"})     # fine

    def test_snapshot_and_renders(self):
        reg = MetricsRegistry()
        reg.counter("reqs_total", help="requests").inc(2)
        reg.gauge("depth").set(3)
        h = reg.histogram("lat_seconds", unit="s", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        snap = reg.snapshot()
        assert snap["reqs_total"][0]["value"] == 2
        assert snap["lat_seconds"][0]["count"] == 2
        json.loads(reg.render_json())                 # JSON-safe
        text = reg.render_prometheus()
        assert "# TYPE reqs_total counter" in text
        assert "reqs_total 2" in text
        # histogram buckets are cumulative, terminated by +Inf == count
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text


# ---------------------------------------------------------------------
# tracing
# ---------------------------------------------------------------------

class TestTracing:
    def test_span_roundtrip_and_validation(self, tmp_path):
        tr = Tracer()
        with tr.span("outer", track="req-0", kind="request"):
            with tr.span("inner", track="req-0"):
                pass
            tr.instant("first_token", track="req-0")
        with tr.span("thread_local_span"):
            pass
        path = tmp_path / "trace.json"
        tr.export(path)
        doc = json.loads(path.read_text())
        assert doc["traceEvents"]
        evs = validate_trace(str(path))
        names = {e["name"] for e in evs}
        assert {"outer", "inner", "first_token",
                "thread_local_span"} <= names
        outer = next(e for e in evs if e["name"] == "outer")
        inner = next(e for e in evs if e["name"] == "inner")
        assert outer["ph"] == "X" and outer["dur"] >= inner["dur"]
        assert outer["args"] == {"kind": "request"}
        # metadata rows name every track
        meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} >= {"req-0"}

    def test_required_fields_enforced(self):
        with pytest.raises(ValueError, match="missing"):
            validate_trace([{"ph": "X", "name": "a"}])
        with pytest.raises(ValueError, match="dur"):
            validate_trace([{"ph": "X", "name": "a", "ts": 0.0,
                             "pid": 0, "tid": 0}])
        with pytest.raises(ValueError, match="bad ts"):
            validate_trace([{"ph": "i", "name": "a", "ts": -1.0,
                             "pid": 0, "tid": 0}])
        with pytest.raises(ValueError, match="ph"):
            validate_trace([{"name": "a"}])

    def test_stack_discipline(self):
        def ev(name, ts, dur, tid=0):
            return {"ph": "X", "name": name, "ts": ts, "dur": dur,
                    "pid": 0, "tid": tid}
        # nesting and adjacency are fine
        validate_trace([ev("a", 0, 10), ev("b", 2, 3), ev("c", 5, 5)])
        # partial overlap on one track is not
        with pytest.raises(ValueError, match="overlaps"):
            validate_trace([ev("a", 0, 10), ev("b", 5, 10)])
        # the same interval on another track is fine
        validate_trace([ev("a", 0, 10), ev("b", 5, 10, tid=1)])

    def test_retroactive_complete_spans(self):
        import time
        tr = Tracer()
        t0 = time.perf_counter()
        with tr.span("child", track="req-1"):
            pass
        tr.complete("parent", t0, time.perf_counter(), track="req-1")
        validate_trace(tr.events())

    def test_next_index_per_key(self):
        tr = Tracer()
        assert [tr.next_index("req") for _ in range(3)] == [0, 1, 2]
        assert tr.next_index("other") == 0


# ---------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------

class TestEngineIntegration:
    @pytest.fixture(scope="class")
    def served(self):
        import jax
        from repro.configs.base import ArchConfig
        from repro.core.quantize import QuantMode
        from repro.models import api
        from repro.serving.engine import Engine, Request

        cfg = ArchConfig(name="obs-tiny", family="dense", n_layers=2,
                         d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                         vocab_size=128, attn_chunk=16)
        params = api.init(jax.random.PRNGKey(0), cfg)
        tracer = Tracer()
        eng = Engine(params, cfg, QuantMode.off(), batch_size=2,
                     max_len=64, scheduler="continuous", tracer=tracer)
        rng = np.random.default_rng(0)
        reqs = [Request(prompt=rng.integers(0, cfg.vocab_size, 4 + 3 * i)
                        .astype(np.int32), max_new=3 + i)
                for i in range(4)]
        done = eng.generate(reqs)
        return eng, tracer, done

    def test_stats_keeps_legacy_keys_and_adds_latency(self, served):
        eng, _, done = served
        st = eng.stats()
        for key in ("scheduler", "admitted", "decode_steps", "slot_steps",
                    "useful_decode_tokens", "decode_utilization",
                    "prefill_chunk_steps", "prefill_compiles",
                    "prefill_chunk_compiles", "decode_compiles",
                    "prefix_hit_tokens", "blocks_in_use",
                    "blocks_evicted", "kv_cache", "admit_seconds"):
            assert key in st, key
        assert st["admitted"] == len(done) == 4
        assert st["admit_seconds"] > 0
        # legacy attribute views stay equal to the registry-backed stats
        assert eng.admitted == st["admitted"]
        assert eng.decode_steps == st["decode_steps"]
        assert eng.useful_decode_tokens == st["useful_decode_tokens"]
        # latency summaries: one TTFT observation per finished request
        assert st["ttft_p50"] is not None and st["ttft_p50"] >= 0
        assert st["ttft_p99"] >= st["ttft_p50"]
        h = eng.metrics.get("serving_ttft_seconds")
        assert h.count == len(done)
        lat = eng.metrics.get("serving_request_latency_seconds")
        assert lat.count == len(done)
        # windowed view starts equal to cumulative, then resets
        assert st["window"]["admitted"] == st["admitted"]
        eng.reset_stats()
        st2 = eng.stats()
        assert st2["window"]["admitted"] == 0
        assert st2["window"]["admit_seconds"] == 0
        assert st2["admitted"] == st["admitted"]      # cumulative kept

    def test_trace_has_lifecycle_and_step_spans(self, served, tmp_path):
        eng, tracer, done = served
        path = tmp_path / "engine_trace.json"
        tracer.export(path)
        evs = validate_trace(str(path))
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], []).append(e)
        # one request-lifecycle span per request, on its own track
        assert len(by_name["request"]) == len(done)
        assert len({e["tid"] for e in by_name["request"]}) == len(done)
        assert len(by_name["first_token"]) == len(done)
        # engine-step machinery spans
        assert by_name["engine_step"]
        assert by_name["decode_step"]
        assert by_name["prefill_chunk"]
        # compile events are instant markers, distinct from exec spans
        assert all(e["ph"] == "i" for e in by_name["compile:decode"])

    def test_prometheus_export_nonempty(self, served):
        eng, _, _ = served
        text = eng.metrics.render_prometheus()
        assert "serving_requests_admitted_total 4" in text
        assert "serving_ttft_seconds_count 4" in text


# ---------------------------------------------------------------------
# engine spans on the profiler's trace; the K/V write's device scope
# ---------------------------------------------------------------------

def _paged_engine(tracer):
    import jax
    from repro.configs.base import ArchConfig
    from repro.core.quantize import QuantMode
    from repro.models import api
    from repro.serving.engine import Engine

    cfg = ArchConfig(name="obs-paged", family="dense", n_layers=2,
                     d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                     vocab_size=128, attn_chunk=16)
    params = api.init(jax.random.PRNGKey(0), cfg)
    return Engine(params, cfg, QuantMode.off(), batch_size=2, max_len=64,
                  scheduler="continuous", kv_layout="paged", page_size=32,
                  kv_cache="mxfp8", tracer=tracer)


def _paged_requests(n=3):
    from repro.serving.engine import Request
    rng = np.random.default_rng(1)
    return [Request(prompt=rng.integers(0, 128, 5 + 7 * i)
                    .astype(np.int32), max_new=4) for i in range(n)]


def _host_events(trace_dir):
    """Every event of the profile's host planes: name -> [(start, end,
    stats)]."""
    import pathlib

    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))[-1]
    out = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(
                    (ev.start_ns, ev.end_ns, dict(ev.stats)))
    return out


def test_engine_spans_land_on_the_profilers_host_plane(tmp_path):
    import jax
    eng = _paged_engine(Tracer())
    eng.generate(_paged_requests(1))          # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level, opts.host_tracer_level = 0, 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        done = eng.generate(_paged_requests())
    finally:
        jax.profiler.stop_trace()
    assert all(r.state.value == "finished" for r in done)
    host = _host_events(tmp_path)
    for name in ("engine_step", "admit", "admit_prep", "logits_fetch",
                 "sample_first", "decode_prep", "decode_burst",
                 "decode_step", "host_sync", "emit"):
        assert host.get(name), name
    # the spans' args ride along as the annotations' stats
    assert all("slot" in st for _s, _e, st in host["admit_prep"])
    assert all({"steps", "lanes"} <= set(st)
               for _s, _e, st in host["decode_burst"])
    assert all("steps" in st for _s, _e, st in host["emit"])
    # nested as they ran (batched admission preps its lanes before the
    # one admit span that prefills them)
    def inside(child, parent):
        return all(any(ps <= cs and ce <= pe for ps, pe, _ in host[parent])
                   for cs, ce, _ in host[child])
    for child, parent in (("admit_prep", "engine_step"),
                          ("logits_fetch", "admit"),
                          ("sample_first", "admit"),
                          ("admit", "engine_step"),
                          ("decode_step", "decode_burst"),
                          ("emit", "engine_step")):
        assert inside(child, parent), (child, parent)
    # retroactive request spans stay in the JSON export only
    assert "request" not in host and "first_token" not in host


@pytest.mark.parametrize("traced", [False, True])
def test_annotations_only_with_a_tracer(monkeypatch, traced):
    from repro.obs import tracing
    entered = []

    class Counting(tracing.TraceAnnotation):
        def __enter__(self):
            entered.append(1)
            return super().__enter__()
    monkeypatch.setattr(tracing, "TraceAnnotation", Counting)
    eng = _paged_engine(Tracer() if traced else None)
    done = eng.generate(_paged_requests())
    assert all(r.state.value == "finished" for r in done)
    assert bool(entered) == traced


@pytest.mark.parametrize("writer", ["token", "chunk"])
def test_kv_write_scope_names_every_op_of_the_write(writer):
    """The MX encode and the pool scatter both carry ``kv_write`` in
    their op_name metadata, whatever XLA fuses them into."""
    import re

    import jax
    import jax.numpy as jnp
    from repro.kernels.packing import PagedKV
    from repro.models import layers

    pool = PagedKV.zeros((4, 16, 64), "mxfp8")
    if writer == "token":
        new = jnp.ones((2, 1, 64), jnp.float32)
        args = (pool, new, jnp.asarray([1, 2], jnp.int32),
                jnp.asarray([0, 5], jnp.int32))
        fn = layers.kv_write_token_paged
    else:
        new = jnp.ones((2, 8, 64), jnp.float32)
        args = (pool, new, jnp.asarray([[1, 2], [3, 0]], jnp.int32),
                jnp.int32(4))
        fn = layers.kv_write_chunk_paged
    text = jax.jit(fn).lower(*args).compile().as_text()
    # the program's own ops (argument names and the scatters' combiner
    # bodies carry bare op_names)
    names = re.findall(r'op_name="(jit\([^"]*)"', text)
    assert any(n.endswith("scatter") for n in names)
    assert any(n.endswith("floor") for n in names)       # the encode
    assert all("/kv_write/" in n for n in names), names
