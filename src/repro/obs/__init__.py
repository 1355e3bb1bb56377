"""Serving telemetry: metrics registry + request-lifecycle tracing.

Two small primitives that every serving-stack layer
reports through (see ``docs/observability.md``):

:mod:`repro.obs.metrics`
    ``Counter`` / ``Gauge`` / ``Histogram`` behind a labeled-metric
    :class:`MetricsRegistry` with ``snapshot()`` (nested dict) and
    ``render_prometheus()`` (text exposition format) exports.
    Histograms keep fixed log-spaced buckets *and* a bounded sample
    reservoir, so p50/p90/p99 are exact (numpy-identical) until the
    reservoir cap and bucket-interpolated beyond it.

:mod:`repro.obs.tracing`
    ``Tracer.span("decode_step", ...)`` context managers recording
    wall-clock intervals onto per-thread (and per-request) track
    buffers, exported as Chrome ``trace_event`` JSON
    (``Tracer.export(path)``) that opens directly in Perfetto /
    ``chrome://tracing``. Each span is also a
    ``jax.profiler.TraceAnnotation``, so a profiler trace of the same
    run shows it on the host plane beside the device's operations.

The serving engine always carries a registry (counter updates cost the
same as the plain Python attributes they replaced); tracing is opt-in
(``Engine(tracer=...)``) and strictly zero-cost when absent — no spans,
no timestamps, no host syncs are added to the hot loop.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .tracing import Tracer, validate_trace

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "Tracer", "validate_trace"]
