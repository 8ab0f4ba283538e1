"""repro.obs — zero-dependency fleet telemetry.

Structured metrics (``MetricsRegistry``: typed counters / gauges /
histograms with labels, Prometheus-style text exposition), program
spans (``repro.obs.trace``: an always-on bounded ring of closed spans
on ``perf_counter``, each mirrored into a ``jax.profiler`` annotation;
``Tracer`` writes it as JSONL at flush), and a crash flight recorder
(``FlightRecorder``: bounded ring of recent tick records, dumped to ``flight_<tick>.json`` on
exception, non-finite payload rejection, or SLO breach). A
``TelemetrySink`` composes the three behind the single export surface
the runtime, the serving driver, and every benchmark consume.

Everything is host-side Python updated between jitted calls — the
compile-once tick loop stays compile-once with telemetry on, and the
serve soak gates the overhead at ≤5% wall-clock.
"""
from repro.obs.flight import FlightRecorder, load_dump
from repro.obs.metrics import (
    LATENCY_BUCKETS_S,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.sink import TICK_PHASES, TelemetryConfig, TelemetrySink
from repro.obs.trace import Span, SpanRing, Tracer, record, span, spans_between

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "LATENCY_BUCKETS_S",
    "Span", "SpanRing", "Tracer", "record", "span", "spans_between",
    "FlightRecorder", "load_dump",
    "TelemetryConfig", "TelemetrySink", "TICK_PHASES",
]
