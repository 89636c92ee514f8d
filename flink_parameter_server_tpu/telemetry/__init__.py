"""Unified telemetry plane (docs/observability.md).

One registry, one tracer, one live endpoint, one end-of-run report —
the seam every subsystem (train / serving / ingest / recovery) measures
through, and the seam every later perf PR is judged through:

  * :mod:`.registry` — typed instruments (Counter, Gauge, Histogram)
    with ``component=`` labels; process-wide default via
    :func:`get_registry`; JSON-lines ``emit`` with shared ts/run_id.
  * :mod:`.spans` — nestable wall-clock spans, ring-buffered, Chrome
    trace-event export; the HOST-side complement of
    ``training/tracing.py``'s device-side ``jax.named_scope``.
  * :mod:`.compile_ledger` — set-up on the program's books: JAX's own
    monitoring events (trace, lowering, compile or cache load) counted
    and recorded by program, a recompile inside a warm run named, and
    ``setup_span`` for set-up's own work.
  * :mod:`.exporter` — Prometheus-text rendering + the TCP
    ``/metrics`` / ``/healthz`` endpoint (live during training).
  * :mod:`.report` — ``results/<platform>/run_report.{md,json}``.
  * :mod:`.distributed` — cross-process trace propagation
    (``t=<trace>:<span>`` wire tokens) + the clock-aligning
    :class:`TraceCollector` that merges per-process rings into one
    Chrome/Perfetto trace.
  * :mod:`.hotkeys` — count-min + space-saving hot-key sketches over
    pull/push/serving key traffic, merged across shards.
  * :mod:`.flightrec` — the bounded blackbox ring dumped to
    ``results/<platform>/flightrec_<reason>.json`` on crash, stall,
    or stale-epoch storm.
  * :mod:`.slo` — declarative objectives evaluated as multi-window
    burn rates, consumable by the elastic controller.
  * :mod:`.timeline` — the time axis: a background sampler polling
    the registry into bounded per-instrument ring series (counters as
    rates, gauges as values, histograms as windowed p50/p99), plus
    the :class:`SkewTracker` per-entity straggler attribution.
  * :mod:`.detectors` — online anomaly detectors (EWMA drift +
    rolling-MAD outlier) riding the timeline sample loop; firings
    count, note the flight recorder, and pressure the elastic
    controller.
  * :mod:`.profiler` — the latency-budget profiler: per-phase cost
    attribution of every cluster round (client serialize → wire →
    queue wait → WAL → scatter → serialize → parse), plus a sampling
    :class:`StackSampler` with folded-stack/flamegraph export.
"""
from .distributed import (
    TraceCollector,
    TraceContext,
    format_token,
    new_trace,
    parse_token,
)
from .exporter import TelemetryServer, prometheus_text, scrape
from .flightrec import FlightRecorder, StormDetector, get_recorder, set_recorder
from .hotkeys import (
    HotKeyAggregator,
    HotKeySketch,
    SpaceSavingTopK,
    get_aggregator,
    set_aggregator,
)
from .profiler import (
    PHASES,
    PhaseProfiler,
    StackSampler,
    get_profiler,
    set_profiler,
)
from .slo import SLOEngine, SLOSpec, default_slos
from .registry import (
    DEFAULT_BUCKETS,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_run_id,
    get_registry,
    json_line,
    set_registry,
)
from .report import build_run_report, render_markdown, write_run_report
from .spans import SpanTracer, get_tracer, set_tracer, span
from .detectors import EWMADriftDetector, RollingMADDetector
from .timeline import (
    SkewTracker,
    TimelineRecorder,
    get_timeline,
    percentile_from_counts,
    set_timeline,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "DEFAULT_BUCKETS",
    "default_run_id",
    "json_line",
    "get_registry",
    "set_registry",
    "SpanTracer",
    "get_tracer",
    "set_tracer",
    "span",
    "TelemetryServer",
    "prometheus_text",
    "scrape",
    "build_run_report",
    "render_markdown",
    "write_run_report",
    "TraceCollector",
    "TraceContext",
    "format_token",
    "new_trace",
    "parse_token",
    "FlightRecorder",
    "StormDetector",
    "get_recorder",
    "set_recorder",
    "HotKeyAggregator",
    "HotKeySketch",
    "SpaceSavingTopK",
    "get_aggregator",
    "set_aggregator",
    "SLOEngine",
    "SLOSpec",
    "default_slos",
    "PHASES",
    "PhaseProfiler",
    "StackSampler",
    "get_profiler",
    "set_profiler",
    "TimelineRecorder",
    "SkewTracker",
    "percentile_from_counts",
    "get_timeline",
    "set_timeline",
    "EWMADriftDetector",
    "RollingMADDetector",
]
