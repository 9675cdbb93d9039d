"""Unified process telemetry (docs/observability.md).

One registry answers "what is this process doing right now" across
training, the input pipeline, data-parallel dispatch and serving — the
role the reference spreads over StatsListener/StatsStorage, OpProfiler
and PerformanceTracker (SURVEY.md §5.1), collapsed into:

    registry    — counters / gauges / ring-buffer histograms with
                  p50/p95/p99, labeled series, thread-safe, near-zero
                  cost when idle (`set_enabled(False)` kill-switch)
    spans       — `span("fit_epoch")` host wall-time regions, nested,
                  into the `span_ms` histogram; `note(name, t0, t1, n)`
                  for sites that hold both clock reads; both land in a
                  ring of the last 8,192 intervals on `time.perf_counter`
                  (`recorded`, `clear_recorded`), which the benchmark lays
                  over the device trace's idle gaps.  `span` also feeds
                  `jax.profiler.TraceAnnotation`, visible only in a
                  capture with the profiler's host tracer on.
                  `lowered_step()`: the train step compiled last, lowered
                  when asked — its compiled text names each device op's
                  layer (`jax.named_scope`)
    instrument  — cached hot-path handle bundles (training / pipeline /
                  parallel) and the metric-name contract

Scrape surface: `GET /metrics` on `ui.server.UIServer` (Prometheus text
format) and a snapshot block on the HTML dashboard; `serving.ServingMetrics`
is a view over the same registry.
"""
from deeplearning4j_tpu.monitor.forecast import (  # noqa: F401
    ArrivalRateForecaster, HoltForecaster)
from deeplearning4j_tpu.monitor.registry import (  # noqa: F401
    Counter, Gauge, Histogram, MetricsRegistry, enabled, registry,
    set_enabled)
from deeplearning4j_tpu.monitor.spans import (  # noqa: F401
    clear_recorded, current_span, lowered_step, note, note_step, recorded,
    span, span_stack)
