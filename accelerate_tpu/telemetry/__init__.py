"""Unified runtime telemetry (docs/observability.md).

Dependency-free, hot-path-safe metrics + tracing for training and serving:

- `telemetry.registry` — counters / gauges / fixed-bucket histograms with
  label sets, Prometheus text rendering, and cross-host aggregation via
  per-process JSON snapshots merged by proc 0 (no collectives).
- `telemetry.spans` — wall-clock host spans as Chrome-trace JSONL; every
  span is also a ``jax.profiler.TraceAnnotation``, so it lands in any
  XPlane capture of the process, whoever started it.
- `telemetry.stepstats` — per-step dispatch vs device-compute split,
  EMA tokens/sec + hardware-FLOPs utilisation, and a recompile counter, wired into the
  `Accelerator` step helper behind ``ATX_METRICS`` (default on; zero device
  syncs unless ``ATX_METRICS_SAMPLE_EVERY`` turns the sampler on).
- `telemetry.export` — stdlib-only Prometheus ``/metrics`` HTTP endpoint
  (`atx serve --metrics-port`).
- `telemetry.views.StatsView` — the registry-backed dict view behind the
  serving engine/router/prefix-cache ``stats`` so the old snapshot shapes
  and the endpoint read one source of truth.

- `telemetry.flight` — the request-scoped tracing layer: a bounded
  per-process ring of span records (the black-box *flight recorder*) that
  the serving path tags with request ids behind ``ATX_TRACE_REQUESTS=1``,
  plus `dump_postmortem`, which abnormal-exit hooks (watchdog 114, exit-75,
  quarantine, chaos violations, the non-finite guard) use to drop a
  last-N-spans + metrics + thread-stacks bundle into ``ATX_POSTMORTEM_DIR``
  (rendered by ``atx trace``).

Knobs: ``ATX_METRICS`` (default 1), ``ATX_METRICS_SAMPLE_EVERY`` (default 0),
``ATX_METRICS_LOG_EVERY`` (default 0), ``ATX_METRICS_DIR`` (shared snapshot
dir), ``ATX_METRICS_EMA`` (default 0.2), ``ATX_TRACE_DIR`` (span JSONL),
``ATX_TRACE_REQUESTS`` (default 0), ``ATX_FLIGHT_RECORDER_SPANS`` (default
4096), ``ATX_POSTMORTEM_DIR`` (unset = no bundles).
"""

from __future__ import annotations

from ..utils.environment import parse_flag_from_env
from . import export, flight, registry, spans, stepstats, views
from .export import MetricsServer
from .flight import (
    FlightRecorder,
    dump_postmortem,
    read_bundle,
    record_span,
    trace_requests_enabled,
)
from .registry import (
    DEFAULT_BYTES_BUCKETS,
    DEFAULT_MS_BUCKETS,
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricError,
    Registry,
    aggregate_snapshots,
    counter,
    gauge,
    histogram,
    merge_snapshots,
    read_snapshots,
    render_prometheus,
    render_snapshot_prometheus,
    snapshot,
    write_snapshot,
)
from .spans import chrome_trace, span, start_trace_log, step_span, stop_trace_log
from .stepstats import StepStats, peak_device_flops, tokens_in_batch
from .views import StatsView

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricError",
    "MetricsServer",
    "Registry",
    "REGISTRY",
    "StatsView",
    "StepStats",
    "DEFAULT_BYTES_BUCKETS",
    "DEFAULT_MS_BUCKETS",
    "FlightRecorder",
    "aggregate_snapshots",
    "chrome_trace",
    "counter",
    "dump_postmortem",
    "gauge",
    "histogram",
    "merge_snapshots",
    "metrics_enabled",
    "peak_device_flops",
    "read_bundle",
    "read_snapshots",
    "record_span",
    "trace_requests_enabled",
    "render_prometheus",
    "render_snapshot_prometheus",
    "snapshot",
    "span",
    "start_trace_log",
    "step_span",
    "stop_trace_log",
    "tokens_in_batch",
    "write_snapshot",
    "export",
    "flight",
    "registry",
    "spans",
    "stepstats",
    "views",
]


def metrics_enabled() -> bool:
    """The ``ATX_METRICS`` master switch (default ON). Gates the training
    step-stats hooks and span emission; registry counters themselves always
    work — they ARE the serving stats."""
    return parse_flag_from_env("ATX_METRICS", True)
