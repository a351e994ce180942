"""apex_tpu.observability — metrics, tracing, and run reports.

The third leg of the production triangle next to ``resilience``
(survive) and ``analysis`` (lint): *observe*. TorchTitan (PAPERS.md,
arXiv:2410.06511) treats metrics/logging/profiling as a first-class
subsystem of a pre-training stack; this package is that subsystem here.

- :class:`MetricsRegistry` — thread-safe counters, gauges, and
  bounded-memory histograms with pluggable sinks
  (:class:`JsonlSink`, :class:`PrometheusTextfileSink`,
  :class:`InMemorySink`).
- :class:`StepMetrics` / :class:`StepTimer` — per-step wall time,
  tokens/s, and MFU (FLOP math shared with the benchmark harness via
  :mod:`apex_tpu.utils.flops`), plus device ``memory_stats`` gauges.
  ``ResilienceConfig(metrics=registry)`` wires the whole layer into
  :func:`apex_tpu.resilience.run_training`.
- :func:`span` / :class:`ProfilerCapture` — host spans written into
  the profiler's trace on the device clock (the serving tick's
  ``tick.*`` spans; optionally timed into a registry histogram), and
  windowed ``jax.profiler`` captures (every-N-steps or on watchdog
  incident). :mod:`~apex_tpu.observability.tracing` also holds the
  span and scope names as constants.
- :func:`build_report` / :func:`render_report` — fold a run's JSONL log
  into the report ``python -m apex_tpu.monitor`` prints.
- :class:`SLOSpec` / :func:`evaluate_slos`
  (:mod:`~apex_tpu.observability.slo`) — declared service-level
  objectives (TTFT/TPOT/latency percentiles, goodput, error budget,
  recovery time) scored from the run log; the monitor renders the
  verdict and ``python -m apex_tpu.loadtest --check`` gates on it.
- :mod:`~apex_tpu.observability.trace` — request-level span timelines:
  every serving request carries a ``trace_id``; the engine/supervisor/
  fleet stamp typed ``kind="span"`` rows whose phase durations sum to
  the request's measured latency (:func:`check_span_conservation`).
- :class:`FleetMetrics` / :class:`ReplicaRegistry`
  (:mod:`~apex_tpu.observability.fleet_metrics`) — per-replica metric
  views merged into one fleet snapshot plus the polled ``signals()``
  dict (goodput window, queue depth, p99 TTFT/TPOT, occupancy,
  per-adapter share) that feeds the autoscaler and the drift sentinel.
- :class:`FlightRecorder` (:mod:`~apex_tpu.observability.recorder`) —
  bounded ring buffers of recent telemetry attached as a registry sink;
  any incident-class event (:data:`TRIGGER_EVENTS`) dumps a
  self-contained JSON postmortem bundle rendered by
  ``python -m apex_tpu.monitor bundle``.
- :class:`DriftSentinel` / :class:`SentinelConfig`
  (:mod:`~apex_tpu.observability.sentinel`) — online EWMA + robust
  z-score drift detection over ``FleetMetrics.signals()``, emitting
  typed ``kind="anomaly"`` records with paired ``anomalies_*``
  counters (and the periodic ``kind="gauge_snapshot"`` trajectory
  feed) from the fleet tick.
"""

from apex_tpu.observability.registry import (
    HistogramSnapshot,
    MetricsRegistry,
    percentile,
)
from apex_tpu.observability.sinks import (
    InMemorySink,
    JsonlSink,
    PrometheusTextfileSink,
)
from apex_tpu.observability.step_metrics import StepMetrics, StepTimer
from apex_tpu.observability.tracing import ProfilerCapture, span
from apex_tpu.observability.report import (
    build_report,
    read_records,
    render_report,
)
from apex_tpu.observability.slo import (
    SLO_METRICS,
    SLOObjective,
    SLOReport,
    SLOSpec,
    evaluate_slos,
    measure_slo_metrics,
)
from apex_tpu.observability.trace import (
    MARK_SPANS,
    PHASE_SPANS,
    build_timelines,
    check_span_conservation,
    emit_request_spans,
    emit_span,
    format_timeline,
    new_trace_id,
)
from apex_tpu.observability.fleet_metrics import (
    FleetMetrics,
    ReplicaRegistry,
    merge_histograms,
)
from apex_tpu.observability.recorder import (
    TRIGGER_EVENTS,
    FlightRecorder,
)
from apex_tpu.observability.sentinel import (
    DriftSentinel,
    SentinelConfig,
)

__all__ = [
    "MetricsRegistry",
    "HistogramSnapshot",
    "percentile",
    "InMemorySink",
    "JsonlSink",
    "PrometheusTextfileSink",
    "StepMetrics",
    "StepTimer",
    "ProfilerCapture",
    "span",
    "build_report",
    "read_records",
    "render_report",
    "SLO_METRICS",
    "SLOSpec",
    "SLOObjective",
    "SLOReport",
    "evaluate_slos",
    "measure_slo_metrics",
    "PHASE_SPANS",
    "MARK_SPANS",
    "new_trace_id",
    "emit_request_spans",
    "emit_span",
    "build_timelines",
    "format_timeline",
    "check_span_conservation",
    "FleetMetrics",
    "ReplicaRegistry",
    "merge_histograms",
    "FlightRecorder",
    "TRIGGER_EVENTS",
    "DriftSentinel",
    "SentinelConfig",
]
