"""Unified telemetry: metrics registry, structured events, fleet rollups.

The observability backbone (≙ the reference's tf.monitoring gauges +
coordinator monitored_timer metrics + tf.summary event files, SURVEY.md
§2.5/§5.5), in four pieces:

- :mod:`registry`  — MetricsRegistry: namespaced Counter / Gauge /
  Histogram / Timer instruments with snapshot/delta export. Every
  existing instrument set (coordinator/metric_utils.py, utils/summary.py
  gauges, resilience/health.py, input pipeline stage stats,
  resilience/faults.py firings) registers through it.
- :mod:`events`    — structured run events: ``span``/``event`` API
  writing append-only JSONL with monotonic timestamps; rendered by
  ``tools/obs_report.py``.
- :mod:`aggregate` — cross-host aggregation: workers publish snapshots
  through the coordination KV store; the coordinator merges fleet
  rollups (sum/max/p50/p95) and emits them to TensorBoard.
- :mod:`stall`     — StallDetector layered on coordinator/watchdog.py:
  no step within ``factor`` x trailing median -> ``stall.suspected``
  naming the slowest worker, non-fatal.
- :mod:`trace`     — cross-host trace assembly: every process's JSONL
  merged into ONE Chrome-trace/Perfetto JSON with per-host clock
  offsets estimated from barrier/heartbeat sync points, span causality
  via ``span_id`` flow arrows, and a bottleneck classifier (input- /
  comm- / compute- / checkpoint- / recovery-bound) with explicit
  thresholds; rendered by ``tools/trace_report.py``.
- :mod:`exporter`  — LIVE export: bounded ring-buffer time-series per
  instrument, Prometheus text endpoint (``DTX_METRICS_PORT``) with a
  ``metrics-live.prom`` file fallback, fleet merge over KV rollups.
- :mod:`goodput`   — goodput/badput ledger pricing every wall-clock
  second into productive step time vs named waste buckets (startup,
  infeed wait, checkpoint block, recovery, preempt replay, idle) with
  ``wall == goodput + Σ badput`` enforced; rendered/gated by
  ``tools/health_report.py``.
- :mod:`slo`       — declarative serving SLOs (p99 latency, TTFT,
  availability) evaluated over multi-window burn rates, live and as CI
  gates.

Quick start::

    from distributed_tensorflow_tpu import telemetry

    telemetry.configure("/tmp/run1/telemetry")     # per-process JSONL
    step_t = telemetry.timer("training/step_time")
    with telemetry.span("train.step", step=i), step_t.time():
        state, metrics = step_fn(state, batch)

Telemetry is OFF by default: with no event log configured and no
publisher started, instrumented call sites cost one None check; a
``span`` is also a ``jax.profiler.TraceAnnotation``, one flag test
while no profiler session runs.
"""

from distributed_tensorflow_tpu.telemetry.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    Timer,
    counter,
    gauge,
    get_registry,
    histogram,
    timer,
)
from distributed_tensorflow_tpu.telemetry.events import (
    ENV_TELEMETRY_DIR,
    EventLog,
    EventLogCorruptError,
    configure,
    enabled,
    event,
    event_log_path,
    get_event_log,
    read_events,
    read_run,
    recording,
    shutdown,
    span,
)
from distributed_tensorflow_tpu.telemetry.aggregate import (
    FleetAggregator,
    MetricsPublisher,
    RollupTopology,
    collect_rollup,
    collect_rollup_tree,
    merge_rollup,
    publish_snapshot,
    read_snapshots,
    rollup_scalars,
    run_duties,
)
from distributed_tensorflow_tpu.telemetry.stall import (
    StallDetector,
    suspect_worker,
)
from distributed_tensorflow_tpu.telemetry.trace import (
    BOTTLENECK_THRESHOLDS,
    assemble_run,
    assemble_trace,
    classify_run,
    estimate_clock_offsets,
    overlap_efficiency,
    trace_completeness,
    write_trace,
)
from distributed_tensorflow_tpu.telemetry.exporter import (
    ENV_METRICS_PORT,
    LIVE_METRICS_FILE,
    MetricsExporter,
    SeriesHistory,
    render_prometheus,
    render_rollup,
)
from distributed_tensorflow_tpu.telemetry.goodput import (
    BADPUT_BUCKETS,
    GoodputLedger,
    ledger_from_events,
    ledger_from_run,
)
from distributed_tensorflow_tpu.telemetry.slo import (
    DEFAULT_BURN_WINDOWS,
    SLO,
    SLOMonitor,
    default_serving_slos,
    evaluate_records,
    records_from_events,
    windows_for_span,
)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Timer",
    "counter", "gauge", "get_registry", "histogram", "timer",
    "ENV_TELEMETRY_DIR", "EventLog", "EventLogCorruptError", "configure",
    "enabled", "event", "event_log_path", "get_event_log", "read_events",
    "read_run", "recording", "shutdown", "span",
    "FleetAggregator", "MetricsPublisher", "RollupTopology",
    "collect_rollup", "collect_rollup_tree", "merge_rollup",
    "publish_snapshot", "read_snapshots", "rollup_scalars",
    "run_duties",
    "StallDetector", "suspect_worker",
    "BOTTLENECK_THRESHOLDS", "assemble_run", "assemble_trace",
    "classify_run", "estimate_clock_offsets", "overlap_efficiency",
    "trace_completeness", "write_trace",
    "ENV_METRICS_PORT", "LIVE_METRICS_FILE", "MetricsExporter",
    "SeriesHistory", "render_prometheus", "render_rollup",
    "BADPUT_BUCKETS", "GoodputLedger", "ledger_from_events",
    "ledger_from_run",
    "DEFAULT_BURN_WINDOWS", "SLO", "SLOMonitor", "default_serving_slos",
    "evaluate_records", "records_from_events", "windows_for_span",
]
