"""Telemetry: typed metrics, Prometheus/JSONL export, span tracing,
cross-rank aggregation.

The observability layer the ROADMAP's "production-scale, heavy traffic"
north star requires (the reference's monitor.h StatRegistry +
PrintSyncTimer + log_for_profile + CUPTI timeline, rebuilt TPU-native):

  * :mod:`metrics` — Counter / Gauge / Histogram with labels, p50/p95/p99
    estimation, delta snapshots, one process-global :data:`registry`
    (``utils/monitor.stats`` forwards here unchanged);
  * :mod:`export` — Prometheus text exposition (``render_prometheus``),
    the standalone :class:`MetricsExporter` ``/metrics`` listener;
  * :mod:`events` — rank-tagged JSONL event/metrics log (size-rotated);
  * :mod:`trace` — ``span("name")`` -> Chrome-trace JSON (Perfetto);
  * :mod:`fleet` — pass-boundary cross-rank snapshot gather + merge;
  * :mod:`context` — W3C-style trace-context propagation (one trace ID
    across router -> replica -> syncer, ``traceparent`` carriage);
  * :mod:`flight` — the always-on flight recorder: a bounded ring of
    recent spans/events dumped to JSON on stalls, rollbacks, sync
    fallbacks, replica crashes and SIGTERM (``tools/pbox_doctor.py``
    correlates the dumps offline);
  * :mod:`health` — the run-health plane: a declarative rule catalog
    (EWMA z-score + absolute checks over training/table/pipeline
    signals) evaluated per pass; firing rules alert, count, and at
    ``critical`` dump the flight ring.
"""

from paddlebox_tpu.telemetry.metrics import (  # noqa: F401
    Counter,
    DEFAULT_LATENCY_BUCKETS,
    Gauge,
    Histogram,
    MetricRegistry,
    Snapshot,
    counter,
    gauge,
    histogram,
    quantile_from_buckets,
    registry,
)
from paddlebox_tpu.telemetry.export import (  # noqa: F401
    MetricsExporter,
    PROMETHEUS_CONTENT_TYPE,
    ensure_exporter,
    render_prometheus,
    stop_exporter,
)
from paddlebox_tpu.telemetry.events import (  # noqa: F401
    EventLog,
    close_event_log,
    emit_event,
    ensure_event_log,
)
from paddlebox_tpu.telemetry.trace import (  # noqa: F401
    Tracer,
    adopt_span,
    annotation,
    current_span,
    disable_tracing,
    enable_tracing,
    flush_trace,
    get_tracer,
    instant,
    span,
)
from paddlebox_tpu.telemetry.fleet import (  # noqa: F401
    FleetGatherTimeout,
    format_fleet_view,
    gather_fleet_snapshot,
    log_fleet_view,
    merge_snapshots,
)
from paddlebox_tpu.telemetry import context  # noqa: F401
from paddlebox_tpu.telemetry.context import (  # noqa: F401
    REPLICA_RESPONSE_HEADER,
    TRACE_ID_RESPONSE_HEADER,
    TRACEPARENT_HEADER,
    TraceContext,
)
from paddlebox_tpu.telemetry.flight import (  # noqa: F401
    FlightRecorder,
    dump_flight,
    install_signal_dump,
    run_identity,
    set_process_name,
    set_run_backend,
)
from paddlebox_tpu.telemetry.health import (  # noqa: F401
    HealthAlert,
    HealthMonitor,
    HealthRule,
    default_rules,
    get_monitor,
    health_view,
    observe_pass,
)
from paddlebox_tpu.telemetry.compiles import (  # noqa: F401
    CountedJit,
    compiles_by_stage,
    counted_jit,
    install_compile_listener,
    stage_scope,
    total_compiles,
)
