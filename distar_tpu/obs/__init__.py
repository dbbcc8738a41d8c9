"""Unified telemetry + fleet health layer.

Instrumentation side (PR 1): one process-wide ``MetricsRegistry``
(``get_registry()``) that every layer — actor, env pool, comm
shuttle/coordinator, learner, league, serve — publishes into; Prometheus
text + JSONL exporters; explicit-context trace spans that ride payloads
actor→comm→learner; freq-gated profiler hooks.

Consumption side (this package's fleet-health subsystem): a bounded
ring-buffer ``TimeSeriesStore`` fed by a ``RegistrySampler``; a
``TelemetryShipper`` pushing compact snapshots from every fleet process to
the coordinator's ``TelemetryIngest``; a declarative ``HealthRule`` engine
with a debounced ok→warning→firing state machine (``HealthEvaluator``,
``default_rulebook``); and a ``FlightRecorder`` crash bundle. Surfaced via
``GET /healthz``, ``/alerts``, ``/timeseries`` on the coordinator and serve
HTTP frontends, and ``tools/opsctl.py``. See docs/observability.md.
"""
from .registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
    set_registry,
)
from .exporters import (
    PROMETHEUS_CONTENT_TYPE,
    JsonlExporter,
    handle_health_get,
    render_prometheus,
    write_json_response,
    write_scrape_response,
)
from .trace import (
    Span,
    annotate,
    annotate_active,
    current_trace,
    finish_trace,
    format_traceparent,
    hop_names,
    is_trace,
    is_wire_ctx,
    join_trace,
    mark_hop,
    mint_span_id,
    parse_traceparent,
    set_active_trace,
    set_tracing,
    start_trace,
    trace_record,
    tracing_enabled,
    unwrap_payload,
    wire_ctx,
    wrap_payload,
)
from .tracestore import (
    ExemplarStore,
    TraceBuffer,
    TraceIngest,
    get_exemplar_store,
    get_trace_buffer,
    note_exemplar,
    set_exemplar_store,
    set_trace_buffer,
)
from .waterfall import build_waterfall, render_listing, render_waterfall
from .profiler import LM_STEP_SCOPES, STEP_SCOPES, ProfilerSession, Spans, feed_spans, loop_spans
from .perf import (
    PerfMonitor,
    estimate_collective_bytes,
    flops_of_compiled,
    flops_of_lowered,
    memory_report,
    peak_flops,
)
from .traceview import analyze_trace, classify, render_markdown
from .timeseries import RegistrySampler, TimeSeriesStore
from .shipper import SERIALIZED_CONTENT_TYPE, TelemetryIngest, TelemetryShipper
from .flightrecorder import FlightRecorder, get_flight_recorder, set_flight_recorder
from .dynamics import (
    ANOMALY_CLASSES,
    BUNDLE_SCHEMA,
    DYNAMICS_DEFAULTS,
    DynamicsMonitor,
    DynamicsSpec,
    bundle_summary,
    config_digest,
    dynamics_tree,
    first_nonfinite,
    list_bundles,
    load_bundle,
    split_tree,
    tree_spec,
)
from .health import (
    FleetHealth,
    HealthEvaluator,
    HealthRule,
    default_rulebook,
    get_fleet_health,
    init_fleet_health,
    set_fleet_health,
)

__all__ = [
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "get_registry",
    "set_registry",
    "PROMETHEUS_CONTENT_TYPE",
    "JsonlExporter",
    "handle_health_get",
    "render_prometheus",
    "write_json_response",
    "write_scrape_response",
    "Span",
    "annotate",
    "annotate_active",
    "current_trace",
    "finish_trace",
    "format_traceparent",
    "hop_names",
    "is_trace",
    "is_wire_ctx",
    "join_trace",
    "mark_hop",
    "mint_span_id",
    "parse_traceparent",
    "set_active_trace",
    "set_tracing",
    "start_trace",
    "trace_record",
    "tracing_enabled",
    "unwrap_payload",
    "wire_ctx",
    "wrap_payload",
    "ExemplarStore",
    "TraceBuffer",
    "TraceIngest",
    "get_exemplar_store",
    "get_trace_buffer",
    "note_exemplar",
    "set_exemplar_store",
    "set_trace_buffer",
    "build_waterfall",
    "render_listing",
    "render_waterfall",
    "ProfilerSession",
    "STEP_SCOPES",
    "LM_STEP_SCOPES",
    "Spans",
    "feed_spans",
    "loop_spans",
    "PerfMonitor",
    "estimate_collective_bytes",
    "flops_of_compiled",
    "flops_of_lowered",
    "memory_report",
    "peak_flops",
    "analyze_trace",
    "classify",
    "render_markdown",
    "RegistrySampler",
    "TimeSeriesStore",
    "SERIALIZED_CONTENT_TYPE",
    "TelemetryIngest",
    "TelemetryShipper",
    "FlightRecorder",
    "get_flight_recorder",
    "set_flight_recorder",
    "ANOMALY_CLASSES",
    "BUNDLE_SCHEMA",
    "DYNAMICS_DEFAULTS",
    "DynamicsMonitor",
    "DynamicsSpec",
    "bundle_summary",
    "config_digest",
    "dynamics_tree",
    "first_nonfinite",
    "list_bundles",
    "load_bundle",
    "split_tree",
    "tree_spec",
    "FleetHealth",
    "HealthEvaluator",
    "HealthRule",
    "default_rulebook",
    "get_fleet_health",
    "init_fleet_health",
    "set_fleet_health",
]
