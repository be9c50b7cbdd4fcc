"""Run telemetry for photon-ml-tpu: metrics registry, hierarchical span
tracing with JAX-aware annotations, and JSONL / Prometheus sinks.

Quick tour::

    from photon_ml_tpu import obs

    run = obs.RunTelemetry()
    run.register_listener(obs.JsonlSink("metrics.jsonl"))
    with obs.use_run(run):
        with obs.span("train"):
            ...  # spans opened here nest under "train"
        run.flush_metrics()
    run.close()

With no sinks registered (``obs.active()`` is False) instrumentation is
passive: cheap host-known numbers still land in the default registry, but
nothing that would force a device fetch runs. `cli.train --metrics-out DIR`
wires this up end to end.
"""

from . import fleet
from .flightrec import FlightRecorder
from .http import IntrospectionServer, compose_statusz
from .memory import memory_block, read_host_memory, sample_memory
from .metrics import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    histogram_quantile,
    render_prometheus,
)
from .run import (
    MetricsSnapshotEvent,
    RunTelemetry,
    StatusBoard,
    active,
    build_run_summary,
    collect_build_info,
    current_run,
    record_build_info,
    record_solver_metrics,
    set_current_run,
    swallowed_error,
    use_run,
)
from .sinks import JsonlSink, PrometheusSink
from .timeline import TimelineRecorder, interval_overlap_seconds, overlap_ratio
from .tracing import (
    Span,
    SpanEvent,
    add_compile_seconds,
    add_device_fetch_bytes,
    add_device_put_bytes,
    add_retrace_seconds,
    compile_seconds_total,
    current_span,
    get_process_index,
    get_replica_id,
    record_device_fetch,
    record_span,
    set_process_index,
    set_replica_id,
    span,
)

__all__ = [
    "DEFAULT_BUCKETS",
    "FlightRecorder",
    "IntrospectionServer",
    "MetricsRegistry",
    "MetricsSnapshotEvent",
    "RunTelemetry",
    "Span",
    "SpanEvent",
    "StatusBoard",
    "TimelineRecorder",
    "JsonlSink",
    "PrometheusSink",
    "active",
    "add_compile_seconds",
    "add_device_fetch_bytes",
    "add_device_put_bytes",
    "add_retrace_seconds",
    "build_run_summary",
    "collect_build_info",
    "compile_seconds_total",
    "compose_statusz",
    "current_run",
    "current_span",
    "fleet",
    "get_process_index",
    "get_replica_id",
    "histogram_quantile",
    "interval_overlap_seconds",
    "overlap_ratio",
    "memory_block",
    "read_host_memory",
    "record_build_info",
    "record_device_fetch",
    "record_solver_metrics",
    "record_span",
    "sample_memory",
    "render_prometheus",
    "set_current_run",
    "set_process_index",
    "set_replica_id",
    "span",
    "swallowed_error",
    "use_run",
]
