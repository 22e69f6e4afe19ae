"""graftel — unified structured tracing, flight recorder, and cross-layer
telemetry (docs/OBSERVABILITY.md).

One process-wide hub the five formerly-disconnected surfaces (``Timer``,
``FeedStats``, ``ServeMetrics``, ``FaultCounters``, ``supervisor.json``) now
emit into: spans/events with thread-aware context propagation across the
stack's seven host thread roots, serve request correlation ids carried
end-to-end, a bounded flight-recorder ring dumped on guard trips / engine
poisoning / checkpoint fallbacks / supervisor restarts, JSONL + Chrome-trace
exporters, a jax compile/annotation bridge, and a Prometheus rendering of
the shared metric registry (training gauges included).

CLI: ``python -m hydragnn_tpu.telemetry smoke`` runs a 2-epoch traced
synthetic train and schema-validates every exporter (the CI smoke step);
``... validate <path>`` checks an existing artifact.
"""

from __future__ import annotations

from . import scopes
from .export import (
    export_chrome_trace,
    export_events_jsonl,
    span_counts,
    validate_chrome_trace,
    validate_events_jsonl,
    validate_flight,
    validate_flight_file,
)
from .graftel import (
    SCHEMA_EVENTS,
    SCHEMA_FLIGHT,
    Context,
    attach,
    clear_counters,
    collected_records,
    collecting,
    configure,
    configured_run_dir,
    counter,
    counter_value,
    counters_snapshot,
    current,
    detach,
    event,
    flight_dump,
    gauge,
    gauges_snapshot,
    install_gc_hook,
    install_jax_hooks,
    jax_annotations,
    jax_seconds,
    new_context,
    new_request_id,
    record_span,
    render_prometheus,
    reset,
    sched_since,
    setup_phase,
    snapshot_records,
    span,
    span_totals,
    thread_sched,
    timer_credit,
    timer_totals,
)
from .stall import StallAccount

__all__ = [
    "SCHEMA_EVENTS",
    "SCHEMA_FLIGHT",
    "Context",
    "StallAccount",
    "attach",
    "clear_counters",
    "collected_records",
    "collecting",
    "configure",
    "configured_run_dir",
    "counter",
    "counter_value",
    "counters_snapshot",
    "current",
    "detach",
    "event",
    "export_chrome_trace",
    "export_events_jsonl",
    "flight_dump",
    "gauge",
    "gauges_snapshot",
    "install_gc_hook",
    "install_jax_hooks",
    "jax_annotations",
    "jax_seconds",
    "new_context",
    "new_request_id",
    "record_span",
    "render_prometheus",
    "reset",
    "sched_since",
    "scopes",
    "setup_phase",
    "snapshot_records",
    "span",
    "span_counts",
    "span_totals",
    "thread_sched",
    "timer_credit",
    "timer_totals",
    "validate_chrome_trace",
    "validate_events_jsonl",
    "validate_flight",
    "validate_flight_file",
]
