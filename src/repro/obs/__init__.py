"""repro.obs -- tracing, metrics, schedule timelines, and the fleet journal.

The observability substrate for the online control plane (ROADMAP:
"planner as a service") and for every perf PR's measurement needs:

  metrics   counters and gauges with labels, JSON snapshot +
            Prometheus text exposition, planner-scoped deltas
  tracing   nestable spans over the hot seams (GA generations, DES
            compile/simulate, MILP phases, fleet decisions), Chrome-trace
            export, near-zero cost when disabled (the default)
  timeline  DES schedule -> Perfetto-viewable trace with per-link tracks
            + the critical-path / per-task-slack report
  journal   structured JSONL log of fleet events + decisions, replayable
  logs      one ``repro.``-hierarchy logging setup (no bare prints)

Quick start::

    from repro import obs
    obs.TRACER.enable()
    ... run a plan ...
    print(obs.TRACER.summary())            # where did the time go
    print(obs.REGISTRY.render_prometheus())   # scrapeable counters
"""
from repro.obs.journal import FleetJournal, rebuild_event, serialize_event
from repro.obs.logs import get_logger, setup_logging
from repro.obs.metrics import (REGISTRY, Counter, Gauge, MetricsRegistry,
                               RegistryScope, get_counter, get_gauge)
from repro.obs.timeline import (plane_rewire_timeline, schedule_timeline,
                                slack_report, task_slack, validate_trace,
                                write_trace)
from repro.obs.tracing import TRACER, SpanRecord, Tracer, enabled, span

__all__ = [
    "Counter", "Gauge", "MetricsRegistry", "RegistryScope",
    "REGISTRY", "get_counter", "get_gauge",
    "Tracer", "TRACER", "SpanRecord", "span", "enabled",
    "plane_rewire_timeline", "schedule_timeline", "slack_report",
    "task_slack", "validate_trace", "write_trace",
    "FleetJournal", "serialize_event", "rebuild_event",
    "get_logger", "setup_logging",
]
