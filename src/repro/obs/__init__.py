"""repro.obs — zero-dependency telemetry: tracing, metrics, contention.

Three pillars, wired through the whole stack (see DESIGN.md "Telemetry
and contention attribution"):

* :mod:`repro.obs.trace` — a nestable, thread-safe span tracer, globally
  **off by default** with a near-zero disabled path (gated <= 2%
  overhead in ``BENCH_obs.json``), exporting Chrome trace-event JSON.
  Instrumented boundaries: scheduler event processing
  (``scheduler.step`` / ``scheduler.place``), the blocked head's
  reservation scan and backfill (``scheduler.reserve`` /
  ``scheduler.backfill``), geometry ranking (``allocation.rank``),
  placement search (``placement.search``), netsim draining
  (``netsim.drain``), backend dispatch (``backend.*`` with jit recompile
  / padding-bucket counters, a compile-vs-execute split, and
  ``xla.call`` / ``xla.fetch`` children), planner candidate pricing
  (``planner.price``), and the launch drivers' wall-clock timers.  With
  jax imported, each span is also a profiler ``TraceAnnotation``
  (``scheduler:reserve``), on the profiler's clock.
* :mod:`repro.obs.metrics` — a registry of counters / gauges /
  histograms with labeled series and JSON snapshot export;
  :func:`scheduler_metrics` derives the scheduler's queue-depth /
  wait / turnaround / utilization / per-job efficiency metrics from the
  event log, so replaying a log reproduces the metrics exactly.
* :mod:`repro.obs.contention` — per-link load attribution by owning job
  (self vs. cross traffic), hotspot flagging, and the
  **avoidable-contention** gauge: measured load of the granted geometry
  vs. the Theorem 3.1-certified optimal from ``advise_partition`` — the
  paper's headline quantity as a continuously-observable metric.

Quickstart::

    from repro import obs
    obs.enable_tracing()
    ...  # run scheduler / netsim / planner work
    obs.export_chrome_trace("trace.json")   # open in Perfetto
    obs.metrics_registry().export("metrics.json")
    report = obs.attribute_contention(machine)
    print(obs.render_dashboard(report))

>>> tracing_enabled()
False
>>> with trace("noop"):
...     pass
>>> export_chrome_trace()["traceEvents"]
[]
"""

from __future__ import annotations

from typing import Any, Dict, Optional

from .trace import TRACER, Span, Timer, Tracer
from .metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    scheduler_metrics,
)
from .contention import (
    ContentionReport,
    HotspotLink,
    JobContention,
    attribute_contention,
    attribute_traffic,
    render_dashboard,
)

__all__ = [
    "TRACER",
    "REGISTRY",
    "Span",
    "Timer",
    "Tracer",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "ContentionReport",
    "HotspotLink",
    "JobContention",
    "attribute_contention",
    "attribute_traffic",
    "render_dashboard",
    "scheduler_metrics",
    "enable_tracing",
    "disable_tracing",
    "tracing_enabled",
    "trace",
    "timer",
    "export_chrome_trace",
    "clear_telemetry",
    "metrics_registry",
    "metrics_snapshot",
]


def enable_tracing(clear: bool = False) -> None:
    """Turn the process-wide tracer on (``clear=True`` drops prior events)."""
    TRACER.enable(clear=clear)


def disable_tracing() -> None:
    """Turn the process-wide tracer off (events are kept)."""
    TRACER.disable()


def tracing_enabled() -> bool:
    """Whether the process-wide tracer is recording."""
    return TRACER.enabled


def trace(name: str, **args: Any):
    """Open a span on the process-wide tracer (no-op while disabled)."""
    return TRACER.span(name, **args)


def timer(name: str, **args: Any) -> Timer:
    """An always-measuring :class:`Timer` on the process-wide tracer."""
    return TRACER.timer(name, **args)


def export_chrome_trace(path: Optional[str] = None) -> Dict[str, Any]:
    """The process-wide tracer's Chrome trace object (written to ``path``
    when given)."""
    return TRACER.export(path)


def metrics_registry() -> MetricsRegistry:
    """The process-wide default metrics registry."""
    return REGISTRY


def metrics_snapshot() -> Dict[str, Any]:
    """JSON-able snapshot of the process-wide metrics registry."""
    return REGISTRY.snapshot()


def clear_telemetry() -> None:
    """Drop all recorded trace events and metrics series."""
    TRACER.clear()
    REGISTRY.clear()
