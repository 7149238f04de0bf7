"""Unified observability layer: dispatch tracing, metrics, spans, and
the retrace alarm.

Four pillars, one switch:

  * ``repro.obs.trace``   -- structured dispatch-decision events from
    the multiply/divide/modexp tier choosers (bounded ring buffer,
    subscribable);
  * ``repro.obs.metrics`` -- process-level counters / gauges /
    histograms with labels (``REGISTRY``), absorbing the serving
    engine's stats and feeding ``repro.api.metrics()``;
  * ``repro.obs.spans``   -- nested wall-time spans, always annotated
    on the profiler's timeline, buffered and exportable as Chrome-trace
    JSON;
  * ``repro.obs.retrace`` -- the zero-retrace contract as a runtime
    guard (``configure(on_retrace="warn"|"raise"|"ignore")``).

The switch is off by default: dispatch events, registry metrics and the
span buffer are then guarded no-ops that allocate nothing.  Enable with
``repro.api.configure(observability=True)`` (scoped via its context-
manager form) or the ``enable()`` / ``disable()`` shorthands here.
Two things ignore the switch.  A span's profiler annotation is always
made (the profiler records it only while a trace is being taken), so
a device trace shows the program's spans without reconfiguring it.  The retrace counter always ticks, because a
post-warm retrace is an operational bug worth counting even when
nobody asked for tracing.

This package is import-light by design (stdlib + ``repro.config``
only): core dispatchers and configs call into it without pulling jax
into their import graphs.
"""
from __future__ import annotations

from repro import config as _config
from repro.obs import metrics, retrace, spans, trace
from repro.obs.metrics import REGISTRY, Counter, Gauge, Histogram
from repro.obs.retrace import RetraceAlarm, RetraceWarning
from repro.obs.spans import chrome_trace, span, write_chrome_trace
from repro.obs.trace import DispatchEvent, format_report, subscribe

# trace.events under its facade name (repro.obs.trace.events reads
# better fully qualified; bare "events" is ambiguous at package level)
dispatch_events = trace.events
dispatch_report = trace.report

__all__ = [
    "metrics", "trace", "spans", "retrace",
    "REGISTRY", "Counter", "Gauge", "Histogram",
    "RetraceAlarm", "RetraceWarning",
    "chrome_trace", "span", "write_chrome_trace",
    "DispatchEvent", "dispatch_events", "dispatch_report",
    "format_report", "subscribe",
    "enable", "disable", "enabled", "reset",
]


def enabled() -> bool:
    return bool(_config.get_override("observability"))


def enable() -> None:
    """Turn observability on process-wide (== configure(observability=
    True); use the configure context manager for scoped enabling)."""
    _config.set_overrides({"observability": True})


def disable() -> None:
    _config.set_overrides({"observability": None})


def reset() -> None:
    """Clear every buffer and the metrics registry (tests, CLI runs)."""
    trace.clear()
    spans.clear()
    metrics.REGISTRY.reset()
