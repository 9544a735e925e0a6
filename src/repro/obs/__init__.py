"""Unified telemetry for the serving stack.

Two instruments, one install contract (the ``serve/faults.py``
nullable-singleton pattern — a disabled instrument costs one ``is None``
check on the hot path):

* :mod:`repro.obs.metrics`  — typed Counter/Gauge/Histogram registry,
  Prometheus-text + JSON snapshot exporters, core-reachable via
  ``core.pager._metrics_hook``.
* :mod:`repro.obs.trace`    — span tracer with Chrome-trace (Perfetto)
  export, plus :class:`RequestTimeline`, the one TTFT / inter-token
  stamping path shared by benchmarks and live serving.  The scheduler's
  own spans (one ``iteration`` per loop turn, split into upkeep,
  admission, prefill chunks, decode dispatch, sampling, the device wait,
  token emission and the client callback) are mirrored into
  ``jax.profiler`` as ``serve.<name>`` annotations, so under a profiler
  they sit on the same clock as the device's programs.

``enable()`` wires both for a scheduler run; ``enabled()`` is the
context-manager form the tests use.  A tracer alone is
``obs.trace.install(SpanTracer())``.
"""
from __future__ import annotations

from contextlib import contextmanager

from repro.obs import metrics, trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import RequestTimeline, SpanTracer

__all__ = [
    "MetricsRegistry", "RequestTimeline", "SpanTracer",
    "enable", "disable", "enabled", "metrics", "trace",
]


def enable(gauge_history: int = 0, clock=None) -> dict:
    """Install a fresh registry + tracer.  Returns the handles;
    ``disable()`` reverses it."""
    reg = MetricsRegistry(max_series=gauge_history)
    kw = {"clock": clock} if clock is not None else {}
    tr = SpanTracer(max_events=gauge_history, **kw)
    metrics.install(reg)
    trace.install(tr)
    return {"registry": reg, "tracer": tr}


def disable():
    trace.uninstall()
    metrics.uninstall()


@contextmanager
def enabled(**kw):
    handles = enable(**kw)
    try:
        yield handles
    finally:
        disable()
