"""repro.obs — metrics, lifecycle tracing and timed spans.

One :class:`Observability` hub bundles a :class:`~repro.obs.Metrics`
registry and a :class:`~repro.obs.Tracer`.  Every instrumented object
(`ServeEngine`, `KVBranchManager`, `BranchFS`) creates its **own** hub
by default and shares it downward (engine → KV manager → branch tree
tracer), so tests and concurrent engines never see each other's
counters; pass ``obs=`` to share a hub across layers explicitly, or
``Observability(trace=True)`` to turn the lifecycle tracer on (disabled
tracing is one predicted branch per site).

:meth:`Observability.span` times one phase of the serving path: it
feeds the registry histogram ``<name>_us`` and opens a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler
session the phase lands on the ``/host:CPU`` plane of the trace, on the
device ops' clock.  With no session no annotation is made and a span
costs about two microseconds, so spans are always on.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import jax

from repro.obs.metrics import Counter, Gauge, Histogram, Metrics
from repro.obs.tracer import ENGINE_TRACK, NULL_TRACER, Span, Tracer

_Annotation = jax.profiler.TraceAnnotation


class TimedSpan:
    """One timed phase: a profiler annotation plus a histogram sample.

    On normal exit the elapsed time is observed in µs and kept as
    :attr:`us`; when the body raises the annotation still closes, but
    nothing is observed (failed attempts have counters of their own).
    The annotation is made only while a profiler session records.
    """

    __slots__ = ("_name", "_args", "_hist", "_annotation", "_t0", "us")

    def __init__(self, name: str, hist: Histogram, args: Dict[str, Any]):
        self._name = name
        self._args = args
        self._hist = hist
        self._annotation: Optional[_Annotation] = None
        self.us: Optional[float] = None

    def __enter__(self) -> "TimedSpan":
        if _Annotation.is_enabled():
            self._annotation = _Annotation(self._name, **self._args)
            self._annotation.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter_ns()
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        if exc_type is None:
            self.us = (t1 - self._t0) / 1000.0
            self._hist.observe(self.us)
        return False


class Observability:
    """Metrics registry + tracer, shared down one engine/manager stack."""

    def __init__(self, *, trace: bool = False):
        self.metrics = Metrics()
        self.tracer = Tracer(enabled=trace)
        self._span_hists: Dict[str, Histogram] = {}

    def span(self, name: str, **args: Any) -> TimedSpan:
        """Time a phase into the histogram ``<name>_us``; ``args`` (a
        request id, a batch's rows) ride the profiler annotation."""
        hist = self._span_hists.get(name)
        if hist is None:
            hist = self._span_hists[name] = self.metrics.histogram(
                name + "_us")
        return TimedSpan(name, hist, args)


__all__ = [
    "ENGINE_TRACK",
    "NULL_TRACER",
    "Counter",
    "Gauge",
    "Histogram",
    "Metrics",
    "Observability",
    "Span",
    "Tracer",
    "TimedSpan",
]
