"""Tracing & profiling ranges.

Reference analogue: NVTX ranges on the hot path (NvtxRange /
NvtxWithMetrics couple a range with a SQLMetric nanosecond accumulator, see
SURVEY §5).  TPU equivalent: ``jax.profiler.TraceAnnotation`` so ranges show
in xprof, with the same metric coupling so wall time lands in the engine's
metrics too.

``trace_range`` is ONE exception-safe path: the optional profiler
annotation, the optional metric coupling, and the telemetry span-stack
push/pop (re-entrant, thread-local — a re-entered range name never
double counts) all ride the same try/finally, enabled or not."""
from __future__ import annotations

import time
from contextlib import contextmanager

_ENABLED = False

_spans = None  # telemetry.spans module, bound at first use


def enable(flag: bool = True) -> None:
    global _ENABLED
    _ENABLED = flag


def _telemetry_spans():
    global _spans
    if _spans is None:
        from ..telemetry import spans as _mod

        _spans = _mod
    return _spans


@contextmanager
def trace_range(name: str, metric=None, **annotation):
    """A named profiler range; if ``metric`` is given, elapsed nanoseconds
    are added to it (reference: NvtxWithMetrics.scala:44).  The range is
    also pushed on the active telemetry span stack, so its wall
    aggregates under the current span (no-op when telemetry is off).
    ``annotation`` is metadata for the profiler's event alone (the
    event keeps the plain ``name``).

    Never hold one open across a ``yield``: the consumer's time would
    be charged to the range (:func:`trace_steps` drives a generator
    with the range closed at every hand-over)."""
    spans = _telemetry_spans()
    start = time.perf_counter_ns()
    profiled = None
    if _ENABLED:
        import jax.profiler

        profiled = jax.profiler.TraceAnnotation(name, **annotation)
        profiled.__enter__()
    token = spans.push_range(name)
    try:
        yield
    finally:
        elapsed = time.perf_counter_ns() - start
        spans.pop_range(token, elapsed)
        if profiled is not None:
            profiled.__exit__(None, None, None)
        if metric is not None:
            metric.add(elapsed)


_DONE = object()


def trace_steps(name: str, steps, metric=None):
    """Drive the iterator ``steps`` with each step — the work between
    two of its yields — inside ``trace_range(name)``, closed before the
    item is handed on."""
    steps = iter(steps)
    try:
        while True:
            with trace_range(name, metric):
                item = next(steps, _DONE)
            if item is _DONE:
                return
            yield item
    finally:
        close = getattr(steps, "close", None)
        if close is not None:  # an abandoned drain closes its source
            close()
