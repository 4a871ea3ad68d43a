"""Tracing & profiling ranges.

Reference analogue: NVTX ranges on the hot path (NvtxRange /
NvtxWithMetrics couple a range with a SQLMetric nanosecond accumulator, see
SURVEY §5).  TPU equivalent: ``jax.profiler.TraceAnnotation`` so ranges show
in xprof, with the same metric coupling so wall time lands in the engine's
metrics too.

``trace_range`` is ONE exception-safe path: the optional profiler
annotation, the optional metric coupling, and the telemetry span-stack
push/pop (re-entrant, thread-local — a re-entered range name never
double counts) all ride the same try/finally, enabled or not.

The host's ranges stop at a program's dispatch.  Inside a program the
names are ``device_phase`` scopes: they land in every op's metadata
(``jit(agg_batch)/lexsort/while/body/gather``), where
``telemetry/device_trace.py`` reads them out of a profiler trace."""
from __future__ import annotations

import time
from contextlib import contextmanager

_ENABLED = False

_spans = None  # telemetry.spans module, bound at first use

#: the phases a program names inside itself (docs/observability.md has
#: what each covers); an operator's scope is its host span's name
DEVICE_PHASES = (
    "lexsort", "reorder", "segments", "gather.partitionOrder",
    "agg.prologue",
    "join.probe", "join.emitCounts", "join.expandSearch",
    "join.expandGather", "join.pairRows", "join.condition",
    "shuffle.hashPids", "shuffle.packedBuild", "shuffle.packedSlice",
    "shuffle.trim", "strings.match",
)

#: ``segment.reduce_sorted``'s reads of one width stand under
#: ``readTier.<rows>``, inside ``segments``.  No phase: it takes no second
#: out of ``segments``; ``telemetry/device_trace.py --by tier`` counts by it
READ_TIER = "readTier."
#: ``ops/kernels/gather.take_rows``' reads, under their caller's phase: one
#: stacked gather of ``<k>`` 32-bit words, or a part read by a gather of
#: its own (a float64, a string's bytes).  No phases either
READ_WORDS = "readWords."
READ_OWN = "readOwn"
#: the scopes ``--by tier`` counts by
READ_TAGS = (READ_TIER, READ_WORDS, READ_OWN)

#: the span a request opens first (session.py, parallel/runner.py)
QUERY_SPAN = "Query"

#: the directory of the last profiler session that was open when a
#: ``Query`` span opened under ``sql.trace.enabled`` (None: none ever)
_profile_dir = None


def enable(flag: bool = True) -> None:
    global _ENABLED
    _ENABLED = flag


@contextmanager
def device_phase(name: str):
    """``jax.named_scope`` for the ops traced inside it (a ``with``, or
    a decorator of a kernel body): one of ``DEVICE_PHASES``, or an
    operator's host-span name where a program is composed of several
    operators' bodies.  Runs at trace time only (nothing at dispatch),
    and JAX strips debug info from the persistent cache's key, so no
    program's key changes."""
    import jax

    with jax.named_scope(name):
        yield


def note_profile_dir() -> None:
    """``trace_range`` calls it where a ``Query`` span opens, under
    ``_ENABLED`` alone (off, the request path reads nothing more):
    remember where the open profiler session, if any, will write.  A
    query outside any session leaves the last one's directory noted: a
    benchmark's window goes on after its profiler stops."""
    global _profile_dir
    import jax._src.profiler as _jax_profiler

    # private, as kernel_cache's ``_jfn._cache_size()`` is:
    # tests/test_device_phases.py fails loudly when JAX moves it
    state = getattr(_jax_profiler, "_profile_state", None)
    log_dir = getattr(state, "log_dir", None)
    if log_dir is not None:
        _profile_dir = str(log_dir)


def last_profile_dir():
    """Where the last profiler session that was open around a traced
    query writes its ``plugins/profile/<run>/*.xplane.pb``; None with
    tracing off, or where no query ran inside a session."""
    return _profile_dir if _ENABLED else None


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

        if name == QUERY_SPAN:
            note_profile_dir()
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
