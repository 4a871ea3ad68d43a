"""Hierarchical query spans — query -> stage -> exec -> attempt.

Reference analogue: NvtxWithMetrics coupling every hot-path range with
a SQLMetric, widened into an explicit span tree so a query profile can
say WHERE wall time went (per exec, per stage, per recovery attempt)
instead of only how much there was in total.

Binding discipline: :meth:`QueryTelemetry.begin` binds the query's
telemetry to the CREATING thread only.  Worker threads (task pools,
prefetch producers, stage watchdogs, multiprocess drains, samplers)
never inherit thread-locals, so every thread-spawn site must
:func:`capture` the binding before spawning and run the worker body
under :func:`attached` (or wrap the target with :func:`bound`) — the
same discipline a query-governor ``activate(current_query())`` binding
uses, and composable with one when a ``governor`` package is present
(capture both, attach both).  The ``thread-capture`` analysis rule
enforces the capture at the AST level for every thread-spawn site in
the package.

Cost model: with ``telemetry.enabled=false`` nothing here is reachable
beyond a thread-local ``getattr`` returning ``None`` — no spans, no
ring, no sink, no sampler.
"""
from __future__ import annotations

import itertools
import os
import threading
import time
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

_tl = threading.local()

_query_counter = itertools.count(1)


# ==========================================================================
# Span
# ==========================================================================
class Span:
    """One node of the span tree.  Counters are additive and
    thread-safe (pool workers of one exec update concurrently)."""

    __slots__ = ("span_id", "name", "kind", "parent_id", "start_ns",
                 "end_ns", "attrs", "rows", "batches", "bytes",
                 "device_sync_ns", "range_ns", "children", "_lock")

    def __init__(self, span_id: int, name: str, kind: str,
                 parent_id: Optional[int] = None, attrs: Optional[Dict] = None):
        self.span_id = span_id
        self.name = name
        self.kind = kind
        self.parent_id = parent_id
        self.start_ns = time.perf_counter_ns()
        self.end_ns: Optional[int] = None
        self.attrs = dict(attrs or {})
        self.rows = 0
        self.batches = 0
        self.bytes = 0
        self.device_sync_ns = 0
        #: aggregated trace_range wall per range name (outermost
        #: occurrence only — re-entrant ranges do not double count)
        self.range_ns: Dict[str, int] = {}
        self.children: List["Span"] = []
        self._lock = threading.Lock()

    # ------------------------------------------------------------------
    def add(self, rows: int = 0, batches: int = 0, nbytes: int = 0,
            device_sync_ns: int = 0) -> None:
        with self._lock:
            self.rows += rows
            self.batches += batches
            self.bytes += nbytes
            self.device_sync_ns += device_sync_ns

    def add_range(self, name: str, elapsed_ns: int) -> None:
        with self._lock:
            self.range_ns[name] = self.range_ns.get(name, 0) + elapsed_ns

    def finish(self) -> None:
        if self.end_ns is None:
            self.end_ns = time.perf_counter_ns()

    @property
    def wall_ns(self) -> int:
        end = self.end_ns if self.end_ns is not None \
            else time.perf_counter_ns()
        return max(0, end - self.start_ns)

    # ------------------------------------------------------------------
    def to_dict(self) -> Dict:
        """Nested plain-dict form (profile rendering / JSON export)."""
        return {
            "name": self.name,
            "kind": self.kind,
            "wall_ns": self.wall_ns,
            "rows": self.rows,
            "batches": self.batches,
            "bytes": self.bytes,
            "device_sync_ns": self.device_sync_ns,
            "ranges": dict(self.range_ns),
            "attrs": dict(self.attrs),
            "children": [c.to_dict() for c in self.children],
        }

    def __repr__(self):  # pragma: no cover
        return f"Span({self.kind}:{self.name}, wall={self.wall_ns}ns)"


# ==========================================================================
# Per-query telemetry
# ==========================================================================
class QueryTelemetry:
    """Everything one query's observability owns: the span tree, the
    event log, and (optionally) the HBM sampler.  Created per query by
    ``ExecContext`` when ``telemetry.enabled`` is on; finished exactly
    once by ``Session._finalize_metrics``."""

    def __init__(self, conf, session=None, query_id: Optional[str] = None):
        from ..config import (TELEMETRY_EVENT_LOG_DIR, TELEMETRY_MAX_EVENTS,
                              TELEMETRY_SAMPLE_HBM_MS)
        from .events import EventLog

        self.query_id = query_id or \
            f"q{os.getpid()}-{next(_query_counter):04d}"
        self._lock = threading.Lock()
        self._next_span_id = itertools.count(1)
        self.root = Span(0, self.query_id, "query")
        self.events = EventLog(
            self.query_id,
            max_events=max(1, conf.get(TELEMETRY_MAX_EVENTS)),
            sink_dir=conf.get(TELEMETRY_EVENT_LOG_DIR) or "")
        #: exec-name -> Span (one span per physical exec name; execs of
        #: the same class share a metrics prefix, so they share a span)
        self._exec_spans: Dict[str, Span] = {}
        self.finished = False
        self.hbm_timeline: List[Tuple[float, int, int]] = []
        self._sampler = None
        sample_ms = conf.get(TELEMETRY_SAMPLE_HBM_MS)
        dm = getattr(session, "device_manager", None) \
            if session is not None else None
        if sample_ms and sample_ms > 0 and dm is not None:
            from .export import HbmSampler

            self._sampler = HbmSampler(dm, sample_ms)

    # ------------------------------------------------------------------
    @classmethod
    def begin(cls, conf, session=None) -> Optional["QueryTelemetry"]:
        """Per-query entry point: returns an ACTIVATED telemetry object
        when ``telemetry.enabled`` is on, else clears any stale binding
        left by a previous query and returns None (a disabled query
        must never append late events to a finished predecessor)."""
        from ..config import TELEMETRY_ENABLED

        if not conf.get(TELEMETRY_ENABLED):
            deactivate()
            return None
        tele = cls(conf, session=session)
        activate(tele)
        tele.events.emit("query_begin", query=tele.query_id)
        if tele._sampler is not None:
            tele._sampler.start()
        return tele

    # ------------------------------------------------------------------
    def start_span(self, name: str, kind: str = "span",
                   parent: Optional[Span] = None,
                   attrs: Optional[Dict] = None) -> Span:
        parent = parent or current_span() or self.root
        sp = Span(next(self._next_span_id), name, kind,
                  parent_id=parent.span_id, attrs=attrs)
        with self._lock:
            parent.children.append(sp)
        return sp

    def exec_span(self, name: str) -> Span:
        """The (deduplicated) exec-kind span for one physical exec
        name; wall/rows/batches are back-filled from the exec's metrics
        at :meth:`finish` so the hot path never touches the span."""
        with self._lock:
            sp = self._exec_spans.get(name)
            if sp is None:
                parent = current_span() or self.root
                sp = Span(next(self._next_span_id), name, "exec",
                          parent_id=parent.span_id)
                parent.children.append(sp)
                self._exec_spans[name] = sp
            return sp

    # ------------------------------------------------------------------
    def _fill_exec_spans(self, metrics: Dict[str, int]) -> None:
        """Back-fill exec spans from the query metric snapshot (the
        per-exec registries use a ``<ExecName>.`` prefix)."""
        for name, sp in self._exec_spans.items():
            prefix = name + "."
            sp.rows = int(metrics.get(prefix + "numOutputRows", sp.rows))
            sp.batches = int(
                metrics.get(prefix + "numOutputBatches", sp.batches))
            wall = metrics.get(prefix + "totalTime")
            if wall is not None:
                sp.end_ns = sp.start_ns + int(wall)
            sync = metrics.get(prefix + "deviceSyncTime")
            if sync is not None:
                sp.device_sync_ns = int(sync)
            sp.finish()

    def finish(self, metrics: Optional[Dict[str, int]] = None,
               plan=None):
        """End the query span, stop the sampler, emit ``query_end`` and
        build the :class:`~.profile.QueryProfile`.  Idempotent (the
        first call wins); safe to call with the query binding still
        active — late events (a degrade decision taken above this
        layer) keep landing in the same ring/sink."""
        from .profile import QueryProfile

        if self.finished:
            return None
        self.finished = True
        if self._sampler is not None:
            self._sampler.stop()
            self.hbm_timeline = self._sampler.timeline()
        metrics = dict(metrics or {})
        self._fill_exec_spans(metrics)
        self.root.finish()
        self.events.emit("query_end", query=self.query_id,
                         wall_ms=round(self.root.wall_ns / 1e6, 3))
        return QueryProfile(self, metrics=metrics, plan=plan)


# ==========================================================================
# Thread-local binding
# ==========================================================================
def activate(tele: QueryTelemetry) -> None:
    _tl.telemetry = tele
    _tl.stack = [tele.root]
    _tl.ranges = []


def deactivate() -> None:
    _tl.telemetry = None
    _tl.stack = None
    _tl.ranges = None


def current() -> Optional[QueryTelemetry]:
    return getattr(_tl, "telemetry", None)


def current_span() -> Optional[Span]:
    stack = getattr(_tl, "stack", None)
    return stack[-1] if stack else None


# ----- worker-thread propagation ------------------------------------------
def capture():
    """Capture the caller's per-query execution binding for a worker
    thread: the telemetry binding PLUS the scheduler's cancel token
    and per-query scoped fault/OOM injectors (all thread-local), so
    every pool/watchdog/prefetch spawn site propagates cancellation
    and failure isolation for free.  Returns None when nothing is
    bound — attach is then a no-op.  Every thread-spawn site in the
    package must call this BEFORE spawning and bind the worker body
    with :func:`attached`/:func:`bound`."""
    from ..fault import injector as _finj
    from ..memory import retry as _retry
    from ..scheduler import cancel as _cancel

    tele = current()
    token = _cancel.current()
    oom_inj = _retry.get_scoped_injector()
    fault_inj = _finj.get_scoped_fault_injector()
    if tele is None and token is None and oom_inj is None \
            and fault_inj is None:
        return None
    parent = current_span() if tele is not None else None
    return (tele, parent, token, oom_inj, fault_inj)


@contextmanager
def attached(cap):
    """Bind a captured execution context to the current (worker)
    thread for the duration of the block; restores the previous
    binding on exit (re-entrant)."""
    if cap is None:
        yield
        return
    from ..fault import injector as _finj
    from ..memory import retry as _retry
    from ..scheduler import cancel as _cancel

    tele, parent, token, oom_inj, fault_inj = cap
    prev_t = getattr(_tl, "telemetry", None)
    prev_s = getattr(_tl, "stack", None)
    prev_r = getattr(_tl, "ranges", None)
    prev_tok = _cancel.current()
    prev_oom = _retry.get_scoped_injector()
    prev_flt = _finj.get_scoped_fault_injector()
    if tele is not None:
        _tl.telemetry = tele
        _tl.stack = [parent or tele.root]
        _tl.ranges = []
    _cancel.activate(token)
    _retry.bind_scoped_injector(oom_inj)
    _finj.bind_scoped_fault_injector(fault_inj)
    try:
        yield
    finally:
        if tele is not None:
            _tl.telemetry = prev_t
            _tl.stack = prev_s
            _tl.ranges = prev_r
        _cancel.activate(prev_tok)
        _retry.bind_scoped_injector(prev_oom)
        _finj.bind_scoped_fault_injector(prev_flt)


def bound(cap, fn):
    """Wrap ``fn`` so it runs under :func:`attached` — the convenience
    form for ``Thread(target=...)`` / ``pool.map`` call sites."""
    if cap is None:
        return fn

    def _runner(*args, **kwargs):
        with attached(cap):
            return fn(*args, **kwargs)

    return _runner


# ----- scoped spans --------------------------------------------------------
@contextmanager
def span(name: str, kind: str = "span", **attrs):
    """Exception-safe scoped span under the current thread's binding;
    yields None (and costs one thread-local getattr) when telemetry is
    inactive."""
    tele = current()
    if tele is None:
        yield None
        return
    sp = tele.start_span(name, kind, attrs=attrs or None)
    stack = getattr(_tl, "stack", None)
    if stack is None:
        stack = _tl.stack = [tele.root]
    stack.append(sp)
    try:
        yield sp
    finally:
        if stack and stack[-1] is sp:
            stack.pop()
        sp.finish()


# ----- trace_range coupling ------------------------------------------------
def push_range(name: str):
    """Range-stack push for ``utils.tracing.trace_range`` (re-entrant,
    thread-local): returns an opaque token, or None when inactive.  A
    finished query's binding lingers on its thread until the next
    query begins: a range opened in between (``Query``, ``Plan``)
    belongs to neither query's table."""
    tele = current()
    if tele is None or tele.finished:
        return None
    st = getattr(_tl, "ranges", None)
    if st is None:
        st = _tl.ranges = []
    reentrant = name in st
    st.append(name)
    return (name, reentrant)


def pop_range(token, elapsed_ns: int) -> None:
    """Range-stack pop: attributes the elapsed wall of the OUTERMOST
    occurrence of a range name to the current span (re-entrant ranges
    never double count)."""
    if token is None:
        return
    st = getattr(_tl, "ranges", None)
    if st:
        st.pop()
    name, reentrant = token
    if reentrant:
        return
    sp = current_span()
    if sp is None:
        tele = current()
        sp = tele.root if tele is not None else None
    if sp is not None:
        sp.add_range(name, elapsed_ns)


def register_exec(node) -> None:
    """exec/base.py hook: one exec-kind span per physical exec name
    under the active query (no-op when telemetry is inactive)."""
    tele = current()
    if tele is not None:
        tele.exec_span(node.name)
