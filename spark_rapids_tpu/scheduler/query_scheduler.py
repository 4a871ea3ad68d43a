"""QueryScheduler — bounded admission, multi-tenant fair share,
dispatch, deadlines, preemption and per-query failure isolation for
concurrent queries.

Reference analogue: the admission/memory-arbitration layer Theseus-
style accelerator engines put in front of scarce device memory (see
PAPERS.md) — here built on the existing DeviceManager budget, retry
framework, degradation ladder and telemetry events, with the
multi-tenant QoS tier of "Accelerating Presto with GPUs" on top
(:mod:`.qos`).

Model:

* ``Session.submit(plan, priority, tenant=...)`` -> :class:`QueryHandle`
  — at most ``scheduler.maxConcurrent`` queries run concurrently (one
  daemon worker thread each); queued queries wait in per-tenant queues
  drained by deficit-weighted fair share with priority aging
  (:mod:`.qos`).  A submit past ``scheduler.maxQueued`` — or a queued
  query not dispatched within ``scheduler.queueTimeoutMs`` — is shed
  with :class:`QueryRejected` plus an ``admission_reject`` event
  carrying the queue depth and queue wait.
* While the :class:`~.qos.OverloadMonitor` declares overload (queue-wait
  p95 or arena pressure past ``scheduler.overload.*`` thresholds), new
  submissions below ``scheduler.overload.shedBelowPriority`` are shed
  with :class:`~.qos.TpuOverloaded` carrying a ``retry_after_ms``
  backoff hint (``overload_shed`` event).
* Each dispatched query holds an HBM *reservation* of
  ``scheduler.reservationFraction`` (or its tenant's ``hbmFraction``)
  x the DeviceManager arena for its lifetime
  (``DeviceManager.try_reserve``): dispatch waits until the reservation
  fits, so the sum of running reservations never exceeds the arena.
  When nothing is running the head query dispatches even if its
  reservation cannot be charged — forward progress is never
  reservation-deadlocked.
* **Checkpoint-backed preemption** — a strictly higher-priority queued
  query blocked on a slot or its reservation cooperatively cancels the
  lowest-priority running victim (the same zero-leak CancelToken
  unwind as a terminal cancel), requeues it with its aging credit
  intact, and on re-dispatch the recovery store (``recovery.enabled``)
  resumes the victim from its completed exchange checkpoints
  (``preempt_victim`` / ``preempt_resume`` events); every preemption
  is charged against the victim's ``fault.maxTotalAttempts`` budget.
* Cancellation is cooperative: ``handle.cancel()`` (or the
  ``scheduler.queryTimeoutMs`` deadline, or an injected ``cancel``
  fault) trips the query's :class:`~.cancel.CancelToken`; every
  operator checkpoint polls it, and the worker unwinds — semaphore
  permits released, upload caches dropped, shuffle slots freed by the
  normal query-end path, a terminal ``query_cancelled`` event emitted.
* Per-query failure isolation: scheduled queries run with PRIVATE
  fault/OOM injectors (thread-local, see ``ExecContext``), and a query
  that exhausts its retry/ladder budget trips a per-query circuit
  breaker onto the CPU-exec plan — without disarming the process-wide
  injector slots or writing the global fault counters, so concurrent
  queries stay on the TPU path unpoisoned.
"""
from __future__ import annotations

import itertools
import logging
import threading
import time
import weakref
from typing import Dict, List, Optional

from .cancel import CancelToken, TpuQueryCancelled
from .qos import (DEFAULT_TENANT, OverloadMonitor,  # noqa: F401
                  QueryRejected, TenantRegistry, TpuOverloaded)

log = logging.getLogger(__name__)

#: all live schedulers in the process — the test harness shuts them
#: down between tests (conftest) so no scheduler thread outlives its
#: test
_LIVE: "weakref.WeakSet[QueryScheduler]" = weakref.WeakSet()


def shutdown_all() -> None:
    """Shut down every live scheduler (test-harness hook)."""
    for sched in list(_LIVE):
        try:
            sched.shutdown()
        except Exception:  # noqa: BLE001 — teardown must not raise
            pass


class QueryStatus:
    QUEUED = "queued"
    RUNNING = "running"
    FINISHED = "finished"
    FAILED = "failed"
    CANCELLED = "cancelled"
    REJECTED = "rejected"


#: terminal status -> tenant counter (QUEUED = a preemption requeue)
_DONE_COUNTER = {QueryStatus.FINISHED: "finished",
                 QueryStatus.FAILED: "failed",
                 QueryStatus.CANCELLED: "cancelled",
                 QueryStatus.REJECTED: "cancelled",
                 QueryStatus.QUEUED: "preempted"}


class QueryHandle:
    """Caller-side handle of one submitted query."""

    def __init__(self, scheduler: "QueryScheduler", query_id: int,
                 plan, priority: int, tenant: str = DEFAULT_TENANT,
                 recovery=None, deadline_ms: Optional[int] = None):
        self._scheduler = scheduler
        self.query_id = query_id
        self.plan = plan
        self.priority = priority
        self.tenant = tenant
        #: caller-provided RecoveryManager (streaming micro-batches
        #: bring their own stream-scoped manager) — None means the
        #: session builds the default per-query one
        self.recovery = recovery
        #: per-query deadline override (streaming batchDeadlineMs);
        #: None/0 falls back to scheduler.queryTimeoutMs
        self.deadline_ms = deadline_ms
        self.token = CancelToken(query_id)
        self._lock = threading.Lock()
        self._done = threading.Event()
        self._status = QueryStatus.QUEUED
        self._result = None
        self._error: Optional[BaseException] = None
        self._queued_at = time.monotonic()
        #: first enqueue stamp — survives preemption requeues, so a
        #: victim keeps its priority-aging credit
        self._first_queued_at = self._queued_at
        #: times this query was preempted; charged against the
        #: fault.maxTotalAttempts budget
        self.preemptions = 0
        self._user_cancel = False
        #: preemptor's query id while an eviction is in flight
        self._preempted_by: Optional[int] = None
        #: event rings of earlier, preempted attempts (events())
        self._prior_events: List[Dict] = []
        #: per-query attribution (the session's last_metrics /
        #: last_profile are last-writer-wins under concurrency)
        self.metrics: Dict = {}
        self.profile = None
        #: "tpu", "cpu" (the circuit-breaker rung) or "cache" (served
        #: from the serving result cache before admission) — which path
        #: produced the result
        self.exec_path: Optional[str] = None
        #: serving-cache identity captured at submit time (serving/);
        #: the worker stores the result under it at success
        self._serving_key = None
        self._ctx = None  # the native attempt's ExecContext

    # ----- caller API ------------------------------------------------------
    def result(self, timeout: Optional[float] = None):
        """Block for the result; raises the query's terminal error
        (``TpuQueryCancelled`` / ``QueryRejected`` / the failure)."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"query {self.query_id} not done after {timeout}s "
                f"(status={self.status()})")
        if self._error is not None:
            raise self._error
        return self._result

    def cancel(self, reason: str = "cancelled by caller") -> bool:
        """Trip the query's cancel token; a queued query is removed
        immediately, a running one unwinds at its next checkpoint.
        Returns True on the first effective cancel."""
        self._user_cancel = True  # a preemption requeue must not undo it
        first = self.token.cancel(reason)
        self._scheduler._on_cancel(self, reason)
        return first

    def status(self) -> str:
        with self._lock:
            return self._status

    def done(self) -> bool:
        return self._done.is_set()

    def events(self) -> List[Dict]:
        """This query's telemetry event ring (empty when telemetry was
        disabled); for a preempted query the rings of its earlier
        attempts come first, so preempt_victim events stay visible."""
        out = list(self._prior_events)
        tele = getattr(self._ctx, "telemetry", None)
        if tele is not None and tele.events is not None:
            out.extend(tele.events.snapshot())
        return out

    # ----- scheduler-side transitions --------------------------------------
    def _mark_running(self) -> None:
        with self._lock:
            if not self._done.is_set():
                self._status = QueryStatus.RUNNING

    def _finish(self, status: str, result=None,
                error: Optional[BaseException] = None) -> bool:
        with self._lock:
            if self._done.is_set():
                return False
            self._status = status
            self._result = result
            self._error = error
            self._done.set()
            return True

    def _reset_for_requeue(self) -> None:
        """Preemption requeue: back to QUEUED with a FRESH cancel token
        (the tripped one is spent) and a fresh queue-timeout clock —
        but the original first-queued stamp, so the victim keeps its
        aging credit and re-dispatches ahead of equal-priority
        newcomers."""
        with self._lock:
            self._status = QueryStatus.QUEUED
        self.token = CancelToken(self.query_id)
        self._queued_at = time.monotonic()


class QueryScheduler:
    """One per Session (created lazily by ``Session.submit``); owns a
    dispatcher thread, an overload-monitor thread (when the
    ``scheduler.overload.*`` thresholds are set), plus one daemon
    worker thread per running query."""

    def __init__(self, session):
        from ..config import (FAULT_DEGRADE_ENABLED,
                              SCHEDULER_MAX_CONCURRENT,
                              SCHEDULER_MAX_QUEUED,
                              SCHEDULER_OVERLOAD_SHED_BELOW_PRIORITY,
                              SCHEDULER_PREEMPTION_ENABLED,
                              SCHEDULER_PRIORITY_AGING_MS,
                              SCHEDULER_QUERY_TIMEOUT_MS,
                              SCHEDULER_QUEUE_TIMEOUT_MS,
                              SCHEDULER_RESERVATION_FRACTION)
        from ..telemetry import spans as tspans

        self.session = session
        conf = session.conf
        self.max_concurrent = max(1, conf.get(SCHEDULER_MAX_CONCURRENT))
        self.max_queued = max(0, conf.get(SCHEDULER_MAX_QUEUED))
        self.queue_timeout_ms = conf.get(SCHEDULER_QUEUE_TIMEOUT_MS)
        self.query_timeout_ms = conf.get(SCHEDULER_QUERY_TIMEOUT_MS)
        self.aging_ms = conf.get(SCHEDULER_PRIORITY_AGING_MS)
        self.preemption_enabled = conf.get(SCHEDULER_PREEMPTION_ENABLED)
        self.shed_below_priority = conf.get(
            SCHEDULER_OVERLOAD_SHED_BELOW_PRIORITY)
        self._dm = session.device_manager
        frac = conf.get(SCHEDULER_RESERVATION_FRACTION)
        self.reservation_bytes = 0
        if self._dm is not None and frac > 0:
            self.reservation_bytes = min(
                int(frac * self._dm.arena_bytes), self._dm.arena_bytes)
        self._degrade_enabled = (self._dm is not None
                                 and conf.get(FAULT_DEGRADE_ENABLED))
        self._cv = threading.Condition()
        self.qos = TenantRegistry(conf)
        self.overload = OverloadMonitor(conf, self._queue_waits_ms,
                                        self._arena_pressure)
        self._next_qid = itertools.count(1)
        self._n_active = 0
        self._running: set = set()  # running QueryHandles
        #: the victim of an in-flight eviction — one at a time, so a
        #: burst of high-tier arrivals cannot cascade-cancel the world
        self._preempt_inflight: Optional[QueryHandle] = None
        #: worker-thread ident -> [currently held reservation bytes];
        #: the mutable cell lets AQE shrink a running query's charge
        #: (rebase_reservation) while the worker's finally still
        #: releases exactly what remains held
        self._reservations: Dict[int, List[int]] = {}
        self._workers: set = set()  # live worker threads
        self._shutdown = False
        _LIVE.add(self)
        # the dispatcher inherits the creator's (usually empty)
        # execution binding via the telemetry capture() discipline
        self._dispatcher = threading.Thread(
            target=tspans.bound(tspans.capture(), self._dispatch_loop),
            daemon=True, name="query-scheduler")
        self._dispatcher.start()
        self.overload.start()

    # ----- submission ------------------------------------------------------
    def submit(self, plan, priority: int = 0,
               tenant: str = DEFAULT_TENANT, *, recovery=None,
               deadline_ms: Optional[int] = None) -> QueryHandle:
        from ..telemetry.events import emit_event

        # serving result-cache lookup BEFORE admission (serving/):
        # fingerprinting and the validated disk read happen outside the
        # scheduler lock, and a hit completes the handle immediately —
        # it never queues, never occupies a slot and is never shed.
        # Callers that bring their own RecoveryManager (streaming
        # micro-batches) bypass the cache: their execution must write
        # checkpoints for the next incremental tick to merge from.
        cached = None
        serving_key = None
        serving = self.session.serving_if_enabled()
        if serving is not None and recovery is None:
            serving_key = serving.results.fingerprint(plan)
            if serving_key is not None:
                cached = serving.results.lookup(serving_key)
        with self._cv:
            if self._shutdown:
                raise RuntimeError("QueryScheduler is shut down")
            if cached is not None:
                handle = QueryHandle(self, next(self._next_qid), plan,
                                     priority, tenant, recovery=recovery,
                                     deadline_ms=deadline_ms)
                handle.exec_path = "cache"
                self.qos.count_cache_hit_locked(tenant)
                handle._finish(QueryStatus.FINISHED, result=cached)
                return handle
            self._maybe_shed_overload_locked(priority, tenant)
            queued = self.qos.queued_count_locked()
            if queued >= self.max_queued \
                    and self._n_active >= self.max_concurrent:
                now = time.monotonic()
                oldest = self.qos.earliest_queued_at_locked()
                head_wait = round((now - oldest) * 1000.0, 1) \
                    if oldest is not None else 0.0
                emit_event("admission_reject", source="scheduler",
                           reason="queue_full", queued=queued,
                           running=self._n_active,
                           queue_depth=queued,
                           queue_wait_ms=head_wait, tenant=tenant,
                           max_queued=self.max_queued,
                           max_concurrent=self.max_concurrent)
                raise QueryRejected(
                    f"scheduler queue full ({self._n_active} running / "
                    f"{queued} queued; maxConcurrent="
                    f"{self.max_concurrent}, maxQueued="
                    f"{self.max_queued})")
            handle = QueryHandle(self, next(self._next_qid), plan,
                                 priority, tenant, recovery=recovery,
                                 deadline_ms=deadline_ms)
            handle._serving_key = serving_key
            self.qos.enqueue_locked(handle)
            self._cv.notify_all()
        return handle

    def _maybe_shed_overload_locked(self, priority: int,
                                    tenant: str) -> None:
        """Load-shedding decision site: while the OverloadMonitor is
        in overload, a submit below scheduler.overload.shedBelowPriority
        is shed with TpuOverloaded (typed, retryable, carrying the
        retry_after_ms backoff hint) plus an overload_shed event —
        emitted on the submitting thread, where the caller's telemetry
        binding lives."""
        from ..telemetry.events import emit_event

        if not self.overload.enabled:
            return
        if not self.overload.evaluate() \
                or priority >= self.shed_below_priority:
            return
        depth = self.qos.queued_count_locked()
        retry_ms = self.overload.retry_after_ms(depth, self.max_queued)
        self.qos.count_shed_locked(tenant)
        emit_event("overload_shed", source="scheduler", tenant=tenant,
                   priority=priority, queue_depth=depth,
                   retry_after_ms=retry_ms,
                   queue_wait_p95_ms=round(self.overload.wait_p95(), 1))
        raise TpuOverloaded(
            f"scheduler overloaded: priority {priority} submission "
            f"shed (below shedBelowPriority="
            f"{self.shed_below_priority}); retry after {retry_ms}ms",
            retry_after_ms=retry_ms)

    # ----- caller-side cancel hook -----------------------------------------
    def _on_cancel(self, handle: QueryHandle, reason: str) -> None:
        """Remove a still-queued handle immediately; a running one
        unwinds cooperatively at its next checkpoint."""
        with self._cv:
            removed = self.qos.remove_locked(handle)
            if removed:
                self._cv.notify_all()
        if removed:
            handle._finish(QueryStatus.CANCELLED,
                           error=TpuQueryCancelled(reason))

    # ----- overload-monitor inputs ------------------------------------------
    def _queue_waits_ms(self) -> List[float]:
        with self._cv:
            return self.qos.queue_waits_ms_locked(time.monotonic())

    def _arena_pressure(self) -> float:
        dm = self._dm
        if dm is None or dm.arena_bytes <= 0:
            return 0.0
        return dm.allocated_bytes / float(dm.arena_bytes)

    # ----- dispatcher ------------------------------------------------------
    def _dispatch_loop(self) -> None:
        from ..telemetry import spans as tspans

        while True:
            with self._cv:
                handle = cand = None
                reservation = 0
                while handle is None:
                    if self._shutdown:
                        return
                    now = time.monotonic()
                    self._shed_expired_locked(now)
                    if self._n_active < self.max_concurrent:
                        cand = self.qos.pick_locked(now, self.aging_ms)
                        if cand is not None:
                            reservation = \
                                self._reservation_for_locked(cand)
                            if reservation and not self._dm.try_reserve(
                                    reservation):
                                if self._n_active == 0:
                                    # forward-progress guarantee: an
                                    # empty machine always runs the
                                    # head query
                                    reservation = 0
                                else:
                                    self.qos.requeue_front_locked(cand)
                                    self._maybe_preempt_locked(cand)
                                    self._cv.wait(timeout=0.05)
                                    continue
                            handle = cand
                            continue
                    else:
                        # every slot is busy: a strictly higher-tier
                        # queued query may still evict a victim
                        cand = self.qos.peek_locked(now, self.aging_ms)
                        if cand is not None:
                            self._maybe_preempt_locked(cand)
                    self._cv.wait(timeout=self._wait_timeout_locked())
                wait_ms = self.qos.note_dispatch_locked(
                    handle, time.monotonic())
                self.overload.record_wait(wait_ms)
                self._n_active += 1
                self._running.add(handle)
                handle._mark_running()
                worker = threading.Thread(
                    target=tspans.bound(tspans.capture(),
                                        self._worker_main),
                    args=(handle, reservation), daemon=True,
                    name=f"query-worker-{handle.query_id}")
                self._workers.add(worker)
            worker.start()
            # drop the frame locals before sleeping on the condition:
            # a dispatcher idling between queries must not pin the last
            # handle (and through it the query's result/context) after
            # every caller reference is gone
            del worker, handle, cand

    def _reservation_for_locked(self, handle: QueryHandle) -> int:
        """The HBM reservation this query must hold: its tenant's
        hbmFraction of the arena, or the scheduler-wide default."""
        if self._dm is None:
            return 0
        frac = self.qos.get_locked(handle.tenant).hbm_fraction
        if frac <= 0:
            return self.reservation_bytes
        return min(int(frac * self._dm.arena_bytes),
                   self._dm.arena_bytes)

    def _maybe_preempt_locked(self, cand: QueryHandle) -> None:
        """Checkpoint-backed preemption decision: a strictly
        higher-priority candidate blocked on a slot or its HBM
        reservation evicts the lowest-priority running victim by
        tripping its CancelToken — the victim unwinds through the
        normal zero-leak cancellation path and ``_requeue_preempted``
        puts it back in its tenant queue.  The ``preempt_victim``
        event is emitted there, on the victim's own worker thread,
        where its telemetry binding (and event ring) lives — the
        dispatcher thread has no query binding
        (the decision-event analysis rule allowlists this site for
        that reason)."""
        if not self.preemption_enabled:
            return
        if self._preempt_inflight is not None:
            return  # one eviction at a time — no preemption cascades
        victims = [h for h in self._running
                   if h.priority < cand.priority]
        if not victims:
            return
        victim = min(victims, key=lambda h: (h.priority, h.query_id))
        victim._preempted_by = cand.query_id
        if not victim.token.cancel(
                f"preempted by query {cand.query_id} (priority "
                f"{cand.priority} > {victim.priority})"):
            victim._preempted_by = None  # already cancelled elsewhere
            return
        self._preempt_inflight = victim
        log.info("query %d (priority %d) preempting query %d "
                 "(priority %d)", cand.query_id, cand.priority,
                 victim.query_id, victim.priority)

    def _wait_timeout_locked(self) -> Optional[float]:
        """How long the dispatcher may sleep: until the earliest
        queued entry would exceed its queue timeout (None = until
        notified)."""
        earliest = self.qos.earliest_queued_at_locked()
        if self.queue_timeout_ms <= 0 or earliest is None:
            return None
        horizon = self.queue_timeout_ms / 1000.0
        return max(0.01, earliest + horizon - time.monotonic())

    def _shed_expired_locked(self, now: float) -> None:
        if self.queue_timeout_ms <= 0:
            return
        horizon = self.queue_timeout_ms / 1000.0
        for h in self.qos.all_queued_locked():
            if h._done.is_set():
                self.qos.remove_locked(h)
            elif now - h._queued_at >= horizon:
                self.qos.remove_locked(h)
                self._reject_queued(h, "queue_timeout")

    def _reject_queued(self, handle: QueryHandle, why: str) -> None:
        from ..telemetry.events import emit_event

        wait_ms = round(
            (time.monotonic() - handle._queued_at) * 1000.0, 1)
        emit_event("admission_reject", source="scheduler", reason=why,
                   query_id=handle.query_id, tenant=handle.tenant,
                   queue_depth=self.qos.queued_count_locked(),
                   queue_wait_ms=wait_ms,
                   queue_timeout_ms=self.queue_timeout_ms)
        log.warning("query %d shed from the scheduler queue (%s after "
                    "%sms)", handle.query_id, why, wait_ms)
        handle._finish(QueryStatus.REJECTED, error=QueryRejected(
            f"query {handle.query_id} shed: {why} (queueTimeoutMs="
            f"{self.queue_timeout_ms})"))

    # ----- worker ----------------------------------------------------------
    def _worker_main(self, handle: QueryHandle,
                     reservation: int) -> None:
        from ..fault.errors import TpuFaultError
        from ..fault.injector import bind_scoped_fault_injector
        from ..memory.retry import bind_scoped_injector
        from ..telemetry import spans as tspans
        from ..telemetry.events import emit_event
        from . import cancel as _cancel

        token = handle.token
        timeout_ms = handle.deadline_ms \
            if handle.deadline_ms and handle.deadline_ms > 0 \
            else self.query_timeout_ms
        if timeout_ms and timeout_ms > 0:
            token.deadline = time.monotonic() + timeout_ms / 1000.0
        _cancel.activate(token)
        holder = [reservation]
        with self._cv:
            self._reservations[threading.get_ident()] = holder
        sink: Dict = {}
        try:
            try:
                out = self.session._execute_native(
                    handle.plan, scheduled=True, cancel_token=token,
                    ctx_sink=sink, recovery=handle.recovery)
                handle.exec_path = "tpu"
                self._store_serving_result(handle, out)
                self._attribute(handle, sink)
                if handle.preemptions:
                    # work-preserving resume evidence: the recovery
                    # counters say how many stages were skipped
                    emit_event(
                        "preempt_resume", query_id=handle.query_id,
                        tenant=handle.tenant,
                        preemptions=handle.preemptions,
                        stages_resumed=handle.metrics.get(
                            "recovery.numStagesResumed", 0))
                handle._finish(QueryStatus.FINISHED, result=out)
            except TpuQueryCancelled as e:
                if handle._preempted_by is not None \
                        and not handle._user_cancel \
                        and not self._shutdown:
                    self._requeue_preempted(handle, sink, e)
                else:
                    self._unwind_cancelled(handle, sink, e)
            except TpuFaultError as e:
                if not self._degrade_enabled:
                    self._attribute(handle, sink)
                    handle._finish(QueryStatus.FAILED, error=e)
                else:
                    try:
                        self._run_cpu_fallback(handle, e, sink)
                    except TpuQueryCancelled as e2:
                        self._unwind_cancelled(handle, sink, e2)
        except BaseException as e:  # noqa: BLE001 — worker must not die silent
            self._attribute(handle, sink)
            handle._finish(QueryStatus.FAILED, error=e)
        finally:
            # the worker thread dies with the query, but unbinding
            # keeps the thread-local discipline explicit
            _cancel.deactivate()
            bind_scoped_injector(None)
            bind_scoped_fault_injector(None)
            tspans.deactivate()
            if self._dm is not None:
                # any device hold still on this thread dies with it —
                # the semaphore can never get a dead thread's permit
                # back, so the worker's last act is to drop its own
                self._dm.semaphore.release_task()
            with self._cv:
                held = holder[0]
                holder[0] = 0
                self._reservations.pop(threading.get_ident(), None)
            if held and self._dm is not None:
                self._dm.release_reservation(held)
            with self._cv:
                self._n_active -= 1
                self._running.discard(handle)
                self._workers.discard(threading.current_thread())
                if self._preempt_inflight is handle:
                    self._preempt_inflight = None
                self.qos.note_done_locked(
                    handle, _DONE_COUNTER.get(handle.status()))
                self._cv.notify_all()

    def _store_serving_result(self, handle: QueryHandle, out) -> None:
        """Store-at-success hook of the serving result cache: the
        fingerprint captured at submit time is re-validated against a
        FRESH stat of the file material inside ``store_result``, so a
        source rewritten mid-flight is never cached under the stale
        pre-execution identity.  Never raises (the cache fails open)."""
        key = handle._serving_key
        if key is None:
            return
        serving = self.session.serving_if_enabled()
        if serving is not None:
            serving.results.store_result(key, out)

    # ----- preemption (victim side) -----------------------------------------
    def _requeue_preempted(self, handle: QueryHandle, sink: Dict,
                           exc: TpuQueryCancelled) -> None:
        """Victim side of checkpoint-backed preemption: the same
        zero-leak unwind as a terminal cancel (permits, upload caches
        — the normal query-end path already freed shuffle slots and
        finalized metrics), then back into the tenant queue instead of
        a terminal CANCELLED.  Emits ``preempt_victim`` from the
        victim's own telemetry binding, preserves the attempt's event
        ring on the handle, and charges the preemption against the
        victim's ``fault.maxTotalAttempts`` budget."""
        from ..config import FAULT_MAX_TOTAL_ATTEMPTS
        from ..telemetry.events import emit_event

        handle.preemptions += 1
        limit = self.session.conf.get(FAULT_MAX_TOTAL_ATTEMPTS)
        emit_event("preempt_victim", query_id=handle.query_id,
                   by_query=handle._preempted_by, tenant=handle.tenant,
                   preemptions=handle.preemptions, reason=str(exc))
        if self._dm is not None:
            try:
                self._dm.semaphore.release_task()
            except Exception:  # noqa: BLE001 — unwind must not raise
                pass
        phys = sink.get("phys")
        if phys is not None:
            self._drop_upload_caches(phys)
        # cooperative preemption carries no diagnosis: the frames'
        # locals would pin device batches past the zero-leak contract
        exc.__cause__ = None
        exc.__context__ = None
        if limit and handle.preemptions >= limit:
            # terminal — _attribute keeps this attempt's ring on the
            # handle, so no _prior_events copy (it would double up)
            self._fail_preempt_budget(handle, sink, limit)
            return
        # keep the preempted attempt's ring visible on the handle (the
        # resumed attempt begins a fresh one)
        tele = getattr(sink.get("ctx"), "telemetry", None)
        if tele is not None and tele.events is not None:
            handle._prior_events.extend(tele.events.snapshot())
        handle._preempted_by = None
        log.warning("query %d preempted (x%d) — requeued for "
                    "checkpoint-backed resume", handle.query_id,
                    handle.preemptions)
        dead = False
        with self._cv:
            if self._shutdown or handle._user_cancel:
                dead = True
            else:
                handle._reset_for_requeue()
                self.qos.requeue_front_locked(handle)
                self._cv.notify_all()
        if dead:
            handle._finish(QueryStatus.CANCELLED,
                           error=exc.with_traceback(None))

    def _fail_preempt_budget(self, handle: QueryHandle, sink: Dict,
                             limit: int) -> None:
        """Terminal: the victim spent its whole fault.maxTotalAttempts
        budget on preemptions — fail it instead of requeueing forever
        (the same attempt-ceiling contract as stacked retries)."""
        from ..fault.budget import AttemptBudgetExhausted
        from ..telemetry.events import emit_event

        ledger = [{"kind": "preempt", "count": handle.preemptions}]
        emit_event("attempt_budget_exhausted",
                   query_id=handle.query_id, limit=limit,
                   attempts=handle.preemptions, ledger=ledger)
        self._attribute(handle, sink)
        handle._finish(QueryStatus.FAILED, error=AttemptBudgetExhausted(
            f"query {handle.query_id} preempted {handle.preemptions} "
            f"times — fault.maxTotalAttempts ({limit}) exhausted",
            ledger))

    # ----- adaptive reservation rebase --------------------------------------
    def rebase_reservation(self, observed_bytes: int) -> int:
        """SHRINK the calling worker thread's HBM reservation to
        ``observed_bytes`` (never grows — growing mid-flight could
        over-commit the arena) and wake the dispatcher so a queued
        query can use the freed headroom.  Called by the adaptive
        executor once real stage-output sizes replace the admission
        estimate.  Returns the bytes freed (0 when not a worker
        thread, or nothing to free)."""
        if self._dm is None:
            return 0
        target = max(0, int(observed_bytes))
        with self._cv:
            holder = self._reservations.get(threading.get_ident())
            if holder is None or holder[0] <= target:
                return 0
            freed = holder[0] - target
            holder[0] = target
        self._dm.release_reservation(freed)
        with self._cv:
            self._cv.notify_all()
        return freed

    def _attribute(self, handle: QueryHandle, sink: Dict) -> None:
        """Per-query metric/profile attribution from the attempt's own
        ExecContext (stowed by ``Session._finalize_metrics``)."""
        ctx = sink.get("ctx")
        if ctx is None:
            return
        handle._ctx = ctx
        handle.metrics = dict(getattr(ctx, "final_metrics", None)
                              or ctx.metrics.snapshot())
        handle.profile = getattr(ctx, "profile", None)

    def _unwind_cancelled(self, handle: QueryHandle, sink: Dict,
                          exc: TpuQueryCancelled) -> None:
        """Terminal cancellation unwind.  The normal query-end path
        (``_execute_native``'s finally) already finalized metrics,
        released the plan's exec lock and freed this query's shuffle
        slots; what remains query-scoped is the worker's own semaphore
        permits and the plan's cached uploads."""
        from ..telemetry.events import emit_event

        # the query's telemetry binding is still on this thread, so
        # the terminal event lands in ITS event ring
        emit_event("query_cancelled", query_id=handle.query_id,
                   reason=str(exc))
        if self._dm is not None:
            try:
                self._dm.semaphore.release_task()
            except Exception:  # noqa: BLE001 — unwind must not raise
                pass
        phys = sink.get("phys")
        if phys is not None:
            self._drop_upload_caches(phys)
        self._attribute(handle, sink)
        log.warning("query %d cancelled: %s", handle.query_id, exc)
        # drop the traceback/context chain before stowing the error on
        # the handle: cancellation is cooperative (the frames carry no
        # diagnosis) and their locals would pin device batches past the
        # zero-leak unwind contract
        exc.__cause__ = None
        exc.__context__ = None
        handle._finish(QueryStatus.CANCELLED,
                       error=exc.with_traceback(None))

    def _drop_upload_caches(self, phys) -> None:
        """Walk the physical tree dropping cached uploads — the one
        device artifact designed to outlive its query must not outlive
        a CANCELLED query (zero-leak unwind contract)."""
        seen = set()
        stack = [phys]
        while stack:
            node = stack.pop()
            if id(node) in seen:
                continue
            seen.add(id(node))
            drop = getattr(node, "drop_cached_uploads", None)
            if drop is not None:
                try:
                    drop()
                except Exception:  # noqa: BLE001 — unwind must not raise
                    pass
            stack.extend(getattr(node, "children", ()) or ())

    def _run_cpu_fallback(self, handle: QueryHandle, cause,
                          sink: Dict) -> None:
        """Per-query circuit breaker: re-execute THIS query on the
        CPU-exec plan.  Unlike the direct-execute ladder rung this
        must NOT disarm the process-wide injectors or write the global
        fault counters — concurrent queries keep their TPU path and
        their own failure budgets."""
        from ..fault.stats import DEGRADE_CPU
        from ..plan.overrides import cpu_exec_plan
        from ..plan.physical import ExecContext, collect_batches
        from ..telemetry.events import emit_event

        # Same zero-leak discipline as the cancellation unwind: the
        # failed attempt's frames (held by cause.__traceback__ and its
        # context chain) pin the attempt's exec tree — and with it any
        # upload cache the attempt already published — so strip them
        # BEFORE the cause reaches a log record that may retain it,
        # and drop the dead attempt's caches deterministically.
        cause.__cause__ = None
        cause.__context__ = None
        cause = cause.with_traceback(None)
        failed_phys = sink.get("phys")
        if failed_phys is not None:
            self._drop_upload_caches(failed_phys)

        emit_event("degrade", level=DEGRADE_CPU, rung="cpu",
                   cause=type(cause).__name__, scheduled=True,
                   query_id=handle.query_id)
        log.warning(
            "scheduled query %d exhausted fault recovery (%s: %s) — "
            "circuit breaker tripped to the CPU-exec plan",
            handle.query_id, type(cause).__name__, cause)
        self._attribute(handle, sink)  # failed attempt's counters
        prior = {k: v for k, v in (handle.metrics or {}).items()
                 if k.startswith(("fault.", "retry."))}
        sess = self.session
        phys = cpu_exec_plan(sess.conf, handle.plan)
        # session=None: a bare host context — no telemetry re-begin,
        # no injector (re)install, no global stats writes
        ctx = ExecContext(sess.conf, None)
        data = phys.execute(ctx)
        schema = phys.schema if len(phys.schema) else handle.plan.schema
        out = collect_batches(data, schema, ctx)
        merged = dict(ctx.metrics.snapshot())
        merged.update(prior)
        merged["fault.degradeLevel"] = DEGRADE_CPU
        handle.metrics = merged
        handle.exec_path = "cpu"
        # the CPU rung's result is bit-identical by the oracle contract,
        # so it is just as cacheable as the native one
        self._store_serving_result(handle, out)
        handle._finish(QueryStatus.FINISHED, result=out)

    # ----- lifecycle -------------------------------------------------------
    def shutdown(self, timeout: float = 10.0) -> None:
        """Cancel queued + running queries, stop the dispatcher and
        overload monitor, and join every scheduler thread."""
        with self._cv:
            already = self._shutdown
            self._shutdown = True
            queued = self.qos.drain_all_locked()
            running = list(self._running)
            workers = list(self._workers)
            self._cv.notify_all()
        self.overload.stop()
        for h in queued:
            h.token.cancel("scheduler shutdown")
            h._finish(QueryStatus.CANCELLED,
                      error=TpuQueryCancelled("scheduler shutdown"))
        for h in running:
            h.token.cancel("scheduler shutdown")
        if not already:
            self._dispatcher.join(timeout)
        for t in workers:
            t.join(timeout)
        if not already:
            # end-of-life storage hygiene (shared with Session.close):
            # orphaned spill files + expired/over-cap checkpoint dirs
            try:
                self.session.sweep_storage()
            except Exception:  # noqa: BLE001 — shutdown must not raise
                log.warning("shutdown storage sweep failed",
                            exc_info=True)

    @property
    def active_count(self) -> int:
        return self._n_active

    @property
    def queued_count(self) -> int:
        with self._cv:
            return self.qos.queued_count_locked()

    def qos_metrics(self) -> Dict[str, float]:
        """``scheduler.tenant.<name>.*`` counters (submitted,
        dispatched, finished, shed, preempted, queue waits, live
        depths, latency percentiles) plus the overload state and the
        queue-wait percentiles — the serving-tier observability
        surface (docs/qos.md)."""
        with self._cv:
            out = self.qos.metrics_locked()
        out["scheduler.overloaded"] = \
            1.0 if self.overload.overloaded else 0.0
        for p, v in self.overload.wait_hist.percentiles().items():
            out[f"scheduler.queueWait{p.capitalize()}Ms"] = round(v, 3)
        return out

    def histograms(self) -> List:
        """``(family_suffix, labels, LatencyHistogram)`` triples for
        ``telemetry.export.prometheus_text(histograms=...)``: the
        queue-wait histogram plus one end-to-end latency histogram per
        tenant."""
        with self._cv:
            out = self.qos.histograms_locked()
        return [("queue_wait_ms", {}, self.overload.wait_hist)] + out
