"""Multi-tenant QoS: tenant registry, deficit-weighted fair admission,
priority aging, overload detection and load shedding.

Reference analogue: the admission tiers + fair resource arbitration
multi-tenant GPU SQL serving needs ("Accelerating Presto with GPUs",
PAPERS.md), layered onto the PR 7 scheduler:

* **Tenants** — every submission names a tenant (``default`` unless
  given).  Tenants need no pre-registration: the first submission
  creates the :class:`TenantState` from the dynamic conf keys
  ``scheduler.tenant.<name>.{weight,maxConcurrent,hbmFraction}``,
  falling back to the registered ``scheduler.tenant.default.*``
  entries.
* **Deficit-weighted fair share** — each tenant carries a virtual-time
  deficit clock advanced by ``1/weight`` per dispatch; the dispatcher
  always drains the eligible tenant with the smallest clock, so under
  contention service converges to the weight ratio regardless of
  arrival order (start-time fair queuing).  An idle tenant re-joining
  is floored to the current minimum active clock so it cannot hoard a
  burst out of banked idle time.
* **Priority aging** — within a tenant the highest *effective*
  priority dispatches first: ``priority + queue_wait_ms /
  scheduler.priorityAgingMs``.  Aging is what turns fixed priorities
  from a starvation hazard into an ordering hint — a steady
  high-priority stream delays, but can never indefinitely starve, an
  already-queued low-priority query.
* **Overload detection** — :class:`OverloadMonitor` tracks the p95
  queue wait (recent dispatches plus queries still waiting) and arena
  pressure against ``scheduler.overload.{queueWaitMs,hbmFraction}``.
  While overloaded, the scheduler sheds new low-tier submissions with
  :class:`TpuOverloaded` — a *typed retryable* rejection carrying a
  ``retry_after_ms`` backoff hint — and emits ``overload_enter`` /
  ``overload_exit`` / ``overload_shed`` events.

All ``*_locked`` methods must be called with the owning scheduler's
condition (``_cv``) held — the registry has no lock of its own.
"""
from __future__ import annotations

import threading
import time
from typing import Callable, Dict, List, Optional

from ..telemetry.histogram import LatencyHistogram

DEFAULT_TENANT = "default"

#: counters every TenantState tracks (surfaced as
#: ``scheduler.tenant.<name>.<counter>`` by ``qos_metrics``)
_COUNTERS = ("submitted", "dispatched", "finished", "failed",
             "cancelled", "shed", "preempted", "cacheHits",
             "queueWaitMsTotal")


class QueryRejected(RuntimeError):
    """The scheduler shed this query (queue full, queue timeout, or —
    as the :class:`TpuOverloaded` subtype — load shedding)."""


class TpuOverloaded(QueryRejected):
    """Typed retryable shed: the scheduler is overloaded and refused a
    low-tier submission.  ``retry_after_ms`` is the backoff hint — the
    client should resubmit no sooner (and ideally with jitter)."""

    def __init__(self, msg: str, *, retry_after_ms: int):
        super().__init__(msg)
        self.retry_after_ms = int(retry_after_ms)


def effective_priority(handle, now: float, aging_ms: int) -> float:
    """A queued query's aged priority: the static priority plus one
    per ``aging_ms`` of queue wait (0 disables aging).  Aging accrues
    from the FIRST enqueue — a preemption victim keeps its credit
    across the requeue."""
    if aging_ms <= 0:
        return float(handle.priority)
    waited_ms = (now - handle._first_queued_at) * 1000.0
    return handle.priority + waited_ms / float(aging_ms)


def tenant_conf(conf, name: str, field: str, conv, default):
    """Read a dynamic per-tenant conf key, falling back to the
    registered ``scheduler.tenant.default.*`` entry (``conf.get_key``
    resolves registered keys through the registry and unknown keys
    from the raw settings dict)."""
    from ..config import (SCHEDULER_TENANT_DEFAULT_HBM_FRACTION,
                          SCHEDULER_TENANT_DEFAULT_MAX_CONCURRENT,
                          SCHEDULER_TENANT_DEFAULT_WEIGHT)

    registered = {"weight": SCHEDULER_TENANT_DEFAULT_WEIGHT,
                  "maxConcurrent": SCHEDULER_TENANT_DEFAULT_MAX_CONCURRENT,
                  "hbmFraction": SCHEDULER_TENANT_DEFAULT_HBM_FRACTION}
    raw = None
    if name != DEFAULT_TENANT:
        raw = conf.get_key(
            f"spark.rapids.tpu.scheduler.tenant.{name}.{field}")
    if raw is None:
        raw = conf.get(registered[field])
    if raw is None:
        return default
    try:
        return conv(raw)
    except (TypeError, ValueError):
        return default


class TenantState:
    """One tenant's queue, fair-share clock and counters."""

    def __init__(self, name: str, weight: float, max_concurrent: int,
                 hbm_fraction: float, hist_window_s: float = 300.0):
        self.name = name
        self.weight = max(1e-6, float(weight))
        self.max_concurrent = int(max_concurrent)
        self.hbm_fraction = float(hbm_fraction)
        #: virtual-time deficit clock: +1/weight per dispatch; the
        #: smallest eligible clock dispatches next
        self.vtime = 0.0
        self.queue: List = []  # FIFO of queued QueryHandles
        self.running = 0
        self.counters: Dict[str, float] = {c: 0 for c in _COUNTERS}
        #: end-to-end (submit -> terminal) latency, sliding-window
        #: p50/p95/p99 in qos_metrics + histogram prometheus exposition
        self.latency_hist = LatencyHistogram(window_s=hist_window_s)


class TenantRegistry:
    """Per-tenant queues drained by deficit-weighted fair share.
    Owned by one QueryScheduler; every ``*_locked`` method runs under
    the scheduler's ``_cv``."""

    def __init__(self, conf):
        from ..config import TELEMETRY_HISTOGRAM_WINDOW_S

        self._conf = conf
        self._hist_window_s = max(1, conf.get(TELEMETRY_HISTOGRAM_WINDOW_S))
        self.tenants: Dict[str, TenantState] = {}

    # ----- tenant lookup ---------------------------------------------------
    def get_locked(self, name: str) -> TenantState:
        t = self.tenants.get(name)
        if t is None:
            t = TenantState(
                name,
                tenant_conf(self._conf, name, "weight", float, 1.0),
                tenant_conf(self._conf, name, "maxConcurrent", int, 0),
                tenant_conf(self._conf, name, "hbmFraction", float, 0.0),
                hist_window_s=self._hist_window_s)
            self.tenants[name] = t
        return t

    def _min_active_vtime_locked(self) -> float:
        active = [t.vtime for t in self.tenants.values()
                  if t.queue or t.running > 0]
        return min(active) if active else 0.0

    # ----- queue operations ------------------------------------------------
    def enqueue_locked(self, handle) -> TenantState:
        t = self.get_locked(handle.tenant)
        # SFQ idle-tenant floor: re-joining after idle must not spend
        # banked virtual time as a burst against busy tenants
        t.vtime = max(t.vtime, self._min_active_vtime_locked())
        t.queue.append(handle)
        t.counters["submitted"] += 1
        return t

    def requeue_front_locked(self, handle) -> None:
        """Put a handle back at its tenant's queue head (reservation
        retry, or a preemption victim keeping its FIFO position)."""
        self.get_locked(handle.tenant).queue.insert(0, handle)

    def _eligible_locked(self, global_slots_free: bool):
        for t in self.tenants.values():
            t.queue = [h for h in t.queue if not h._done.is_set()]
            if not t.queue:
                continue
            if global_slots_free and t.max_concurrent > 0 \
                    and t.running >= t.max_concurrent:
                continue
            yield t

    def _best_locked(self, now: float, aging_ms: int,
                     respect_tenant_caps: bool = True):
        best = None
        for t in self._eligible_locked(respect_tenant_caps):
            if best is None or t.vtime < best.vtime \
                    or (t.vtime == best.vtime and t.name < best.name):
                best = t
        if best is None:
            return None, None
        # max() keeps the FIRST of equals, and the queue is FIFO — so
        # equal effective priorities dispatch in arrival order
        h = max(best.queue,
                key=lambda h: effective_priority(h, now, aging_ms))
        return best, h

    def pick_locked(self, now: float, aging_ms: int):
        """Remove and return the next handle to dispatch (smallest
        tenant clock, then highest effective priority), or None.  The
        fair-share charge happens at ``note_dispatch_locked`` so a
        failed reservation can requeue without skewing the clock."""
        t, h = self._best_locked(now, aging_ms)
        if h is None:
            return None
        t.queue.remove(h)
        return h

    def peek_locked(self, now: float, aging_ms: int):
        """The handle ``pick_locked`` would return, without removing
        it — the preemption check runs while every slot is busy, where
        per-tenant run caps must not hide a higher-tier candidate."""
        _t, h = self._best_locked(now, aging_ms,
                                  respect_tenant_caps=False)
        return h

    def remove_locked(self, handle) -> bool:
        t = self.tenants.get(handle.tenant)
        if t is None or handle not in t.queue:
            return False
        t.queue.remove(handle)
        return True

    def drain_all_locked(self) -> List:
        out: List = []
        for t in self.tenants.values():
            out.extend(t.queue)
            t.queue = []
        return out

    # ----- accounting ------------------------------------------------------
    def note_dispatch_locked(self, handle, now: float) -> float:
        """Charge the fair-share clock and queue-wait accounting for a
        dispatch; returns the wait in milliseconds."""
        t = self.get_locked(handle.tenant)
        t.vtime += 1.0 / t.weight
        t.running += 1
        wait_ms = max(0.0, (now - handle._queued_at) * 1000.0)
        t.counters["dispatched"] += 1
        t.counters["queueWaitMsTotal"] += wait_ms
        return wait_ms

    def note_done_locked(self, handle, counter: Optional[str]) -> None:
        t = self.get_locked(handle.tenant)
        t.running = max(0, t.running - 1)
        if counter is not None:
            t.counters[counter] += 1
            if counter in ("finished", "failed", "cancelled"):
                # end-to-end latency from the FIRST enqueue: a
                # preemption victim's requeue wait stays inside its
                # measured latency, exactly as its submitter saw it
                first = getattr(handle, "_first_queued_at", None)
                if first is not None:
                    t.latency_hist.observe(
                        max(0.0, (time.monotonic() - first) * 1000.0))

    def count_shed_locked(self, tenant: str) -> None:
        self.get_locked(tenant).counters["shed"] += 1

    def count_cache_hit_locked(self, tenant: str) -> None:
        """A serving result-cache hit completed before admission: it
        counts as submitted AND finished for the tenant (the caller got
        a FINISHED handle) but never dispatches, so its near-zero
        latency goes straight into the tenant histogram — the warm-path
        p50 of a serving tier is this population."""
        t = self.get_locked(tenant)
        t.counters["submitted"] += 1
        t.counters["finished"] += 1
        t.counters["cacheHits"] += 1
        t.latency_hist.observe(0.0)

    # ----- queue introspection --------------------------------------------
    def queued_count_locked(self) -> int:
        return sum(len(t.queue) for t in self.tenants.values())

    def queue_waits_ms_locked(self, now: float) -> List[float]:
        return [(now - h._queued_at) * 1000.0
                for t in self.tenants.values() for h in t.queue]

    def earliest_queued_at_locked(self) -> Optional[float]:
        stamps = [h._queued_at for t in self.tenants.values()
                  for h in t.queue]
        return min(stamps) if stamps else None

    def all_queued_locked(self) -> List:
        return [h for t in self.tenants.values() for h in t.queue]

    def metrics_locked(self) -> Dict[str, float]:
        """``scheduler.tenant.<name>.<counter>`` snapshot plus live
        queue/running depths."""
        out: Dict[str, float] = {}
        for name, t in self.tenants.items():
            pfx = f"scheduler.tenant.{name}."
            for c, v in t.counters.items():
                out[pfx + c] = v
            out[pfx + "queued"] = len(t.queue)
            out[pfx + "running"] = t.running
            out[pfx + "weight"] = t.weight
            for p, v in t.latency_hist.percentiles().items():
                out[pfx + f"latency{p.capitalize()}Ms"] = round(v, 3)
        return out

    def histograms_locked(self) -> List:
        """``(family_suffix, labels, hist)`` triples for
        ``prometheus_text(histograms=...)``."""
        return [("query_latency_ms", {"tenant": name}, t.latency_hist)
                for name, t in sorted(self.tenants.items())]


def _p95(samples: List[float]) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    return s[min(len(s) - 1, int(0.95 * len(s)))]


class OverloadMonitor:
    """Tracks queue-wait p95 and arena pressure against the
    ``scheduler.overload.*`` thresholds and holds the overload state
    the scheduler sheds against.

    The state is re-evaluated inline at every submit AND by a sampler
    thread (so overload *exit* is detected even when no submissions
    arrive).  Transitions emit ``overload_enter`` / ``overload_exit``
    events and are recorded in :attr:`history` (the monitor thread
    usually has no query-telemetry binding, so the history is the
    test-visible record).  Hysteresis: overload exits only
    once every enabled signal drops below half its threshold."""

    def __init__(self, conf, queued_waits_ms: Callable[[], List[float]],
                 arena_pressure: Callable[[], float]):
        from ..config import (SCHEDULER_OVERLOAD_HBM_FRACTION,
                              SCHEDULER_OVERLOAD_QUEUE_WAIT_MS,
                              SCHEDULER_OVERLOAD_RETRY_AFTER_MS,
                              SCHEDULER_OVERLOAD_SAMPLE_MS)

        self.queue_wait_ms = conf.get(SCHEDULER_OVERLOAD_QUEUE_WAIT_MS)
        self.hbm_fraction = conf.get(SCHEDULER_OVERLOAD_HBM_FRACTION)
        self.retry_after_base_ms = conf.get(
            SCHEDULER_OVERLOAD_RETRY_AFTER_MS)
        self.sample_ms = max(10, conf.get(SCHEDULER_OVERLOAD_SAMPLE_MS))
        self._queued_waits_ms = queued_waits_ms
        self._arena_pressure = arena_pressure
        self._lock = threading.Lock()
        #: queue-wait latency histogram (30s sliding window for the
        #: overload p95 — the pre-PR-13 deque recency — while its
        #: cumulative buckets feed the prometheus histogram exposition)
        self.wait_hist = LatencyHistogram(window_s=30.0)
        self._overloaded = False
        #: enter/exit transition records (test-visible)
        self.history: List[Dict] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    @property
    def enabled(self) -> bool:
        return self.queue_wait_ms > 0 or self.hbm_fraction > 0

    @property
    def overloaded(self) -> bool:
        return self._overloaded

    # ----- inputs ----------------------------------------------------------
    def record_wait(self, wait_ms: float) -> None:
        self.wait_hist.observe(float(wait_ms))

    def wait_p95(self, now: Optional[float] = None) -> float:
        """p95 over the histogram's sliding window (recent recorded
        waits) PLUS the live waits of still-queued queries — a wedged
        queue must register as overload even before anything
        dispatches."""
        try:
            live = list(self._queued_waits_ms())
        except Exception:  # noqa: BLE001 — monitor must never throw
            live = []
        # the live waits are exact values; merging them as raw samples
        # next to the bucketed window keeps the wedged-queue signal
        # unquantized (a single long-stuck query must cross the
        # threshold at the threshold, not at the next bucket bound)
        return max(self.wait_hist.percentile(95.0, now), _p95(live))

    def arena_pressure(self) -> float:
        try:
            return float(self._arena_pressure())
        except Exception:  # noqa: BLE001 — monitor must never throw
            return 0.0

    # ----- state machine ---------------------------------------------------
    def evaluate(self) -> bool:
        """Recompute the overload state; emits the transition events.
        Returns the (possibly new) state."""
        from ..telemetry.events import emit_event

        if not self.enabled:
            return False
        p95 = self.wait_p95()
        pressure = self.arena_pressure()
        wait_hot = self.queue_wait_ms > 0 and p95 >= self.queue_wait_ms
        hbm_hot = self.hbm_fraction > 0 and pressure >= self.hbm_fraction
        with self._lock:
            prev = self._overloaded
            if not prev and (wait_hot or hbm_hot):
                self._overloaded = True
            elif prev:
                wait_cool = self.queue_wait_ms <= 0 \
                    or p95 < 0.5 * self.queue_wait_ms
                hbm_cool = self.hbm_fraction <= 0 \
                    or pressure < 0.5 * self.hbm_fraction
                if wait_cool and hbm_cool:
                    self._overloaded = False
            cur = self._overloaded
            if cur != prev:
                self.history.append({
                    "event": "overload_enter" if cur else "overload_exit",
                    "ts": time.time(),
                    "queue_wait_p95_ms": round(p95, 1),
                    "arena_pressure": round(pressure, 4)})
        if cur != prev:
            emit_event("overload_enter" if cur else "overload_exit",
                       queue_wait_p95_ms=round(p95, 1),
                       arena_pressure=round(pressure, 4),
                       queue_wait_threshold_ms=self.queue_wait_ms,
                       hbm_threshold=self.hbm_fraction)
        return cur

    def retry_after_ms(self, queue_depth: int, max_queued: int) -> int:
        """Backoff hint for a shed submission: the base, scaled up
        with how full the queue is — deeper congestion, later
        retry."""
        base = max(1, self.retry_after_base_ms)
        return int(base * (1.0 + queue_depth / float(max(1, max_queued))))

    # ----- sampler thread --------------------------------------------------
    def start(self) -> None:
        """Spawn the sampler thread (no-op when both thresholds are 0:
        the monitor is inert and submit-side evaluation suffices)."""
        from ..telemetry import spans as tspans

        if not self.enabled or self._thread is not None:
            return
        self._thread = threading.Thread(
            target=tspans.bound(tspans.capture(), self._sample_loop),
            daemon=True, name="query-scheduler-overload")
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._stop.set()
        t = self._thread
        if t is not None:
            t.join(timeout)
            self._thread = None

    def _sample_loop(self) -> None:
        while not self._stop.wait(self.sample_ms / 1000.0):
            try:
                self.evaluate()
            except Exception:  # noqa: BLE001 — monitor must never die
                pass
