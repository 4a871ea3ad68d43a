"""Continuous-query surface: micro-batch ticks over growing sources.

``Session.stream(plan, trigger=...)`` returns a :class:`StreamHandle`.
Each tick re-discovers the scan sources (one stat pass — the same
fingerprints feed the ledger AND the recovery leaf material), pins the
discovered files into a concrete cumulative plan, merges grown
exchanges incrementally (streaming/incremental.py), and submits the
cumulative plan through the PR-11 scheduler path with the stream's
:class:`~.incremental.StreamRecoveryManager` and the per-batch deadline
(``streaming.batchDeadlineMs``) attached.  Untouched exchanges resume
from CRC-verified checkpoints; only affected partitions recompute.

Every batch result equals a cold full recompute of the same cumulative
input — the stream never serves a stale or partial answer, it only
saves work.  Equal is to the bit for keys, counts, integer sums, minima,
maxima and picks; a floating-point SUM or AVG computed on the device is
equal to rounding (streaming/incremental.py says why; with
``sql.variableFloatAgg.enabled=false`` it is to the bit as well).  The
ledger commit after the result materializes is the exactly-once marker;
a crash anywhere before it re-runs an idempotent tick.

Triggers: ``trigger_ms > 0`` runs a daemon tick loop;
``trigger_ms == 0`` means manual ticks via :meth:`StreamHandle
.process_available` (what the deterministic tests use).
"""
from __future__ import annotations

import contextlib
import copy
import logging
import shutil
import threading
import time
from typing import Dict, List, Optional

from ..config import (STREAMING_BATCH_DEADLINE_MS, STREAMING_MAX_BATCH_FILES,
                      TELEMETRY_ENABLED)
from ..io.scans import discover_files
from ..plan import logical as L
from ..recovery.manager import resolve_root
from ..recovery.store import QUARANTINE_PREFIX, CheckpointStore
from ..scheduler import cancel as _cancel
from ..scheduler.cancel import CancelToken, TpuQueryCancelled, check_cancel
from ..telemetry import spans as tspans
from ..telemetry.events import emit_event
from ..telemetry.spans import QueryTelemetry
from ..serving.result_cache import register_stream_result
from .incremental import (StreamRecoveryManager, merge_growing_exchanges,
                          stream_fingerprint)
from .ledger import SourceLedger, split_new_files

log = logging.getLogger(__name__)


def _collect_scans(node, out: List) -> None:
    """Preorder list of the template plan's ``FileScan`` leaves —
    the positions are the ledger's source order."""
    if isinstance(node, L.FileScan):
        out.append(node)
    for c in getattr(node, "children", ()):
        _collect_scans(c, out)


def _pin_sources(node, files_per_scan: List[List[str]], pos: List[int]):
    """Rebuild the template logical plan with each ``FileScan``'s path
    list replaced by concrete discovered files (preorder-matched).
    Pinning makes the tick's plan a closed description of its input —
    a file landing mid-tick joins the NEXT batch, never a torn one."""
    if isinstance(node, L.FileScan):
        i = pos[0]
        pos[0] += 1
        return L.FileScan(node.fmt, list(files_per_scan[i]), node.schema,
                          dict(node.options))
    clone = copy.copy(node)
    clone.children = [_pin_sources(c, files_per_scan, pos)
                      for c in node.children]
    return clone


class StreamHandle:
    """One continuous query: ledger + pinned checkpoint state + ticks.

    Thread model: ticks run either on the daemon trigger thread or on
    the caller's thread via :meth:`process_available`, never both at
    once for correctness-critical state — the ledger and checkpoint
    merges happen inside the tick under ``_tick_lock``.  Consumers wait
    on :meth:`await_batch`."""

    def __init__(self, session, plan, *, trigger_ms: int,
                 priority: int = 0, tenant: str = "default"):
        conf = session.conf
        self.session = session
        self.template = plan
        self.priority = priority
        self.tenant = tenant
        self.trigger_ms = int(trigger_ms)
        self._scans: List[L.FileScan] = []
        _collect_scans(plan, self._scans)
        if not self._scans:
            raise ValueError(
                "streaming requires at least one file source "
                "(in-memory relations cannot grow)")
        for sc in self._scans:
            _files, _values, keys, _fps = discover_files(sc.paths)
            if keys:
                raise ValueError(
                    "streaming over Hive-partitioned sources is not "
                    f"supported (found partition keys {keys!r})")
        self.stream_fp = stream_fingerprint(conf, plan)
        self.stream_id = f"stream-{self.stream_fp[:12]}"
        serving = session.serving_if_enabled()
        self._ledger = SourceLedger(
            conf, self.stream_fp,
            result_cache=serving.results if serving is not None else None)
        #: True when a committed ledger from a previous process/handle
        #: was loaded — the next tick resumes instead of starting over
        self.resumed = self._ledger.load()
        self._store = CheckpointStore(resolve_root(conf))
        # the stream's aggregate state must survive TTL/maxBytes sweeps
        # for as long as this handle lives
        self._store.pin(self.stream_fp)
        self._tele = QueryTelemetry(conf, session=None,
                                    query_id=self.stream_id) \
            if conf.get(TELEMETRY_ENABLED) else None
        self.token = CancelToken()
        self._deadline_ms = int(conf.get(STREAMING_BATCH_DEADLINE_MS) or 0)
        self._max_batch_files = int(
            conf.get(STREAMING_MAX_BATCH_FILES) or 0)
        self._tick_lock = threading.Lock()
        self._cv = threading.Condition()
        self._seq = 0
        self._last = None
        self._progress: List[Dict] = []
        from ..config import TELEMETRY_HISTOGRAM_WINDOW_S
        from ..telemetry.histogram import LatencyHistogram

        #: per-batch commit latency: p50/p95/p99 in progress() and a
        #: histogram family in Session.metrics_text()
        self.latency_hist = LatencyHistogram(
            window_s=max(1, conf.get(TELEMETRY_HISTOGRAM_WINDOW_S)))
        self._stopped = False
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        with self._bound():
            emit_event("stream_start", stream=self.stream_id,
                       resumed=bool(self.resumed),
                       batch_id=self._ledger.batch_id,
                       trigger_ms=self.trigger_ms,
                       sources=len(self._scans))
        if self.trigger_ms > 0:
            self._thread = threading.Thread(
                target=tspans.bound(tspans.capture(), self._trigger_loop),
                name=self.stream_id, daemon=True)
            self._thread.start()

    # ----- context ---------------------------------------------------------
    @contextlib.contextmanager
    def _bound(self):
        """Bind the stream's telemetry + cancel token to the current
        thread for the duration of a tick (and restore whatever was
        bound before — process_available may run inside a caller that
        has its own query active)."""
        prev_tele = tspans.current()
        prev_token = _cancel.current()
        if self._tele is not None:
            tspans.activate(self._tele)
        _cancel.activate(self.token)
        try:
            yield
        finally:
            if prev_tele is not None:
                tspans.activate(prev_tele)
            else:
                tspans.deactivate()
            _cancel.activate(prev_token)

    # ----- trigger loop ----------------------------------------------------
    def _trigger_loop(self) -> None:
        interval = self.trigger_ms / 1000.0
        while not self._stop_evt.wait(interval):
            if self.token.cancelled():
                break
            with self._bound():
                try:
                    check_cancel("streaming.trigger")
                    self._tick()
                except TpuQueryCancelled:
                    break
                except Exception:  # noqa: BLE001 - loop survives a bad tick
                    log.warning("stream %s: tick failed — next trigger "
                                "retries", self.stream_id, exc_info=True)

    def process_available(self):
        """Run ONE tick synchronously on the caller's thread and return
        its result (None when the tick was skipped — no new files).
        Batch errors propagate to the caller.  The deterministic tests
        and ``trigger=0`` streams drive everything through this."""
        if self._stopped:
            raise RuntimeError(f"stream {self.stream_id} is stopped")
        with self._bound():
            return self._tick()

    # ----- decision helpers (lint-pinned: every skip/shed/cap decision
    # emits its stream_* event from exactly one place) ----------------------
    def _skip_tick(self, reason: str) -> None:
        emit_event("stream_tick_skip", stream=self.stream_id,
                   batch_id=self._ledger.batch_id, reason=reason)
        return None

    def _skip_incremental(self, reason: str) -> None:
        emit_event("stream_incremental_skip", stream=self.stream_id,
                   exchange="*", reason=reason)

    def _cap_batch(self, deferred: int) -> None:
        emit_event("stream_batch_capped", stream=self.stream_id,
                   batch_id=self._ledger.batch_id + 1,
                   max_batch_files=self._max_batch_files,
                   deferred_files=deferred)

    # ----- one tick --------------------------------------------------------
    def _admit(self, prev: List[List[Dict]], new: List[List[Dict]]):
        """Apply ``streaming.maxBatchFiles`` across sources in template
        order; the overflow stays undiscovered until the next tick (a
        growing backlog is drained maxBatchFiles at a time)."""
        if self._max_batch_files <= 0:
            return ([p + n for p, n in zip(prev, new)], 0)
        budget = self._max_batch_files
        admitted, deferred = [], 0
        for p, n in zip(prev, new):
            take = n[:budget] if budget > 0 else []
            budget -= len(take)
            deferred += len(n) - len(take)
            admitted.append(p + take)
        if deferred:
            self._cap_batch(deferred)
        return admitted, deferred

    def _tick(self):
        with self._tick_lock:
            return self._tick_locked()

    def _tick_locked(self):
        t0 = time.monotonic()
        check_cancel("streaming.tick")
        session, conf = self.session, self.session.conf
        cur = [discover_files(sc.paths)[3] for sc in self._scans]
        prev = self._ledger.files
        if len(prev) != len(cur):
            prev = [[] for _ in cur]
        stable, new = True, []
        for p, c in zip(prev, cur):
            ok, suffix = split_new_files(p, c)
            stable = stable and ok
            new.append(suffix if ok else [])
        if not stable:
            # a committed file was rewritten/removed: the incremental
            # contract is broken, but a full-recompute batch over the
            # CURRENT discovery is still exactly right
            self._skip_incremental("source_rewritten")
            admitted, deferred = [list(c) for c in cur], 0
            new = [[] for _ in cur]
            prev = [[] for _ in cur]
        else:
            n_new = sum(len(s) for s in new)
            if n_new == 0:
                if self._ledger.batch_id > 0:
                    return self._skip_tick("no_new_files")
                if sum(len(c) for c in cur) == 0:
                    return self._skip_tick("no_files")
            admitted, deferred = self._admit(prev, new)
        batch_id = self._ledger.batch_id + 1
        paths = [[fp["path"] for fp in fps] for fps in admitted]
        cum_plan = _pin_sources(self.template, paths, [0])
        mgr = StreamRecoveryManager(conf, self.stream_fp)
        mgr.attach_query(cum_plan)
        if mgr.query_fp is None:
            mgr = None
        merged = 0
        if mgr is not None and stable and self._ledger.batch_id > 0 \
                and self._ledger.exchanges:
            # cumulative file tuple -> that source's new-file suffix:
            # how the merge locates each exchange subtree's delta
            new_by_cum = {
                tuple(ps): [fp["path"] for fp in fps[len(p):]]
                for ps, fps, p in zip(paths, admitted, prev)}
            try:
                merged = merge_growing_exchanges(
                    mgr, new_by_cum, self._ledger.exchanges)
            except TpuQueryCancelled:
                raise
            except Exception as e:  # noqa: BLE001 - recompute, never fail
                self._skip_incremental(f"{type(e).__name__}: {e}")
        emit_event("stream_batch_start", stream=self.stream_id,
                   batch_id=batch_id,
                   files_new=sum(len(s) for s in new),
                   files_total=sum(len(a) for a in admitted),
                   merged_exchanges=merged)
        check_cancel("streaming.submit")
        try:
            handle = session.scheduler.submit(
                cum_plan, priority=self.priority, tenant=self.tenant,
                recovery=mgr, deadline_ms=self._deadline_ms or None)
            out = handle.result()
        except BaseException as e:
            # deadline miss / preemption / execution failure: the
            # ledger did NOT advance, so the next tick retries the same
            # cumulative input — committed state is untouched
            emit_event("stream_batch_error", stream=self.stream_id,
                       batch_id=batch_id, error=type(e).__name__,
                       reason=str(e))
            with self._cv:
                self._last = ("err", e)
                self._seq += 1
                self._cv.notify_all()
            raise
        stamped = mgr.stamped_total if mgr is not None else 0
        resumed = int(handle.metrics.get(
            "recovery.numStagesResumed", 0)) if mgr is not None else 0
        fraction = 1.0 if stamped <= 0 \
            else max(0.0, 1.0 - resumed / stamped)
        self._ledger.commit(batch_id, admitted,
                            mgr.exchange_fps if mgr is not None else {})
        # register the committed tick's materialized result with the
        # serving result cache (serving/ owns policy + cache_* events):
        # an ad-hoc submit() of the same cumulative query between ticks
        # fingerprints to this exact (plan, data) identity and hits
        register_stream_result(session, cum_plan, out)
        latency_ms = (time.monotonic() - t0) * 1000.0
        self.latency_hist.observe(latency_ms)
        emit_event("stream_batch_commit", stream=self.stream_id,
                   batch_id=batch_id, latency_ms=round(latency_ms, 3),
                   stages_resumed=resumed, stages_total=stamped,
                   merged_exchanges=merged,
                   recompute_fraction=round(fraction, 4))
        if mgr is not None:
            self._gc_superseded(set(mgr.exchange_fps.values()))
        prog = {
            "streaming.batchId": batch_id,
            "streaming.filesNew": sum(len(s) for s in new),
            "streaming.filesTotal": sum(len(a) for a in admitted),
            "streaming.batchLatencyMs": round(latency_ms, 3),
            "streaming.stagesResumed": resumed,
            "streaming.stagesTotal": stamped,
            "streaming.mergedExchanges": merged,
            "streaming.recomputeFraction": round(fraction, 4),
            "streaming.backlogFiles": deferred,
        }
        for p, v in self.latency_hist.percentiles().items():
            prog[f"streaming.batchLatency{p.capitalize()}Ms"] = round(v, 3)
        with self._cv:
            self._progress.append(prog)
            self._last = ("ok", out)
            self._seq += 1
            self._cv.notify_all()
        return out

    def _gc_superseded(self, keep: set) -> None:
        """Drop checkpoints of exchange fingerprints the latest commit
        superseded (a stream would otherwise accrete one generation per
        tick inside its pinned — unsweepable — query dir).  Quarantined
        dirs are left for the post-mortem sweep.  Never raises."""
        qdir = self._store.query_dir(self.stream_fp)
        try:
            import os

            for name in os.listdir(qdir):
                if name in keep or name.startswith(QUARANTINE_PREFIX):
                    continue
                shutil.rmtree(os.path.join(qdir, name),
                              ignore_errors=True)
        except OSError:
            pass

    # ----- consumer surface ------------------------------------------------
    def await_batch(self, timeout: Optional[float] = None):
        """Block until a tick COMMITS a batch after this call (or one
        errors) and return/raise its outcome."""
        with self._cv:
            seen = self._seq
            ok = self._cv.wait_for(
                lambda: self._seq > seen or self._stopped, timeout)
            if not ok:
                raise TimeoutError(
                    f"stream {self.stream_id}: no batch within "
                    f"{timeout}s")
            if self._seq == seen:
                raise RuntimeError(
                    f"stream {self.stream_id} stopped before a batch")
            kind, payload = self._last
        if kind == "err":
            raise payload
        return payload

    def progress(self) -> Dict:
        """The latest committed batch's progress metrics
        (``streaming.*`` keys; empty before the first commit)."""
        with self._cv:
            return dict(self._progress[-1]) if self._progress else {}

    def progress_history(self) -> List[Dict]:
        with self._cv:
            return [dict(p) for p in self._progress]

    def events(self) -> List[Dict]:
        """Snapshot of the stream's event ring (``stream_*`` lifecycle
        plus checkpoint/merge events emitted inside ticks)."""
        return self._tele.events.snapshot() if self._tele else []

    def stop(self) -> None:
        """Stop the stream: cancel any in-flight tick cooperatively,
        join the trigger thread, unpin the checkpoint state (hygiene
        sweeps may reclaim it afterwards).  Idempotent."""
        if self._stopped:
            return
        self._stopped = True
        self._stop_evt.set()
        self.token.cancel("stream stopped")
        t = self._thread
        if t is not None and t is not threading.current_thread():
            t.join(timeout=30)
        self._store.unpin(self.stream_fp)
        prev = tspans.current()
        if self._tele is not None:
            tspans.activate(self._tele)
        emit_event("stream_stop", stream=self.stream_id,
                   batch_id=self._ledger.batch_id)
        if prev is not None:
            tspans.activate(prev)
        else:
            tspans.deactivate()
        with self._cv:
            self._cv.notify_all()
