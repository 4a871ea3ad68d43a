"""Session — engine entry point and plugin bootstrap.

Reference analogue: SQLPlugin / RapidsDriverPlugin / RapidsExecutorPlugin
(Plugin.scala:145-247) + SparkSession surface.  A Session owns the conf,
initializes the device runtime (device manager + semaphore — the
executor-plugin init path), and drives query execution:

    logical plan -> planner -> host physical plan
      -> TpuOverrides (tag/convert)            [preColumnarTransitions]
      -> TpuTransitionOverrides (transitions)  [postColumnarTransitions]
      -> execute
"""
from __future__ import annotations

import logging
from typing import Dict, List, Optional

import numpy as np

log = logging.getLogger(__name__)

from . import types as T
from .config import EXPORT_COLUMNAR_RDD, TpuConf
from .data.column import HostBatch
from .plan import logical as L
from .plan.logical import DataFrame
from .plan.physical import ExecContext, PhysicalPlan, collect_batches
from .plan.planner import Planner


def _clear_registry_quietly(registry):
    try:
        registry.clear()
    except Exception:  # noqa: BLE001 - interpreter teardown
        pass


class Session:
    """User entry point.

    ``Session()`` enables TPU acceleration; ``Session(tpu_enabled=False)``
    is the pure host engine (the CPU oracle in tests)."""

    _active: Optional["Session"] = None

    def __init__(self, conf: Optional[Dict] = None,
                 tpu_enabled: bool = True):
        settings = dict(conf or {})
        if not tpu_enabled:
            settings.setdefault("spark.rapids.tpu.sql.enabled", False)
        self.conf = TpuConf(settings)
        self._executed_plans: List[PhysicalPlan] = []
        self.capture_plans = False
        self.last_metrics: Dict[str, int] = {}
        self.last_write_stats = None  # WriteStatsTracker of last write
        #: one-line retry/split-retry summary of the last execution
        #: ("" when the query saw no memory pressure) — EXPLAIN/trace
        #: surface for degraded queries
        self.last_retry_summary: str = ""
        #: telemetry.QueryProfile of the most recent execution (None
        #: unless telemetry.enabled); Session.profiles keeps the last
        #: telemetry.maxQueryProfiles of them
        self.last_profile = None
        #: per-kernel profiler delta of the most recent execution
        #: ({fingerprint -> telemetry.profiler.KernelStat}; None unless
        #: telemetry.profiler.enabled)
        self.last_kernel_profile = None
        from collections import deque as _deque

        from .config import TELEMETRY_MAX_QUERY_PROFILES

        self._profiles = _deque(
            maxlen=max(1, self.conf.get(TELEMETRY_MAX_QUERY_PROFILES)))
        #: weakrefs to live StreamHandles (metrics_text/metrics_json
        #: fold their progress + latency histograms into the exports)
        self._streams: List = []
        # logical-plan -> physical-plan cache: repeated collect() of the
        # same DataFrame reuses the exec instances and with them every
        # per-exec jit cache (without this, each collect re-traced and
        # re-compiled ~5 XLA programs — measured ~8s/collect on CPU)
        import weakref

        self._plan_cache = weakref.WeakKeyDictionary()
        #: numbers this session's requests: the ``Query`` range's
        #: ``query_id`` in a profiler trace
        import itertools

        self._query_ids = itertools.count(1)
        # concurrent query scheduler — created lazily on first submit()
        # so plain execute() sessions never pay for its threads
        import threading as _threading

        self._scheduler = None
        self._scheduler_lock = _threading.Lock()
        # serving caches (serving/) — created lazily on first prepare()
        # or first cache-enabled submission
        self._serving = None
        from .config import TRACE_ENABLED
        from .utils import tracing

        if self.conf.get(TRACE_ENABLED):
            tracing.enable(True)
        if self.conf.is_sql_enabled:
            from .memory.device_manager import DeviceManager
            from .memory.spill import install as install_spill

            self.device_manager = DeviceManager.get_or_create(self.conf)
            self.spill_framework = install_spill(self.device_manager,
                                                 self.conf)
            # the shared kernel cache is process-wide (like the device
            # manager); each device session (re)applies its sizing conf
            from .exec.kernel_cache import GLOBAL as _kernel_cache

            _kernel_cache.configure(self.conf)
            # the per-kernel dispatch profiler is process-wide too
            from .telemetry.profiler import PROFILER as _profiler

            _profiler.configure(self.conf)
            # reusable broadcast artifacts (reference:
            # GpuBroadcastExchangeExec's broadcast variable, built once
            # and shared by every consumer)
            from .exec.broadcast import BroadcastRegistry
            from .shuffle.catalog import ShuffleCatalog

            self.broadcast_registry = BroadcastRegistry(
                self.spill_framework)
            weakref.finalize(self, _clear_registry_quietly,
                             self.broadcast_registry)
            # shuffle-id -> map-id -> buffers index with per-shuffle
            # cleanup (reference: ShuffleBufferCatalog.scala)
            self.shuffle_catalog = ShuffleCatalog(self.spill_framework)
            weakref.finalize(self, _clear_registry_quietly,
                             self.shuffle_catalog)
        else:
            self.device_manager = None
            self.spill_framework = None
            self.broadcast_registry = None
            self.shuffle_catalog = None
        Session._active = self

    # ----- data sources ----------------------------------------------------
    def create_dataframe(self, data, schema=None,
                         n_partitions: int = 2) -> DataFrame:
        """From a dict of name->values, a HostBatch, or list of row tuples
        with a Schema.

        Source data is treated as IMMUTABLE once handed in: repeated
        collects may serve cached device uploads (HostToDeviceExec), so
        mutating the source afterwards yields undefined results.  Dict
        and row inputs are copied at creation; a HostBatch hands its
        arrays over — they are frozen (``writeable=False``) so a later
        caller write raises instead of silently serving stale cached
        results.  (A column built over a VIEW can still be mutated
        through the base array; the freeze is a tripwire, not a fence.)"""
        if isinstance(data, HostBatch):
            batch = data
            for c in batch.columns:
                for arr in (c.data, c.validity):
                    if isinstance(arr, np.ndarray):
                        arr.flags.writeable = False
        elif isinstance(data, dict):
            batch = HostBatch.from_pydict(data, schema)
        elif isinstance(data, list):
            assert schema is not None, "row data requires a schema"
            cols = {f.name: [r[i] for r in data]
                    for i, f in enumerate(schema)}
            batch = HostBatch.from_pydict(cols, schema)
        else:
            raise TypeError(f"cannot create dataframe from {type(data)}")
        return DataFrame(self, L.LocalRelation([batch], batch.schema,
                                               n_partitions))

    def read_parquet(self, *paths, schema=None, **options) -> DataFrame:
        return self._read("parquet", list(paths), schema, options)

    def read_orc(self, *paths, schema=None, **options) -> DataFrame:
        return self._read("orc", list(paths), schema, options)

    def read_csv(self, *paths, schema=None, header: bool = True,
                 **options) -> DataFrame:
        options = dict(options, header=header)
        if schema is not None:
            options["schema"] = schema
        return self._read("csv", list(paths), schema, options)

    def _read(self, fmt, paths, schema, options) -> DataFrame:
        from .io import scans

        if schema is None:
            schema = scans.infer_schema(fmt, paths, options)
        return DataFrame(self, L.FileScan(fmt, paths, schema, options))

    # ----- execution -------------------------------------------------------
    def physical_plan(self, plan: L.LogicalPlan) -> PhysicalPlan:
        from .plan.optimizer import optimize

        phys = Planner(self.conf).plan(optimize(plan))
        if self.conf.is_sql_enabled:
            from .plan.overrides import TpuOverrides
            from .plan.transitions import TpuTransitionOverrides

            phys = TpuOverrides(self.conf).apply(phys)
            phys = TpuTransitionOverrides(self.conf).apply(phys)
        return phys

    def prepare_execution(self, plan: L.LogicalPlan, *,
                          scheduled: bool = False, cancel_token=None,
                          force_host_shuffle: bool = False,
                          recovery=None):
        """Plan + capture + context — the shared front half of execute
        paths (incl. the ML columnar export).

        Cached exec instances are handed out to ONE execution at a
        time (``_exec_lock``, non-blocking): execs carry per-execution
        state (metrics registries), so a concurrent collect of the same
        DataFrame gets a freshly planned tree instead of sharing."""
        import time

        from .utils.tracing import trace_range

        # what THIS request pays for planning: the cache lookups, the
        # planner on a miss, the context, the recovery stamp
        t0 = time.perf_counter_ns()
        with trace_range("Plan"):
            phys, ctx = self._prepare_execution(
                plan, scheduled=scheduled, cancel_token=cancel_token,
                force_host_shuffle=force_host_shuffle, recovery=recovery)
        # the registry is made inside the range, so it is told after
        ctx.metrics.metric("Session.planTime", "ns").add(
            time.perf_counter_ns() - t0)
        from .plan.fusion import count_absorbed

        # how often the fusion pass folded a filter into its aggregate
        ctx.metrics.metric("fusion.filtersAbsorbed").add(
            count_absorbed(phys))
        return phys, ctx

    def _prepare_execution(self, plan, *, scheduled, cancel_token,
                           force_host_shuffle, recovery):
        import threading

        from .exec.kernel_cache import GLOBAL as _kernel_cache

        from .telemetry.profiler import PROFILER as _profiler

        # snapshot BEFORE planning: exec construction is where keyed
        # kernels register (sharedKernels) and misses start compiling,
        # and it belongs to this query's kernelCache.* delta
        kc_mark = _kernel_cache.counters()
        kp_mark = _profiler.mark()
        try:
            phys = self._plan_cache.get(plan)
        except TypeError:  # unhashable/unweakref-able plan
            phys = None
        if phys is not None and not phys._exec_lock.acquire(
                blocking=False):
            phys = None  # cached tree busy in another thread
        serving = self.serving_if_enabled()
        template_key = None
        if phys is None and serving is not None:
            # plan-template cache: a DIFFERENT plan object that
            # normalizes to a seen (skeleton, binding) template reuses
            # its planned tree — acquire() hands it out with the same
            # non-blocking _exec_lock discipline as the cache above
            template_key = serving.templates.key_for(plan)
            phys = serving.templates.acquire(template_key)
            if phys is not None:
                try:
                    self._plan_cache[plan] = phys
                except TypeError:
                    pass
        if phys is None:
            phys = self.physical_plan(plan)
            phys._exec_lock = threading.Lock()
            phys._exec_lock.acquire()
            try:
                self._plan_cache[plan] = phys
            except TypeError:
                pass
            if serving is not None and template_key is not None:
                serving.templates.store(template_key, phys)
        if self.capture_plans:
            self._executed_plans.append(phys)
        if recovery is None:
            # direct callers that bypass the ladder (scheduled queries,
            # the ML columnar export) still checkpoint + auto-resume
            from .config import RECOVERY_ENABLED

            if self.conf.get(RECOVERY_ENABLED):
                from .recovery import RecoveryManager

                recovery = RecoveryManager(self.conf)
                recovery.attach_query(plan)
        ctx = ExecContext(self.conf, self, scheduled=scheduled,
                          cancel_token=cancel_token,
                          force_host_shuffle=force_host_shuffle)
        ctx.kernel_cache_mark = kc_mark
        ctx.kernel_profiler_mark = kp_mark
        if recovery is not None:
            # stamp every exchange with its rung-invariant plan
            # fingerprint (re-stamping a cached tree is idempotent)
            recovery.stamp_plan(phys)
            ctx.recovery = recovery
        return phys, ctx

    def execute(self, plan: L.LogicalPlan) -> HostBatch:
        """Execute with the graceful-degradation ladder: when the
        native (device) execution exhausts its typed fault recovery —
        payload corruption past its task retries, a stage crash, a
        tripped watchdog, a device-semaphore timeout — the query
        re-executes on the CPU-exec plan (bit-identical by the oracle
        contract) instead of raising, and ``fault.degradeLevel``
        records the rung (``fault.degrade.enabled`` gates this)."""
        return self._execute_with_ladder(plan, force_resume=False)

    def resume(self, plan: L.LogicalPlan) -> HostBatch:
        """Crash-recovery entry point: execute ``plan``, resuming from
        any durable stage checkpoints a previous (crashed or killed)
        process left under ``recovery.dir`` — regardless of
        ``recovery.autoResume``.  Requires ``recovery.enabled``;
        checkpoints that fail validation (plan/query fingerprint,
        schema signature, result-affecting conf snapshot, per-frame
        CRC32C) are quarantined with a ``checkpoint_quarantine`` event
        and their stages simply re-execute — a stale or corrupt
        checkpoint can cost time, never correctness."""
        return self._execute_with_ladder(plan, force_resume=True)

    def _execute_with_ladder(self, plan: L.LogicalPlan, *,
                             force_resume: bool) -> HostBatch:
        """The shared body of ``execute``/``resume``: arm the per-query
        attempt budget (``fault.maxTotalAttempts`` — one ceiling across
        task retries, stage retries, shuffle fallbacks and ladder
        rungs), create the ONE RecoveryManager the whole ladder shares
        (checkpoints written on a failed rung are resumed by the next),
        then run the degradation ladder."""
        from .config import FAULT_MAX_TOTAL_ATTEMPTS, RECOVERY_ENABLED
        from .fault.budget import GLOBAL as _budget
        from .fault.errors import TpuFaultError

        recovery = None
        if self.conf.get(RECOVERY_ENABLED):
            from .recovery import RecoveryManager

            recovery = RecoveryManager(self.conf,
                                       force_resume=force_resume)
            recovery.attach_query(plan)
        owned = _budget.begin(self.conf.get(FAULT_MAX_TOTAL_ATTEMPTS))
        try:
            try:
                return self._execute_native(plan, recovery=recovery)
            except TpuFaultError as e:
                from .config import FAULT_DEGRADE_ENABLED, SHUFFLE_MODE

                if self.device_manager is None or \
                        not self.conf.get(FAULT_DEGRADE_ENABLED):
                    raise
                # ladder rung between native and CPU: re-execute with
                # every exchange forced onto the host-staged shuffle
                # path — the recovery for faults confined to the
                # device-resident data path (a device-targeted
                # corruption drill, HBM exhaustion during a packed
                # write).  Skipped when the conf already pins host
                # shuffle (the rung would change nothing).
                if (self.conf.get(SHUFFLE_MODE)
                        or "auto").lower() != "host":
                    try:
                        return self._execute_host_shuffle_rung(
                            plan, e, recovery=recovery)
                    except TpuFaultError as e2:
                        return self._execute_degraded_cpu(
                            plan, e2, recovery=recovery)
                return self._execute_degraded_cpu(
                    plan, e, recovery=recovery)
        finally:
            _budget.end(owned)

    def _finalize_metrics(self, ctx, phys=None,
                          preserve: Optional[Dict] = None) -> None:
        """The ONE place the per-query metric snapshot, the fault/retry
        counters and the telemetry profile are merged into the session
        at query end (previously duplicated — with hand-copied prefix
        filters — between ``_execute_native`` and the CPU-fallback
        path, where drift silently double- or under-counted).

        ``preserve``: already-merged counters from a FAILED earlier
        attempt (the degraded path) that must stay visible next to the
        fresh snapshot.  Counters are never double-counted across
        consecutive queries: the snapshot always starts from this
        query's own registry, and the process-global fault stats are
        reset at every query start by ``ExecContext``."""
        from .fault.stats import GLOBAL as _fault_stats
        from .fault.stats import fault_summary
        from .memory.retry import retry_summary

        merged = ctx.metrics.snapshot()
        # per-exchange partition histograms (adaptive/stats.py) —
        # surfaced regardless of adaptive.enabled, so shuffle skew is
        # visible in last_metrics / profiles / the Prometheus export
        stage_stats = getattr(ctx, "stage_stats", None)
        if stage_stats is not None:
            merged.update(stage_stats.metrics())
        recovery = getattr(ctx, "recovery", None)
        if recovery is not None:
            # recovery.* counters accumulate across ladder rungs (one
            # manager per query), so later rungs report the running sum
            merged.update(recovery.metrics())
        if preserve:
            merged.update(preserve)
        from .fault.budget import GLOBAL as _attempt_budget

        # after ``preserve``: the armed ledger's live count supersedes
        # any stale fault.totalAttempts carried from a failed rung
        if _attempt_budget.armed():
            merged.update(_attempt_budget.snapshot())
        if self.device_manager is not None:
            if not getattr(ctx, "scheduled", False):
                # scheduled queries never reset (or report) the
                # process-global fault counters — a neighbor's fault
                # drill must not leak into this query's metrics
                merged.update(_fault_stats.snapshot())
            from .exec.kernel_cache import GLOBAL as _kernel_cache
            from .shuffle.device_shuffle import GLOBAL as _shuffle_stats

            merged.update(_kernel_cache.metrics_since(
                getattr(ctx, "kernel_cache_mark", None)))
            merged.update(_shuffle_stats.metrics_since(
                getattr(ctx, "shuffle_stats_mark", None)))
            from .telemetry.profiler import PROFILER as _profiler

            if _profiler.enabled:
                # the per-kernel dispatch counts of THIS query; the
                # handle/profile read it because last_kernel_profile is
                # last-writer-wins shared state (like last_metrics)
                ctx.kernel_profile = _profiler.since(
                    getattr(ctx, "kernel_profiler_mark", None))
                self.last_kernel_profile = ctx.kernel_profile
            fsum = fault_summary(merged)
            if fsum:
                log.warning(
                    "query recovered from faults DEGRADED: %s", fsum)
        self.last_metrics = merged
        self.last_retry_summary = retry_summary(merged)
        if self.last_retry_summary:
            from .config import TRACE_ENABLED

            lvl = logging.WARNING if self.conf.get(TRACE_ENABLED) \
                else logging.INFO
            log.log(lvl, "query completed DEGRADED under memory "
                    "pressure: %s", self.last_retry_summary)
        from .telemetry import finish_query

        # per-query attribution for concurrent callers (QueryHandle):
        # session.last_metrics/last_profile are last-writer-wins shared
        # state, so the handle reads these instead
        ctx.final_metrics = merged
        # an adaptive run profiles its FINAL (rewritten) plan — the
        # "AdaptiveSparkPlan isFinalPlan=true" tree — not the static one
        final_phys = getattr(ctx, "aqe_final_phys", None) or phys
        ctx.profile = finish_query(self, ctx, phys=final_phys,
                                   metrics=merged)
        if ctx.profile is not None:
            kstats = getattr(ctx, "kernel_profile", None)
            if kstats:
                # the profile renders its own dispatches section
                ctx.profile.kernel_stats = kstats
            from .config import TELEMETRY_TRACE_DIR

            trace_dir = self.conf.get(TELEMETRY_TRACE_DIR)
            if trace_dir:
                from .telemetry.trace import write_query_trace

                write_query_trace(trace_dir, ctx.profile)
        nodes = getattr(ctx, "aqe_broadcast_nodes", None)
        if nodes:
            # dynamic-conversion build batches are keyed by weakrefs
            # to THIS execution's stage leaves: no future query can
            # reuse them, so free them now (the recorded strong refs
            # keep the keys matchable) instead of leaving them
            # cataloged until the registry's next lazy purge
            if self.broadcast_registry is not None:
                from .exec.broadcast import canonical_key

                for node in nodes:
                    self.broadcast_registry.free_key(canonical_key(node))
            ctx.aqe_broadcast_nodes = None
        if getattr(ctx, "aqe_final_phys", None) is not None:
            # the final plan holds the per-execution stage leaves (and
            # through them the resident shuffle blocks) — drop it now
            # that the profile is rendered
            ctx.aqe_final_phys = None

    def _execute_native(self, plan: L.LogicalPlan, *,
                        scheduled: bool = False, cancel_token=None,
                        ctx_sink: Optional[Dict] = None,
                        force_host_shuffle: bool = False,
                        recovery=None) -> HostBatch:
        from .utils.tracing import trace_range

        # the request inside the program, planning to rows on the host
        with trace_range("Query", query_id=next(self._query_ids)):
            return self._execute_planned(
                plan, scheduled=scheduled, cancel_token=cancel_token,
                ctx_sink=ctx_sink, force_host_shuffle=force_host_shuffle,
                recovery=recovery)

    def _execute_planned(self, plan, *, scheduled, cancel_token,
                         ctx_sink, force_host_shuffle,
                         recovery) -> HostBatch:
        phys, ctx = self.prepare_execution(
            plan, scheduled=scheduled, cancel_token=cancel_token,
            force_host_shuffle=force_host_shuffle, recovery=recovery)
        if ctx_sink is not None:
            ctx_sink["phys"] = phys
            ctx_sink["ctx"] = ctx
        try:
            from .adaptive.executor import maybe_execute_adaptive

            # adaptive execution: materialize stages one at a time and
            # re-plan the unexecuted suffix from real sizes; returns
            # None when the plan/conf is ineligible (then the static
            # plan executes unchanged)
            data = maybe_execute_adaptive(phys, ctx)
            if data is None:
                data = phys.execute(ctx)
            schema = phys.schema if len(phys.schema) else plan.schema
            return collect_batches(data, schema, ctx)
        finally:
            # benchmark/debug hook: per-exec metric snapshot of the most
            # recent execution (upload/readback wall decomposition); a
            # degraded query must be VISIBLY degraded (retry/fault
            # counters + summaries, mirroring the reference's retry
            # metrics in the SQL UI)
            self._finalize_metrics(ctx, phys=phys)
            phys._exec_lock.release()
            # per-shuffle cleanup at query end — frees shuffle output
            # even when a reader abandoned early (limit over a join)
            if self.shuffle_catalog is not None:
                for sid in ctx.shuffle_ids:
                    self.shuffle_catalog.unregister_shuffle(sid)

    def _execute_host_shuffle_rung(self, plan: L.LogicalPlan,
                                   cause, recovery=None) -> HostBatch:
        """The device-shuffle → host-shuffle ladder rung: re-execute
        the whole query natively with every exchange forced onto the
        host-staged path.  Injectors stay ARMED (re-armed from conf by
        the new ExecContext) — a drill that also hits the host path
        fails this rung and falls through to the CPU rung.  Fault
        counters from the failed device attempt stay visible in
        ``last_metrics`` whether this rung succeeds or not.  With
        recovery enabled, exchanges the failed attempt checkpointed are
        RESUMED here instead of re-executed (host frames are
        mode-independent), and this rung's own completed exchanges
        checkpoint for the CPU rung below."""
        from .fault.budget import GLOBAL as _budget
        from .fault.errors import TpuFaultError
        from .fault.stats import GLOBAL as _fault_stats
        from .fault.stats import fault_summary
        from .telemetry.events import emit_event

        _budget.charge("ladder_host_shuffle", site="session.ladder")

        # the failed attempt's counters were finalized into
        # last_metrics by _execute_native's finally — carry them
        prior = {k: v for k, v in (self.last_metrics or {}).items()
                 if k.startswith(("fault.", "retry."))}
        prior["fault.numShuffleFallbacks"] = \
            prior.get("fault.numShuffleFallbacks", 0) + 1

        def _emit_rung_events():
            # emitted AFTER the rung's execution: the telemetry binding
            # then points at the rung's own profile (the final
            # last_profile), not the already-finished device attempt's
            emit_event("shuffle_fallback", reason="ladder",
                       cause=type(cause).__name__)
            emit_event("degrade", rung="host-shuffle",
                       cause=type(cause).__name__)

        log.warning(
            "native execution exhausted fault recovery (%s: %s) — "
            "re-executing on the host-staged shuffle rung",
            type(cause).__name__, cause)

        def _merge_prior():
            merged = dict(self.last_metrics)
            for k, v in prior.items():
                if k == "fault.degradeLevel":
                    merged[k] = max(merged.get(k, 0), v)
                else:
                    merged[k] = merged.get(k, 0) + v
            self.last_metrics = merged

        try:
            out = self._execute_native(plan, force_host_shuffle=True,
                                       recovery=recovery)
        except TpuFaultError:
            # keep the device attempt (and this rung's fallback count)
            # visible to the CPU rung: both in last_metrics and in the
            # process-global stats its finalize snapshots (the CPU
            # rung's session-less context never resets them)
            _merge_prior()
            _fault_stats.add("numShuffleFallbacks")
            _emit_rung_events()
            raise
        _merge_prior()
        _fault_stats.add("numShuffleFallbacks")
        _emit_rung_events()
        from .config import TELEMETRY_ENABLED

        if self.last_profile is not None \
                and self.conf.get(TELEMETRY_ENABLED):
            self.last_profile.metrics = dict(self.last_metrics)
        fsum = fault_summary(self.last_metrics)
        if fsum:
            log.warning(
                "query recovered on the host-shuffle rung DEGRADED: %s",
                fsum)
        return out

    def _execute_degraded_cpu(self, plan: L.LogicalPlan,
                              cause, recovery=None) -> HostBatch:
        """The bottom ladder rung: re-execute the WHOLE query on the
        host engine (no TPU overrides), with every injector disarmed —
        the fallback must run clean.  Fault counters from the failed
        native attempt are preserved in ``last_metrics`` so the
        degradation stays visible.  Checkpoints written by the failed
        device/host rungs resume here too: the host plan subtree
        fingerprints are rung-invariant and the frames are plain
        serialized HostBatches."""
        from .fault.budget import GLOBAL as _budget
        from .fault.injector import install_fault_injector
        from .fault.stats import DEGRADE_CPU, GLOBAL as _fault_stats
        from .memory.retry import install_injector
        from .plan.overrides import cpu_exec_plan
        from .telemetry.events import emit_event

        _budget.charge("ladder_cpu", site="session.ladder")
        install_injector(None)
        install_fault_injector(None)
        _fault_stats.set_max("degradeLevel", DEGRADE_CPU)
        emit_event("degrade", level=DEGRADE_CPU, rung="cpu",
                   cause=type(cause).__name__)
        log.warning(
            "native execution exhausted fault recovery (%s: %s) — "
            "DEGRADED to the CPU-exec plan",
            type(cause).__name__, cause)
        # keep the failed attempt's degradation counters visible
        prior = {k: v for k, v in (self.last_metrics or {}).items()
                 if k.startswith(("fault.", "retry."))}
        phys = cpu_exec_plan(self.conf, plan)
        ctx = ExecContext(self.conf, None)
        if recovery is not None:
            recovery.stamp_plan(phys)
            ctx.recovery = recovery
        data = phys.execute(ctx)
        schema = phys.schema if len(phys.schema) else plan.schema
        out = collect_batches(data, schema, ctx)
        self._finalize_metrics(ctx, phys=phys, preserve=prior)
        from .config import TELEMETRY_ENABLED

        if self.last_profile is not None \
                and self.conf.get(TELEMETRY_ENABLED):
            # telemetry was on for THIS query, so last_profile is the
            # native attempt's: refresh it with the final merged
            # counters (degrade event included).  Without the conf
            # guard a stale prior-query profile would be corrupted.
            self.last_profile.metrics = dict(self.last_metrics)
        return out

    # ----- concurrent submission (scheduler/) -------------------------------
    @property
    def scheduler(self):
        """The session's QueryScheduler, created on first access."""
        with self._scheduler_lock:
            if self._scheduler is None:
                from .scheduler.query_scheduler import QueryScheduler

                self._scheduler = QueryScheduler(self)
            return self._scheduler

    # ----- sub-second serving (serving/) ------------------------------------
    @property
    def serving(self):
        """The session's serving caches (prepared statements / plan
        templates / results), created on first access."""
        with self._scheduler_lock:
            if self._serving is None:
                from .serving import ServingCaches

                self._serving = ServingCaches(self)
            return self._serving

    def serving_if_enabled(self):
        """The serving caches when ``serving.cache.enabled`` is on,
        else None — the form the hot paths (prepare_execution, the
        scheduler's admission) consult so disabled sessions never pay
        for normalization or fingerprinting."""
        from .config import SERVING_CACHE_ENABLED

        if not self.conf.get(SERVING_CACHE_ENABLED):
            return None
        return self.serving

    def prepare(self, plan):
        """Prepare ``plan`` (a DataFrame or logical plan) for repeated
        execution: literal values are extracted into positional
        parameters and the returned ``PreparedStatement``'s
        ``execute(params)`` / ``submit(params)`` re-bind them at
        dispatch — planning, fusion and compilation are reused through
        the serving caches instead of redone (docs/serving_cache.md).
        Works regardless of ``serving.cache.enabled`` (that conf gates
        the caching of ad-hoc submissions)."""
        if isinstance(plan, DataFrame):
            plan = plan.plan
        from .serving import PreparedStatement

        return PreparedStatement(self, plan)

    def submit(self, plan, priority: int = 0, tenant: str = "default"):
        """Submit a query (a DataFrame or logical plan) for concurrent
        execution; returns a ``QueryHandle`` with ``result()`` /
        ``cancel()`` / ``status()``.  Queued queries drain by
        per-tenant deficit-weighted fair share with priority aging
        (``scheduler.tenant.<tenant>.*`` confs; see docs/qos.md).
        Admission is bounded (``scheduler.maxConcurrent`` running +
        ``scheduler.maxQueued`` queued); a submit past the bound raises
        ``QueryRejected`` and emits an ``admission_reject`` event, and
        under declared overload a low-tier submit is shed with the
        retryable ``TpuOverloaded`` (its ``retry_after_ms`` is the
        backoff hint)."""
        if isinstance(plan, DataFrame):
            plan = plan.plan
        return self.scheduler.submit(plan, priority=priority,
                                     tenant=tenant)

    # ----- continuous queries (streaming/) ----------------------------------
    def stream(self, plan, trigger=None, priority: int = 0,
               tenant: str = "default"):
        """Start a continuous query over ``plan``'s file sources and
        return a ``StreamHandle`` (``await_batch()`` / ``progress()`` /
        ``stop()``).  Each micro-batch re-discovers the sources, merges
        grown exchanges incrementally through the recovery substrate
        and submits the cumulative plan via the scheduler with the
        per-batch ``streaming.batchDeadlineMs`` deadline — every batch
        result is bit-identical to a cold full recompute of the same
        cumulative input.  ``trigger`` is the tick interval in ms
        (default ``streaming.triggerIntervalMs``); ``trigger=0`` means
        manual ticks via ``handle.process_available()``.  Requires
        ``streaming.enabled``."""
        from .config import STREAMING_ENABLED, STREAMING_TRIGGER_INTERVAL_MS
        from .streaming.stream import StreamHandle

        if not self.conf.get(STREAMING_ENABLED):
            raise RuntimeError(
                "streaming is disabled — set "
                "spark.rapids.tpu.streaming.enabled=true")
        if isinstance(plan, DataFrame):
            plan = plan.plan
        trigger_ms = self.conf.get(STREAMING_TRIGGER_INTERVAL_MS) \
            if trigger is None else int(trigger)
        handle = StreamHandle(self, plan, trigger_ms=trigger_ms,
                              priority=priority, tenant=tenant)
        import weakref

        self._streams = [r for r in self._streams if r() is not None]
        self._streams.append(weakref.ref(handle))
        return handle

    def active_streams(self) -> List:
        """Live StreamHandles started by :meth:`stream`.  Stopped or
        GC'd handles drop out — the scrape surface reflects what is
        running, not what once ran (callers keep the handle if they
        want its final progress)."""
        out = []
        for r in self._streams:
            h = r()
            if h is not None and not getattr(h, "_stopped", False):
                out.append(h)
        return out

    def resume_stream(self, plan, trigger=None, priority: int = 0,
                      tenant: str = "default"):
        """Alias of :meth:`stream` that documents intent after a crash
        or restart: resuming IS starting again — the durable ledger
        (``streaming.stateDir``) carries the exactly-once position and
        the pinned checkpoints carry the aggregate state, so the next
        tick continues from the last COMMITTED batch.  Check
        ``handle.resumed`` to confirm a ledger was found."""
        return self.stream(plan, trigger=trigger, priority=priority,
                           tenant=tenant)

    def shutdown_scheduler(self) -> None:
        """Stop the scheduler (cancelling queued + running queries) and
        join its threads; a later submit() starts a fresh one."""
        with self._scheduler_lock:
            sched, self._scheduler = self._scheduler, None
        if sched is not None:
            sched.shutdown()

    def sweep_storage(self) -> Dict[str, int]:
        """Durable-storage hygiene (shared by :meth:`close` and the
        scheduler's shutdown): remove orphaned spill files a crashed
        process left behind, crash-orphaned checkpoint temp files,
        checkpoint query dirs past ``recovery.ttlSeconds`` and — over
        ``recovery.maxBytes`` — the least-recently-touched checkpoint
        dirs.  Never raises; returns removal counts."""
        out: Dict[str, int] = {}
        try:
            if self.spill_framework is not None:
                out["removedSpillOrphans"] = \
                    self.spill_framework.sweep_orphans()
        except Exception:  # noqa: BLE001 - hygiene must not mask exit
            log.warning("spill orphan sweep failed", exc_info=True)
        try:
            from .recovery.manager import sweep_recovery_dir

            out.update(sweep_recovery_dir(self.conf))
        except Exception:  # noqa: BLE001
            log.warning("recovery sweep failed", exc_info=True)
        return out

    def close(self) -> None:
        """End-of-life hygiene: stop the scheduler (joining its
        threads) and :meth:`sweep_storage`.  Idempotent — the session
        remains usable for further queries afterwards."""
        self.shutdown_scheduler()
        self.sweep_storage()

    def execute_columnar(self, plan: L.LogicalPlan):
        """Zero-copy device export: returns the list of DeviceBatches of
        the final columnar stage (reference analogue: ColumnarRdd /
        InternalColumnarRddConverter, requires exportColumnarRdd)."""
        if not self.conf.get(EXPORT_COLUMNAR_RDD):
            raise RuntimeError(
                "set spark.rapids.tpu.sql.exportColumnarRdd=true")
        from .ml.columnar_export import export_device_batches

        return export_device_batches(self, plan)

    def explain(self, plan: L.LogicalPlan, mode: str = "ALL") -> str:
        phys = Planner(self.conf).plan(plan)
        if not self.conf.is_sql_enabled:
            return phys.tree_string()
        from .plan.overrides import TpuOverrides

        return TpuOverrides(self.conf.set(
            "spark.rapids.tpu.sql.explain", mode)).explain(phys)

    # ----- telemetry surface ------------------------------------------------
    @property
    def profiles(self):
        """Completed query profiles, newest last (bounded by
        ``telemetry.maxQueryProfiles``)."""
        return list(self._profiles)

    def profile_report(self, top_n: int = 5,
                       device_trace: Optional[str] = None) -> str:
        """EXPLAIN-ANALYZE report of the most recent execution: the
        physical plan annotated with per-exec metrics, the span tree, a
        top-N hot-operator summary and the event digest.  Empty string
        unless ``telemetry.enabled`` was on for the query.

        ``device_trace``: an ``.xplane.pb[.gz]`` a ``jax.profiler``
        session around the query wrote; the report then ends in a
        ``-- Device phases --`` section, the device's seconds and GB/s
        by program and phase (``telemetry/device_trace.py``)."""
        if self.last_profile is None:
            return ""
        return self.last_profile.render(top_n=top_n,
                                        device_trace=device_trace)

    def export_metrics(self) -> Dict:
        """One combined metrics dict for the exporters: the last
        query's snapshot plus the scheduler's ``qos_metrics()`` (when a
        scheduler exists — never created just to export) and every live
        stream's ``streaming.*`` progress."""
        merged = dict(self.last_metrics)
        with self._scheduler_lock:
            sched = self._scheduler
            serving = self._serving
        if sched is not None:
            merged.update(sched.qos_metrics())
        if serving is not None:
            merged.update(serving.metrics())
        for h in self.active_streams():
            merged.update(h.progress())
        return merged

    def metrics_text(self) -> str:
        """Prometheus text exposition of :meth:`export_metrics` plus
        the latency histograms (scheduler queue-wait, per-tenant query
        latency, streaming batch latency) as proper ``# TYPE
        histogram`` families — the process scrape surface."""
        from .telemetry.export import prometheus_text

        with self._scheduler_lock:
            sched = self._scheduler
        hists = list(sched.histograms()) if sched is not None else []
        for h in self.active_streams():
            hists.append(("stream_batch_latency_ms",
                          {"stream": h.stream_id}, h.latency_hist))
        return prometheus_text(self.export_metrics(), histograms=hists)

    def metrics_json(self) -> str:
        """JSON snapshot of :meth:`export_metrics` (byte-stable for
        identical state — exporter stability is what lets a scraper
        diff two snapshots)."""
        from .telemetry.export import json_snapshot

        return json_snapshot(self.export_metrics())

    # ----- test hooks (reference: ExecutionPlanCaptureCallback) ------------
    def start_capture(self):
        self.capture_plans = True
        self._executed_plans = []

    def captured_plans(self) -> List[PhysicalPlan]:
        return list(self._executed_plans)
