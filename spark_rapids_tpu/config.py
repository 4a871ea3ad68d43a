"""Typed configuration registry.

Capability parity with the reference's ``RapidsConf.scala`` (832 LoC): a
typed builder with defaults and validators, a global registry, markdown doc
generation, and *auto-derived per-operator enable/disable keys* from the
plan-rewrite rule framework (reference: GpuOverrides.scala:118-123 derives
``spark.rapids.sql.<kind>.<ClassName>``).

Keys here live under ``spark.rapids.tpu.*`` and mirror the reference's
grouping: memory, scheduling, batch sizing, feature gates, test hooks,
shuffle/exchange, explain.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Callable, Dict, List, Optional

_REGISTRY: Dict[str, "ConfEntry"] = {}
_REG_LOCK = threading.Lock()


class ConfEntry:
    def __init__(self, key: str, converter: Callable[[str], Any],
                 doc: str, default: Any, is_internal: bool = False,
                 checker: Optional[Callable[[Any], Optional[str]]] = None):
        self.key = key
        self.converter = converter
        self.doc = doc
        self.default = default
        self.is_internal = is_internal
        self.checker = checker
        with _REG_LOCK:
            if key in _REGISTRY:
                raise ValueError(f"duplicate conf key {key}")
            _REGISTRY[key] = self

    def get(self, conf: Dict[str, Any]) -> Any:
        if self.key in conf:
            raw = conf[self.key]
            val = self.converter(raw) if isinstance(raw, str) else raw
        else:
            env_key = self.key.upper().replace(".", "_")
            if env_key in os.environ:
                val = self.converter(os.environ[env_key])
            else:
                return self.default
        if self.checker is not None:
            err = self.checker(val)
            if err:
                raise ValueError(f"{self.key}: {err}")
        return val

    def help(self) -> str:
        return f"{self.key} — {self.doc} (default: {self.default})"


def _to_bool(s: str) -> bool:
    return s.strip().lower() in ("true", "1", "yes", "on")


class ConfBuilder:
    """``conf("key").doc(...).boolean_conf(default)`` builder, mirroring the
    reference's ``ConfBuilder``/``TypedConfBuilder`` (RapidsConf.scala:128-206)."""

    def __init__(self, key: str):
        self.key = key
        self._doc = ""
        self._internal = False
        self._checker = None

    def doc(self, text: str) -> "ConfBuilder":
        self._doc = text
        return self

    def internal(self) -> "ConfBuilder":
        self._internal = True
        return self

    def check(self, fn: Callable[[Any], Optional[str]]) -> "ConfBuilder":
        self._checker = fn
        return self

    def _mk(self, conv, default):
        return ConfEntry(self.key, conv, self._doc, default,
                         self._internal, self._checker)

    def boolean_conf(self, default: bool) -> ConfEntry:
        return self._mk(_to_bool, default)

    def int_conf(self, default: int) -> ConfEntry:
        return self._mk(int, default)

    def long_conf(self, default: int) -> ConfEntry:
        return self._mk(int, default)

    def double_conf(self, default: float) -> ConfEntry:
        return self._mk(float, default)

    def string_conf(self, default: Optional[str]) -> ConfEntry:
        return self._mk(str, default)


def conf(key: str) -> ConfBuilder:
    return ConfBuilder(key)


def lookup(key: str) -> Optional[ConfEntry]:
    return _REGISTRY.get(key)


def register_op_enable_key(kind: str, name: str, doc: str,
                           default: bool = True) -> ConfEntry:
    """Auto-derived per-operator key, e.g.
    ``spark.rapids.tpu.sql.exec.SortExec`` (reference GpuOverrides.scala:118-123).

    Idempotent per key."""
    key = f"spark.rapids.tpu.sql.{kind}.{name}"
    existing = lookup(key)
    if existing is not None:
        return existing
    return conf(key).doc(doc).boolean_conf(default)


def dump_markdown() -> str:
    """Generate the configs doc table (reference: docs/configs.md is generated
    from the registry, RapidsConf.scala help/makeConfAnchor)."""
    lines = ["# Configuration", "",
             "| Key | Default | Description |", "|---|---|---|"]
    for key in sorted(_REGISTRY):
        e = _REGISTRY[key]
        if e.is_internal:
            continue
        lines.append(f"| `{key}` | {e.default} | {e.doc} |")
    lines += ["", _MEMORY_ROBUSTNESS_DOC, "", _FAULT_TOLERANCE_DOC,
              "", _SCHEDULING_DOC, "", _QOS_DOC, "",
              _OBSERVABILITY_DOC, "", _PERF_TUNING_DOC, "",
              _SHUFFLE_DOC, "", _ADAPTIVE_DOC, "", _RECOVERY_DOC, "",
              _STREAMING_DOC, "", _SERVING_CACHE_DOC]
    return "\n".join(lines)


_QOS_DOC = """\
## Multi-tenant QoS: fair admission, aging, preemption, shedding

The `scheduler.tenant.*` / `scheduler.overload.*` /
`scheduler.priorityAgingMs` / `scheduler.preemption.*` confs (table
above) configure the multi-tenant QoS layer
(`spark_rapids_tpu/scheduler/qos.py`, docs/qos.md):

* **Tenants** — `Session.submit(plan, priority, tenant="name")` routes
  through per-tenant queues drained by deficit-weighted fair share.
  Tenant names need no pre-registration: `scheduler.tenant.<name>.
  {weight,maxConcurrent,hbmFraction}` are read as dynamic keys, falling
  back to the registered `scheduler.tenant.default.*` entries.
* **Priority aging** — a queued query's effective priority grows by one
  per `scheduler.priorityAgingMs` of queue wait, so fixed priorities
  order dispatch but can never starve a queued query forever.
* **Checkpoint-backed preemption** — `scheduler.preemption.enabled`
  lets a strictly higher-priority queued query evict the
  lowest-priority running victim through the cooperative-cancel
  zero-leak unwind; the victim is requeued (keeping its aging credit)
  and, with `recovery.enabled`, resumes from its completed exchange
  checkpoints (`recovery.numStagesResumed` in the victim's metrics).
  Each preemption is charged against the victim's
  `fault.maxTotalAttempts` budget.
* **Overload detection + load shedding** — the OverloadMonitor tracks
  queue-wait p95 and arena pressure against
  `scheduler.overload.{queueWaitMs,hbmFraction}`; while overloaded,
  submissions below `scheduler.overload.shedBelowPriority` are shed
  with `TpuOverloaded(retry_after_ms=...)`, and
  `overload_{enter,exit,shed}` / `preempt_{victim,resume}` telemetry
  events plus `scheduler.tenant.*` counters make the behavior
  observable (`QueryScheduler.qos_metrics()`)."""


_RECOVERY_DOC = """\
## Stage-level checkpointing & crash recovery

The `recovery.*` confs (table above) configure durable stage
checkpoints (`spark_rapids_tpu/recovery/`, docs/recovery.md):

* **Checkpoint writes** — with `recovery.enabled`, every exchange the
  engine finishes materializing is persisted under
  `recovery.dir/<query_fingerprint>/<exchange_fingerprint>/` as
  CRC32C-stamped partition frames (the spill frame format, host
  bytes — readable by the device, host-shuffle and CPU ladder rungs
  alike) plus an atomically written JSON manifest carrying the plan
  fingerprint, schema signatures, the partition histogram and a
  snapshot of the result-affecting conf keys.
* **Resume** — stage retries, degradation-ladder rungs and (with
  `recovery.autoResume`, or explicitly via `Session.resume(plan)`) a
  fresh process after a crash fingerprint-match the plan, verify every
  manifest and frame CRC eagerly, skip completed exchanges by feeding
  the checkpointed blocks through the exchange read path, and
  re-execute only the unexecuted suffix
  (`recovery.numStagesResumed` in `Session.last_metrics`).
* **Quarantine, never a wrong answer** — a checkpoint failing ANY
  validity check (frame CRC, plan fingerprint, schema signature,
  result-affecting conf snapshot, malformed manifest) is renamed aside
  and a `checkpoint_quarantine` event emitted; the exchange re-executes
  from scratch.
* **Hygiene** — `Session.close()` and scheduler shutdown sweep
  crash-orphaned temp files, expired checkpoints
  (`recovery.ttlSeconds`) and evict least-recently-touched query
  directories over `recovery.maxBytes`; ENOSPC/OSError during a
  checkpoint write disables checkpointing for the query
  (`checkpoint_disabled` event) instead of failing it.
* **Unified retry budget** — `fault.maxTotalAttempts` is the single
  per-query ceiling across task retries, stage retries, shuffle
  fallbacks and ladder rungs; crossing it emits ONE terminal
  `attempt_budget_exhausted` event with the full attempt ledger."""


_STREAMING_DOC = """\
## Incremental streaming execution

The `streaming.*` confs (table above) configure micro-batch
continuous queries (`spark_rapids_tpu/streaming/`, docs/streaming.md):

* **Micro-batch triggers** — `session.stream(plan)` returns a
  `StreamHandle`; every `streaming.triggerIntervalMs` a tick discovers
  newly arrived files (at most `streaming.maxBatchFiles` per batch),
  pins the cumulative file list into the plan and executes it through
  the PR-11 scheduler path under a per-batch
  `streaming.batchDeadlineMs` deadline SLA.
* **Incremental state on the recovery substrate** — each growing
  exchange's partial-aggregate frames persist via the CheckpointStore;
  the next tick executes only the delta files and MERGES their frames
  after the checkpointed ones, so untouched partitions resume from
  CRC-verified checkpoints instead of recomputing
  (`streaming.recomputeFraction` < 1 in batch progress).
* **Exactly-once ledger** — the source ledger under
  `<recovery.dir>/streams/<stream-fingerprint>/` (relocatable via
  `streaming.stateDir`) commits atomically AFTER each batch; a crash
  between batches replays the tick idempotently
  (`Session.resume_stream` in a fresh process, the same results,
  `recovery.numStagesResumed > 0`).
* Every decision emits a `stream_*` telemetry event; results equal a
  cold recompute of the same cumulative input, including under fault
  injection and ladder degradation: to the bit, except that a float
  SUM/AVG the device computes equals the host engine's to rounding
  (docs/streaming.md; `sql.variableFloatAgg.enabled=false` makes that
  bit for bit too)."""


_SERVING_CACHE_DOC = """\
## Sub-second serving: prepared statements & the serving caches

The `serving.cache.*` confs (table above) configure the serving
subsystem (`spark_rapids_tpu/serving/`, docs/serving_cache.md):

* **Prepared statements** — `Session.prepare(plan)` extracts literal
  parameters from the logical plan into a parameterized skeleton;
  `prepared.execute(params)` / `prepared.submit(params)` re-bind
  literals at dispatch without re-planning, re-fingerprinting or
  re-fusing the plan.
* **Plan-template cache** — keyed by the skeleton fingerprint (the
  KernelCache fingerprint discipline applied to optimized-plan
  skeletons): ad-hoc `submit()` calls that normalize to an
  already-seen template reuse the cached optimized physical plan and
  fused segments instead of planning from scratch
  (`serving.cache.templates.maxEntries` bounds the LRU).
* **Result cache** — keyed by the recovery subsystem's rung-invariant
  query+data fingerprint (plan fingerprint x per-file leaf material
  from the discovery stat pass) and stored in the CheckpointStore
  frame format under the reserved `serving/` directory of the
  recovery root.  A `submit()` whose fingerprint matches a cached
  result completes BEFORE admission — a hit never queues, never
  holds an HBM reservation and reports `exec_path == "cache"`.
* **Invalidation, never a stale answer** — every read re-stats the
  scanned files (the same per-file fingerprints the streaming ledger
  commits) and re-validates plan fingerprint, schema signature,
  result-affecting conf snapshot and frame CRCs; ANY doubt
  quarantines the entry (`cache_quarantine`) and the query executes
  cold.  Changed inputs invalidate eagerly (`cache_invalidate`).
* **Eviction** — `serving.cache.results.maxBytes` caps the on-disk
  result bytes; least-recently-used entries are evicted
  (`cache_evict`).  `cache_hit`/`cache_miss`/`cache_store` events and
  `serving.cache.*` metrics (plus the per-tenant `cacheHits` counter)
  make every decision observable.
* **Streaming composition** — a maintained incremental streaming
  aggregate registers its materialized per-tick result in the result
  cache, so a `submit()` of the stream's own query between ticks is a
  cache hit instead of a recompute."""


_ADAPTIVE_DOC = """\
## Adaptive query execution

The `adaptive.*` confs (table above) configure the AQE subsystem
(`spark_rapids_tpu/adaptive/`, docs/adaptive.md):

* **Runtime stage statistics** — the device shuffle's write drain
  already pulls per-partition count vectors to the host in its one
  gated batch readback; `StageStats` aggregates them (plus block byte
  sizes from the arena accounting) into exact per-exchange partition
  histograms with ZERO extra device syncs (lint-enforced), surfaced as
  `shuffle.exchange<N>.partRows{Min,P50,Max}`/`skewPct` in
  `Session.last_metrics`, `profile_report()` and the Prometheus export
  even with `adaptive.enabled=false`.
* **Partition coalescing** — adjacent small post-shuffle partitions
  are merged up to `adaptive.targetPartitionBytes`, shrinking reader
  fan-in; both sides of a co-partitioned join get the identical
  grouping.
* **Skew-join splitting** — a partition exceeding
  `adaptive.skewedPartitionFactor` x the median rows (and
  `adaptive.skewedPartitionThresholdBytes`) is cut into contiguous
  row-balanced sub-slices, each joined against a replica of the full
  build-side partition — the straggler that used to eat the whole
  stage wall (and trip the stage watchdogs) becomes parallel work.
* **Dynamic broadcast conversion** — a planned shuffled-hash join
  whose MATERIALIZED build side lands under
  `adaptive.autoBroadcastJoinThreshold` is demoted to a broadcast
  join, skipping the stream-side exchange entirely.  Stages run build
  sides first, and a shuffled join's stream-side exchange waits for a
  build side that is still being computed (an aggregate under it, as
  in `IN (select ... group by ... having ...)`), so the conversion's
  window stays open until the build side's size is known.
* Every decision emits a structured `aqe_*` telemetry event, the
  final plan renders AdaptiveSparkPlan-style in EXPLAIN ANALYZE, and
  the scheduler's per-query HBM reservation is re-based from observed
  stage output.  All rewrites are bit-identical to the non-adaptive
  plan — same values, same row placement after the re-partitioning
  rules — including under fault injection and concurrent submit."""


_SHUFFLE_DOC = """\
## Device-resident shuffle

The `shuffle.*` confs (table above) configure the exchange data path
(`exec/exchange.py`, `shuffle/device_shuffle.py`, docs/shuffle.md):

* **Device path** (`shuffle.mode=device`, or `auto` with HBM headroom)
  — hash/round-robin/single-partitioned shuffle blocks stay resident in
  HBM: one jitted partition-build kernel (shared through the kernel
  cache) sorts each input batch by destination partition and records
  per-partition start/count vectors, and readers slice their partition
  out with one gather kernel.  No per-partition d2h -> CRC -> h2d round
  trip; CRC32C stamping happens only if a block crosses the spill/host
  boundary.  Mesh-distributed plans move the same packed form between
  participants via one fused `lax.all_to_all` collective
  (`parallel/exchange.py`).
* **Host path** (`shuffle.mode=host`) — every block is staged to host
  memory immediately and CRC32C-stamped, the fully-verified pre-device
  behavior; `auto` degrades to it under HBM pressure, and blocks the
  spill framework demotes off-device are verified on re-read either
  way.
* **Fallback ladder** — a device-shuffle query that exhausts fault
  recovery re-executes on the host shuffle path (a `shuffle_fallback` +
  `degrade` event, counted in `fault.numShuffleFallbacks`) before the
  CPU rung.
* **Observability** — `shuffle.deviceBytes` / `shuffle.hostBytes` /
  `shuffle.collectiveTime` land in `Session.last_metrics`; on the
  chip the exchange's seconds are `exchange_device_s` and
  `shuffle_write_idle_s` of a traced benchmark run."""


_SCHEDULING_DOC = """\
## Concurrent query scheduling

The `scheduler.*` confs (table above) configure the concurrent query
scheduler (`spark_rapids_tpu/scheduler/`, docs/scheduling.md):

* **Admission control** — `Session.submit(plan)` returns a
  `QueryHandle` (`result()` / `cancel()` / `status()`); at most
  `scheduler.maxConcurrent` queries run at once, each holding an HBM
  reservation of `scheduler.reservationFraction` x the DeviceManager
  arena for its lifetime, and at most `scheduler.maxQueued` queries
  wait in the bounded priority queue.  A submit beyond that bound — or
  a queued query not dispatched within `scheduler.queueTimeoutMs` — is
  shed with `QueryRejected` and an `admission_reject` event.
* **Cooperative cancellation** — `handle.cancel()` and
  `scheduler.queryTimeoutMs` deadlines trip the query's `CancelToken`;
  every operator checkpoint the OOM/fault injectors reach polls it, so
  the query unwinds with `TpuQueryCancelled` at its next allocation,
  upload, drain or stage boundary: semaphore permits released,
  spill/upload-cache buffers dropped, shuffle-catalog slots freed, a
  terminal `query_cancelled` event emitted.
* **Per-query failure isolation** — scheduled queries bind private
  (thread-local) fault/OOM injectors instead of the process-wide
  slots, and a query that exhausts its retry/ladder budget trips a
  per-query circuit breaker to the CPU-exec plan without disarming or
  degrading concurrent queries.
* **Deterministic cancellation testing** — `fault.injection.type=
  cancel` cancels the running query's token at any injector checkpoint
  site, so mid-stage unwind is testable everywhere the injector
  reaches."""


_MEMORY_ROBUSTNESS_DOC = """\
## Memory-pressure robustness

On a fixed-HBM TPU, memory pressure is the steady state, not the
exception.  Device operators route every allocation-heavy attempt
through the OOM retry framework (`spark_rapids_tpu/memory/retry.py`):

* **retry** (`TpuRetryOOM`): the allocation failed but may succeed once
  memory is freed.  The task releases its device-semaphore permits,
  forces a synchronous spill through the spill framework, backs off
  with a bounded exponential delay plus seeded jitter
  (`retry.backoffBaseMs` / `retry.backoffMaxMs` / `retry.backoffSeed`),
  and re-executes the attempt from its checkpointed input — up to
  `retry.maxRetries` times.
* **split-and-retry** (`TpuSplitAndRetryOOM`): retrying the same input
  cannot succeed; the input batch is halved by rows — recursively, down
  to the `retry.minSplitRows` floor — and each piece is processed
  independently (upload, stream-side joins, aggregate and sort compose
  per-piece results back into the unsplit answer).  An OOM at the floor
  is genuine and surfaces with a diagnostic naming the operator.

Recovery is observable: per-query counters `retry.numRetries`,
`retry.numSplitRetries`, `retry.retryBlockTimeMs` and
`retry.spillBytesOnRetry` land in `Session.last_metrics`, and a
degraded query logs a summary when `spark.rapids.tpu.sql.trace.enabled`
is on.

The `oomInjection.*` confs (table above) drive any operator path
through its OOM-recovery path deterministically in CI on CPU-only JAX —
no real memory exhaustion required."""


_FAULT_TOLERANCE_DOC = """\
## Distributed fault tolerance

The `fault.*` confs (table above) configure the query-level
fault-tolerance layer (`spark_rapids_tpu/fault/`, docs/fault_tolerance.md):

* **Payload integrity** — spill frames and exchange host round-trips
  carry CRC32C checksums computed on write and verified on read
  (`fault.checksum.enabled`); a mismatch raises `TpuPayloadCorruption`
  and the producing stage is recomputed from lineage.
* **Stage watchdogs** — `fault.stageTimeoutMs` bounds every distributed
  stage and leaf drain; a tripped watchdog abandons the hung attempt
  with `TpuStageTimeout` and re-executes it, bounded by
  `fault.maxStageRetries`.  `fault.semaphoreTimeoutMs` bounds a blocked
  device-semaphore acquire, and `fault.queuePutTimeoutMs` bounds a
  producer blocked on a full prefetch queue.
* **Graceful degradation** — after `fault.maxStageRetries` the runner
  falls back distributed -> single-process -> CPU-exec plan
  (`fault.degrade.enabled`) instead of failing the query; the final
  rung is reported as `fault.degradeLevel`.
* **Elastic multi-host execution** — the `fault.peer.*` confs arm peer
  failure detection (`parallel/elastic.py`): a heartbeat ledger
  (`fault.peer.heartbeatMs` / `missedHeartbeats` / `heartbeatDir`)
  detects dead worker processes, and `fault.peer.collectiveTimeoutMs`
  bounds every guarded collective so a dead peer aborts the dispatch
  with `TpuPeerLost` instead of wedging the mesh.  The ladder then
  re-forms the mesh on the surviving devices (the "shrunken mesh" rung
  above single-process) and re-executes from the recovery substrate's
  checkpoints rather than from scratch.
* **Straggler speculation** — `speculation.*` arms duplicate attempts
  for leaf-drain shards whose latency exceeds
  `speculation.multiplier` x the rolling `speculation.quantile`
  percentile; the first result wins and the loser is cancelled through
  its CancelToken with the zero-leak unwind discipline.
* **Deterministic injection** — `fault.injection.*` drives every
  recovery path (`oom|corrupt|delay|stage_crash|cancel|peer_crash|`
  `peer_stall`, site-filtered, `nth`/`random`/`always` modes) in CI on
  CPU-only JAX; every injected run must produce results bit-identical
  to an injection-free run.

Recovery is observable: `fault.numStageRetries`,
`fault.numChecksumFailures`, `fault.numWatchdogTrips`,
`fault.degradeLevel`, `fault.numPeerLost`, `fault.numMeshShrinks` and
`fault.numSpeculativeWins` land in `Session.last_metrics`, and a
degraded query logs a DEGRADED summary."""


_PERF_TUNING_DOC = """\
## Whole-stage fusion & kernel cache

The `fusion.*` and `kernelCache.*` confs (table above) configure the
compute hot path (`plan/fusion.py`, `exec/kernel_cache.py`,
docs/perf_tuning.md):

* **Whole-stage fusion** — maximal chains of row-local device operators
  (Project, Filter, Expand, Generate) are collapsed into one
  `TpuFusedSegmentExec` whose single jitted kernel composes the member
  compute bodies, so a Project -> Filter -> Project chain issues one
  XLA dispatch per batch instead of three and materializes no
  intermediate batch in HBM.  Filters fuse by threading their keep mask
  through the segment and compacting once at segment exit — results
  stay bit-identical to the unfused plan.  Fusion stops at exchanges,
  aggregates, sorts, joins, transitions and nondeterministic
  expressions; `fusion.maxSegmentExecs` bounds segment size.  One
  consumer takes the chain in: a `partial`/`complete` aggregate directly
  over a Filter (or a Filter/Project segment) absorbs it as a prologue
  of its own kernels and reads the keep mask, so nothing compacts
  (`fusion.filtersAbsorbed` in `Session.last_metrics`).
* **Shared kernel cache** — every device exec routes jit compilation
  through the process-wide `KernelCache`, keyed by kernel fingerprint
  and schema signature (the row-bucket dimension rides the underlying
  jax shape cache), so identical operators across plans share one
  compiled executable.  `donate_argnums` buffer donation is applied on
  non-CPU backends for segments whose input batches are provably
  single-consumer.  Hit/miss/compile-wall counters land in
  `Session.last_metrics` under `kernelCache.*`, a per-exec
  `compileTime` metric attributes compile wall to operators in
  EXPLAIN ANALYZE; the benchmark reports the cold side as
  `first_query_s` / `setup_compile_s` and holds a warm window to
  `compiles_in_window` 0."""


_OBSERVABILITY_DOC = """\
## Query telemetry

The `telemetry.*` confs (table above) configure the query-scoped
observability subsystem (`spark_rapids_tpu/telemetry/`,
docs/observability.md):

* **Hierarchical spans** — query -> stage -> exec -> attempt, with wall
  time, device-sync time and rows/batches per physical exec, propagated
  to worker threads via an explicitly captured thread-local binding.
* **Structured event log** — query begin/end, spill, retry, split,
  checksum failure, watchdog trip, degrade-rung change, admission
  verdict and injected faults, in a bounded in-memory ring plus an
  optional append-only JSONL sink (`telemetry.eventLog.dir`);
  multi-controller runs ship worker events back to every controller.
* **EXPLAIN ANALYZE** — `Session.profile_report()` renders the physical
  plan annotated with per-exec metrics plus a top-N hot-operator
  summary; `Session.last_profile` / `Session.profiles` keep the last
  `telemetry.maxQueryProfiles` profiles.
* **Exporters** — Prometheus-text and JSON snapshots over the query
  metrics, plus an HBM-watermark timeline sampled from the
  DeviceManager every `telemetry.sampleHbmMs` milliseconds.
  Dimensional keys (`scheduler.tenant.<name>.*`,
  `shuffle.exchange<N>.*`) export with proper `tenant=`/`exchange=`
  labels; the scheduler's queue-wait, per-tenant query-latency and
  streaming batch-latency distributions export as real
  `# TYPE histogram` families (`Session.metrics_text()`).
* **Per-kernel profiler** — `telemetry.profiler.enabled` attributes
  every jitted-kernel dispatch to a stable kernel fingerprint
  (dispatches, enqueue wall, rows/bytes, padding waste) and renders
  the `-- Kernel dispatches --` table in `Session.profile_report()`;
  the disabled cost is one attribute read per dispatch.  A kernel's
  device seconds and GB/s by phase come from a profiler trace:
  `Session.profile_report(device_trace=<xplane>)`
  (docs/profiling.md).
* **Trace timelines** — `telemetry.trace.dir` exports one
  Chrome-trace/Perfetto JSON per query (span tree as duration tracks,
  HBM watermark as a counter track, ring events as instants), written
  atomically.
* **Latency histograms** — fixed log-scale bucket histograms
  (`telemetry.histogram.windowS` sliding window for p50/p95/p99
  readouts, cumulative buckets for prometheus) back the scheduler
  queue-wait p95, per-tenant latency and streaming batch latency.

With `telemetry.enabled=false` (the default) every emitter is a no-op
and the metrics snapshot is byte-identical to the un-instrumented
engine."""


# ==========================================================================
# Global entries (grouping mirrors RapidsConf.scala:221-584)
# ==========================================================================

# --- memory (spark.rapids.memory.* :221-269) ------------------------------
DEVICE_MEMORY_FRACTION = conf("spark.rapids.tpu.memory.allocFraction").doc(
    "Fraction of device HBM the engine treats as its working arena; "
    "admission control and spill thresholds derive from it").double_conf(0.9)
HOST_SPILL_STORAGE_SIZE = conf("spark.rapids.tpu.memory.host.spillStorageSize").doc(
    "Bytes of host memory used to spill device batches before disk").long_conf(
    1024 * 1024 * 1024)
DEVICE_MEMORY_DEBUG = conf("spark.rapids.tpu.memory.debug").doc(
    "Log device allocations/frees").boolean_conf(False)

# --- OOM retry / split-and-retry (memory/retry.py; reference:
# RmmRapidsRetryIterator + the RMM OOM-injection test mode) ----------------
RETRY_MAX_RETRIES = conf("spark.rapids.tpu.memory.retry.maxRetries").doc(
    "OOM retries of one attempt (spill + backoff + re-execute) before a "
    "split-capable operator halves its input instead; non-splittable "
    "operators surface the OOM after this many retries").int_conf(8)
RETRY_MIN_SPLIT_ROWS = conf("spark.rapids.tpu.memory.retry.minSplitRows").doc(
    "Split-and-retry floor: an input batch is never split below this "
    "many rows — an OOM at the floor is genuine and surfaces with a "
    "diagnostic naming the operator").int_conf(1)
RETRY_BACKOFF_BASE_MS = conf("spark.rapids.tpu.memory.retry.backoffBaseMs").doc(
    "Base delay of the bounded exponential backoff between OOM retries, "
    "milliseconds (delay = min(base * 2^attempt, backoffMaxMs) with "
    "seeded jitter)").double_conf(2.0)
RETRY_BACKOFF_MAX_MS = conf("spark.rapids.tpu.memory.retry.backoffMaxMs").doc(
    "Upper bound on the exponential backoff delay between OOM retries, "
    "milliseconds").double_conf(200.0)
RETRY_BACKOFF_SEED = conf("spark.rapids.tpu.memory.retry.backoffSeed").doc(
    "Seed for the backoff jitter (decorrelates tasks that OOMed "
    "together without making test timings nondeterministic)").int_conf(0)

# --- deterministic OOM injection (test mode; reference: RMM's
# oomInjection / RmmSpark.forceRetryOOM) -----------------------------------
OOM_INJECTION_MODE = conf("spark.rapids.tpu.memory.oomInjection.mode").doc(
    "Fault-injection mode driving operators through their OOM-recovery "
    "paths without real memory exhaustion: none (off), nth (fire once "
    "at allocation checkpoint #skipCount), random (seeded probabilistic "
    "firing, suppressed during recovery so progress is guaranteed), "
    "always (fire at every checkpoint — proves split-retry bottoms out "
    "at retry.minSplitRows)").string_conf("none")
OOM_INJECTION_SKIP_COUNT = conf(
    "spark.rapids.tpu.memory.oomInjection.skipCount").doc(
    "mode=nth: 0-based allocation checkpoint at which the single "
    "injected OOM fires; sweeping 0..N drives every checkpoint of a "
    "pipeline through recovery, one run at a time").int_conf(0)
OOM_INJECTION_SEED = conf("spark.rapids.tpu.memory.oomInjection.seed").doc(
    "Seed for mode=random's injection decisions (deterministic given "
    "a fixed checkpoint order)").int_conf(0)
OOM_INJECTION_TYPE = conf("spark.rapids.tpu.memory.oomInjection.oomType").doc(
    "Type of injected OOM: retry (TpuRetryOOM — spill+backoff+retry) or "
    "split (TpuSplitAndRetryOOM — the input batch must be halved)"
).string_conf("retry")

# --- distributed fault tolerance (fault/; reference: the transparent
# recovery promise of SURVEY §L0 extended to the distributed path) ---------
FAULT_INJECTION_MODE = conf("spark.rapids.tpu.fault.injection.mode").doc(
    "Generalized fault-injection mode (fault/injector.py) driving every "
    "recovery path deterministically in CI: none (off), nth (fire once "
    "at matching checkpoint #skipCount), random (seeded, suppressed "
    "during recovery), always (every matching checkpoint — proves "
    "bounded retries exhaust into the degradation ladder)"
).string_conf("none")
FAULT_INJECTION_TYPE = conf("spark.rapids.tpu.fault.injection.type").doc(
    "Injected fault type: oom (typed retry OOM), corrupt (flip a byte "
    "in the next checksummed payload write so the read-side CRC32C "
    "verify must catch it), delay (sleep delayMs at the checkpoint — a "
    "straggler), stage_crash (raise TpuStageCrash — a died stage), "
    "cancel (cancel the running query's CancelToken at the checkpoint "
    "— deterministic mid-stage cancellation for unwind testing), "
    "peer_crash (raise TpuPeerLost — a died peer worker; drives the "
    "shrunken-mesh rung), peer_stall (sleep delayMs like delay — a "
    "stalled peer shard; drives straggler speculation)"
).string_conf("oom")
FAULT_INJECTION_SKIP_COUNT = conf(
    "spark.rapids.tpu.fault.injection.skipCount").doc(
    "mode=nth: 0-based matching checkpoint at which the single "
    "injected fault fires; sweeping 0..N drives every checkpoint of a "
    "site class through recovery, one run at a time").int_conf(0)
FAULT_INJECTION_SEED = conf("spark.rapids.tpu.fault.injection.seed").doc(
    "Seed for mode=random's injection decisions").int_conf(0)
FAULT_INJECTION_SITE = conf("spark.rapids.tpu.fault.injection.site").doc(
    "Substring filter on checkpoint sites (spill.write, spill.read, "
    "exchange.write, exchange.write.device, exchange.read, stage.run, "
    "leaf.drain, host.stack, shuffle.collective); empty matches every "
    "site.  Only matching checkpoints advance the skipCount counter"
).string_conf("")
FAULT_INJECTION_DELAY_MS = conf(
    "spark.rapids.tpu.fault.injection.delayMs").doc(
    "type=delay: milliseconds the injected straggler sleeps at the "
    "checkpoint").double_conf(50.0)
FAULT_STAGE_TIMEOUT_MS = conf("spark.rapids.tpu.fault.stageTimeoutMs").doc(
    "Stage watchdog: a distributed stage (or leaf drain) that has not "
    "completed after this many milliseconds is abandoned with "
    "TpuStageTimeout and re-executed from lineage (0 disables; leave "
    "disabled on multi-controller deployments unless every controller "
    "shares the conf — recovery control flow must stay replicated)"
).int_conf(0)
FAULT_MAX_STAGE_RETRIES = conf("spark.rapids.tpu.fault.maxStageRetries").doc(
    "Bounded re-executions of a failed distributed stage/leaf before "
    "the query walks down the degradation ladder (distributed -> "
    "single-process -> CPU-exec plan)").int_conf(2)
FAULT_CHECKSUM_ENABLED = conf("spark.rapids.tpu.fault.checksum.enabled").doc(
    "Compute CRC32C checksums on spill-frame writes and exchange host "
    "round-trips and verify them on read; a mismatch raises "
    "TpuPayloadCorruption and triggers recompute-from-lineage of the "
    "producing stage instead of consuming garbage").boolean_conf(True)
FAULT_HOST_ROUNDTRIP_CHECKSUM = conf(
    "spark.rapids.tpu.fault.checksum.hostRoundtrip").doc(
    "Also stamp+verify the distributed runner's exchange host staging "
    "(per-shard batches between drain and mesh placement).  Costs a "
    "full CRC pass over the staged data per leaf, so it is off by "
    "default in production; it arms automatically while a corrupt "
    "fault injector is installed, and can be forced on to chase "
    "suspected host-memory corruption").boolean_conf(False)
FAULT_DEGRADE_ENABLED = conf("spark.rapids.tpu.fault.degrade.enabled").doc(
    "Graceful degradation: a query that exhausts its fault recovery "
    "(stage retries, task retries) re-executes on the next ladder rung "
    "(single-process, then the CPU-exec plan) instead of failing; the "
    "final rung is reported as fault.degradeLevel in "
    "Session.last_metrics").boolean_conf(True)
FAULT_SEMAPHORE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.fault.semaphoreTimeoutMs").doc(
    "Device-semaphore acquire watchdog: a blocked acquire that sees no "
    "progress for this long raises DeviceSemaphoreTimeout — a "
    "retryable fault the degradation ladder can recover/degrade on — "
    "instead of hanging the process (0 uses the built-in default of "
    "180s)").int_conf(0)
FAULT_QUEUE_PUT_TIMEOUT_MS = conf(
    "spark.rapids.tpu.fault.queuePutTimeoutMs").doc(
    "Producer-side watchdog on bounded prefetch queues: a put() into a "
    "persistently full queue past this deadline raises TpuStageTimeout "
    "(the consumer has died or wedged) instead of busy-looping "
    "silently (0 disables)").int_conf(180000)
FAULT_MAX_TOTAL_ATTEMPTS = conf(
    "spark.rapids.tpu.fault.maxTotalAttempts").doc(
    "Per-query ceiling on the TOTAL number of recovery re-executions "
    "across every mechanism — task retries, adaptive stage retries, "
    "shuffle host fallbacks and degradation-ladder rungs — so stacked "
    "recovery paths cannot multiply into unbounded re-execution.  "
    "Crossing the ceiling emits one terminal attempt_budget_exhausted "
    "event carrying the full attempt ledger and fails the query with "
    "AttemptBudgetExhausted (0 disables the ceiling)").int_conf(64)
FAULT_PEER_HEARTBEAT_MS = conf(
    "spark.rapids.tpu.fault.peer.heartbeatMs").doc(
    "Interval at which each multi-controller worker process touches "
    "its heartbeat file in fault.peer.heartbeatDir so peers can detect "
    "its death without waiting out a wedged collective (0 disables the "
    "heartbeat ledger)").int_conf(0)
FAULT_PEER_MISSED_HEARTBEATS = conf(
    "spark.rapids.tpu.fault.peer.missedHeartbeats").doc(
    "Consecutive missed heartbeat intervals after which a peer is "
    "declared lost: a peer whose heartbeat file is staler than "
    "heartbeatMs * missedHeartbeats aborts in-flight guarded "
    "collectives with TpuPeerLost and triggers the shrunken-mesh "
    "rung").int_conf(3)
FAULT_PEER_COLLECTIVE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.fault.peer.collectiveTimeoutMs").doc(
    "Deadline on every guarded collective dispatch "
    "(parallel/elastic.py): a process_allgather / compiled-collective "
    "call that makes no progress past this deadline is abandoned with "
    "TpuPeerLost instead of wedging every surviving peer forever (0 "
    "disables the deadline; dead peers are then only detectable via "
    "the heartbeat ledger)").int_conf(0)
FAULT_PEER_HEARTBEAT_DIR = conf(
    "spark.rapids.tpu.fault.peer.heartbeatDir").doc(
    "Shared directory for the peer heartbeat ledger (one file per "
    "process id, mtime = last heartbeat).  Must be visible to every "
    "worker process — a shared filesystem or a local dir when all "
    "workers are colocated; empty uses <system tempdir>/"
    "srt-heartbeats").string_conf("")
SPECULATION_ENABLED = conf("spark.rapids.tpu.speculation.enabled").doc(
    "Straggler speculation on leaf drains: when a shard's drain "
    "latency exceeds speculation.multiplier x the rolling "
    "speculation.quantile percentile, a duplicate attempt is launched; "
    "the first result wins and the loser is cancelled through its "
    "CancelToken with the zero-leak unwind discipline").boolean_conf(False)
SPECULATION_MULTIPLIER = conf("spark.rapids.tpu.speculation.multiplier").doc(
    "A shard speculates once its elapsed drain time exceeds this "
    "multiple of the rolling percentile "
    "(speculation.quantile)").double_conf(2.0)
SPECULATION_QUANTILE = conf("spark.rapids.tpu.speculation.quantile").doc(
    "Percentile of the per-shard drain-latency histogram used as the "
    "speculation baseline (e.g. 95.0 = p95)").double_conf(95.0)
SPECULATION_MIN_SAMPLES = conf(
    "spark.rapids.tpu.speculation.minSamples").doc(
    "Minimum completed drains in the rolling latency window before "
    "speculation arms — prevents duplicating shards off a cold, "
    "unrepresentative baseline").int_conf(4)
SPECULATION_MIN_LATENCY_MS = conf(
    "spark.rapids.tpu.speculation.minLatencyMs").doc(
    "Floor below which a shard never speculates regardless of the "
    "percentile baseline, so uniformly fast drains do not duplicate "
    "work over scheduling jitter").double_conf(25.0)

# --- stage-level checkpointing & crash recovery (recovery/;
# reference: Theseus-style resumable exchange artifacts) -------------------
RECOVERY_ENABLED = conf("spark.rapids.tpu.recovery.enabled").doc(
    "Persist every completed exchange materialization as a durable "
    "stage checkpoint (CRC32C-stamped partition frames + an atomically "
    "written JSON manifest under recovery.dir/<query_fingerprint>/).  "
    "Stage retries, degradation-ladder rungs and — with "
    "recovery.autoResume — a fresh process after a crash resume from "
    "the last completed checkpoint instead of re-running the whole "
    "query").boolean_conf(False)
RECOVERY_DIR = conf("spark.rapids.tpu.recovery.dir").doc(
    "Directory holding durable stage checkpoints; empty uses "
    "<system tempdir>/srt-recovery.  Must survive process restarts to "
    "be useful for crash recovery (i.e. point it at a real disk, not a "
    "per-process tmpdir)").string_conf("")
RECOVERY_AUTO_RESUME = conf("spark.rapids.tpu.recovery.autoResume").doc(
    "When recovery.enabled is on, Session.execute() transparently "
    "fingerprint-matches the plan against existing checkpoints and "
    "skips completed exchanges (Session.resume() does this "
    "unconditionally).  Disable to only WRITE checkpoints, e.g. while "
    "validating a new deployment").boolean_conf(True)
RECOVERY_TTL_SECONDS = conf("spark.rapids.tpu.recovery.ttlSeconds").doc(
    "Checkpoint expiry: query directories older than this are removed "
    "by the Session.close()/scheduler-shutdown hygiene sweep (0 "
    "disables age-based expiry)").long_conf(86400)
RECOVERY_MAX_BYTES = conf("spark.rapids.tpu.recovery.maxBytes").doc(
    "Cap on total checkpoint bytes under recovery.dir: the hygiene "
    "sweep evicts least-recently-touched query directories until under "
    "the cap (0 disables the cap)").long_conf(4 * 1024 * 1024 * 1024)
RECOVERY_KILL_AFTER_CHECKPOINTS = conf(
    "spark.rapids.tpu.recovery.killAfterCheckpoints").doc(
    "Test hook: SIGKILL the process immediately after the Nth "
    "successful checkpoint write (0 disables).  Drives the "
    "crash-and-resume integration tests").internal().int_conf(0)

# --- incremental streaming execution (streaming/; reference: Structured
# Streaming micro-batches over the Theseus-style checkpoint substrate) -----
STREAMING_ENABLED = conf("spark.rapids.tpu.streaming.enabled").doc(
    "Allow Session.stream(plan): micro-batch continuous queries over "
    "arriving files, with incremental aggregate state persisted "
    "through the recovery checkpoint store so each tick recomputes "
    "only the partitions the new files touch (requires "
    "recovery.enabled for incremental reuse; without it every batch "
    "is a full recompute)").boolean_conf(False)
STREAMING_TRIGGER_INTERVAL_MS = conf(
    "spark.rapids.tpu.streaming.triggerIntervalMs").doc(
    "Micro-batch trigger period, milliseconds: the stream's tick loop "
    "polls the source directories this often; a tick that finds no "
    "new or changed files emits stream_tick_skip and goes back to "
    "sleep (0 means ticks run only via "
    "StreamHandle.process_available())").int_conf(500)
STREAMING_MAX_BATCH_FILES = conf(
    "spark.rapids.tpu.streaming.maxBatchFiles").doc(
    "Cap on NEW files admitted into one micro-batch; a backlog beyond "
    "it is carried to later ticks (oldest first, stable discovery "
    "order) with a stream_batch_capped event per capped tick (0 "
    "disables the cap)").int_conf(0)
STREAMING_BATCH_DEADLINE_MS = conf(
    "spark.rapids.tpu.streaming.batchDeadlineMs").doc(
    "Per-batch deadline SLA, milliseconds from dispatch, enforced "
    "through the scheduler's cooperative CancelToken: a batch past it "
    "unwinds with TpuQueryCancelled, the tick reports the miss "
    "(stream_batch_error) and the ledger stays at the previous batch "
    "— the next tick retries the same cumulative input (0 falls back "
    "to scheduler.queryTimeoutMs)").int_conf(0)
STREAMING_STATE_DIR = conf("spark.rapids.tpu.streaming.stateDir").doc(
    "Directory holding stream ledgers (source fingerprints + batch "
    "commit markers) under <stateDir>/<stream-fingerprint>/; empty "
    "uses <recovery.dir>/streams/ (the ledger then lives beside the "
    "checkpoints it references, which is what crash recovery wants, "
    "in a subtree hygiene sweeps never touch)"
).string_conf("")

# --- serving caches (serving/; reference: parameterized prepared
# statements + plan-template caching per "Accelerating Presto with
# GPUs" — one compile serves millions of distinct literals) ---------------
SERVING_CACHE_ENABLED = conf("spark.rapids.tpu.serving.cache.enabled").doc(
    "Master enable for the serving caches: Session.submit() consults "
    "the plan-template cache (skip planning/fusion for plans that "
    "normalize to a seen skeleton) and the fingerprint-keyed result "
    "cache (a validated hit completes before admission and never "
    "queues).  Session.prepare() works regardless; this gates the "
    "caching of ad-hoc submissions").boolean_conf(False)
SERVING_CACHE_TEMPLATE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.serving.cache.templates.maxEntries").doc(
    "LRU capacity of the in-memory plan-template cache (entries hold "
    "one optimized+fused physical plan per (skeleton fingerprint, "
    "literal binding); eviction drops the planned tree, not any "
    "compiled kernel — those live in the kernel cache)").int_conf(128)
SERVING_CACHE_RESULTS_ENABLED = conf(
    "spark.rapids.tpu.serving.cache.results.enabled").doc(
    "Result-cache tier of the serving subsystem: completed query "
    "results persist as CRC32C-stamped frames keyed by the recovery "
    "query+data fingerprint, and a later submit of the same query "
    "over unchanged inputs is served from the cache without "
    "executing (requires serving.cache.enabled)").boolean_conf(True)
SERVING_CACHE_RESULTS_MAX_BYTES = conf(
    "spark.rapids.tpu.serving.cache.results.maxBytes").doc(
    "Byte budget of the on-disk result cache: storing a new result "
    "evicts least-recently-used entries until the total fits (0 "
    "disables the cap)").long_conf(1024 * 1024 * 1024)
SERVING_CACHE_RESULTS_MAX_ENTRY_BYTES = conf(
    "spark.rapids.tpu.serving.cache.results.maxEntryBytes").doc(
    "Largest single result the cache will store; bigger results "
    "execute normally and are simply not cached (0 disables the "
    "per-entry cap)").long_conf(256 * 1024 * 1024)
SERVING_CACHE_DIR = conf("spark.rapids.tpu.serving.cache.dir").doc(
    "Directory holding cached result frames; empty uses the reserved "
    "serving/ directory under the recovery root, which the recovery "
    "hygiene sweep skips by name (the serving cache runs its own "
    "byte-budget eviction)").string_conf("")

# --- concurrent query scheduler (scheduler/; reference: Theseus-style
# admission + memory arbitration across concurrent queries) ----------------
SCHEDULER_MAX_CONCURRENT = conf(
    "spark.rapids.tpu.scheduler.maxConcurrent").doc(
    "Queries the scheduler runs concurrently; further admitted queries "
    "wait in the bounded priority queue until a slot AND an HBM "
    "reservation are available").int_conf(2)
SCHEDULER_MAX_QUEUED = conf("spark.rapids.tpu.scheduler.maxQueued").doc(
    "Bound on queries waiting for a run slot; a submit beyond "
    "maxConcurrent+maxQueued in-flight queries is shed immediately "
    "(QueryRejected + an admission_reject event) — reject-or-queue "
    "backpressure, never unbounded buffering").int_conf(16)
SCHEDULER_QUEUE_TIMEOUT_MS = conf(
    "spark.rapids.tpu.scheduler.queueTimeoutMs").doc(
    "A queued query not dispatched within this many milliseconds is "
    "shed with QueryRejected + an admission_reject event (0 waits "
    "forever)").int_conf(30000)
SCHEDULER_RESERVATION_FRACTION = conf(
    "spark.rapids.tpu.scheduler.reservationFraction").doc(
    "Fraction of the DeviceManager arena reserved per admitted query "
    "for its lifetime; dispatch requires a free reservation, so the "
    "sum of running reservations never exceeds the arena — the "
    "admission-side HBM budget that keeps concurrent queries from "
    "thrashing the spill path (0 disables reservations)"
).double_conf(0.25)
SCHEDULER_QUERY_TIMEOUT_MS = conf(
    "spark.rapids.tpu.scheduler.queryTimeoutMs").doc(
    "Deadline on a running query, milliseconds, measured from "
    "dispatch: past it the query's CancelToken trips and the query "
    "unwinds cooperatively at its next operator checkpoint with "
    "TpuQueryCancelled (0 disables)").int_conf(0)

# --- multi-tenant QoS: fair admission, aging, preemption, shedding
# (scheduler/qos.py; reference: admission tiers + fair arbitration in
# "Accelerating Presto with GPUs") --------------------------------------
SCHEDULER_PRIORITY_AGING_MS = conf(
    "spark.rapids.tpu.scheduler.priorityAgingMs").doc(
    "Priority aging: for every this-many milliseconds a query waits "
    "in the admission queue its EFFECTIVE priority grows by one, so a "
    "steady stream of high-priority submissions can delay — but never "
    "indefinitely starve — an already-queued low-priority query (0 "
    "disables aging and restores fixed priorities)").int_conf(5000)
SCHEDULER_PREEMPTION_ENABLED = conf(
    "spark.rapids.tpu.scheduler.preemption.enabled").doc(
    "Checkpoint-backed preemption: a strictly higher-priority queued "
    "query blocked on a run slot or its HBM reservation cooperatively "
    "cancels the lowest-priority running query (the zero-leak "
    "CancelToken unwind), requeues it, and on re-admission the "
    "recovery store (recovery.enabled) resumes the victim from its "
    "completed exchange checkpoints — bit-identical results, each "
    "preemption charged against the victim's fault.maxTotalAttempts "
    "budget").boolean_conf(True)
SCHEDULER_TENANT_DEFAULT_WEIGHT = conf(
    "spark.rapids.tpu.scheduler.tenant.default.weight").doc(
    "Fair-share weight of the default tenant; any "
    "scheduler.tenant.<name>.weight key (read dynamically, no "
    "pre-registration) sets another tenant's weight and falls back to "
    "this one.  Dispatch drains per-tenant queues by deficit-weighted "
    "fair share: under contention a tenant with twice the weight "
    "receives twice the dispatch share").double_conf(1.0)
SCHEDULER_TENANT_DEFAULT_MAX_CONCURRENT = conf(
    "spark.rapids.tpu.scheduler.tenant.default.maxConcurrent").doc(
    "Per-tenant cap on concurrently RUNNING queries, 0 = bounded only "
    "by scheduler.maxConcurrent; scheduler.tenant.<name>.maxConcurrent "
    "(dynamic key) overrides it per tenant").int_conf(0)
SCHEDULER_TENANT_DEFAULT_HBM_FRACTION = conf(
    "spark.rapids.tpu.scheduler.tenant.default.hbmFraction").doc(
    "Per-tenant HBM reservation fraction charged per dispatched query, "
    "0 = use scheduler.reservationFraction; "
    "scheduler.tenant.<name>.hbmFraction (dynamic key) overrides it "
    "per tenant").double_conf(0.0)
SCHEDULER_OVERLOAD_QUEUE_WAIT_MS = conf(
    "spark.rapids.tpu.scheduler.overload.queueWaitMs").doc(
    "Overload threshold on the p95 queue wait (recent dispatches plus "
    "queries still waiting): past it the OverloadMonitor declares "
    "overload and new submissions below "
    "scheduler.overload.shedBelowPriority are shed with TpuOverloaded "
    "carrying a retry_after_ms backoff hint (0 disables queue-wait "
    "overload detection)").int_conf(0)
SCHEDULER_OVERLOAD_HBM_FRACTION = conf(
    "spark.rapids.tpu.scheduler.overload.hbmFraction").doc(
    "Overload threshold on arena pressure (DeviceManager allocated / "
    "arena bytes): past it the OverloadMonitor declares overload and "
    "sheds low-tier submissions (0 disables arena-pressure overload "
    "detection)").double_conf(0.0)
SCHEDULER_OVERLOAD_SHED_BELOW_PRIORITY = conf(
    "spark.rapids.tpu.scheduler.overload.shedBelowPriority").doc(
    "While overloaded, a submit with priority below this value is shed "
    "with TpuOverloaded (a typed retryable QueryRejected carrying "
    "retry_after_ms); submissions at or above it are still admitted "
    "under the normal queue bounds").int_conf(1)
SCHEDULER_OVERLOAD_RETRY_AFTER_MS = conf(
    "spark.rapids.tpu.scheduler.overload.retryAfterMs").doc(
    "Base backoff hint carried by TpuOverloaded.retry_after_ms, scaled "
    "up with current queue depth — a shed client should not retry "
    "sooner").int_conf(1000)
SCHEDULER_OVERLOAD_SAMPLE_MS = conf(
    "spark.rapids.tpu.scheduler.overload.sampleMs").doc(
    "OverloadMonitor sampling period, milliseconds: the monitor thread "
    "re-evaluates queue-wait p95 and arena pressure this often (the "
    "state is also re-evaluated inline at every submit), emitting "
    "overload_enter/overload_exit transition events").int_conf(100)

# --- scheduling -----------------------------------------------------------
CONCURRENT_TPU_TASKS = conf("spark.rapids.tpu.sql.concurrentTpuTasks").doc(
    "Number of tasks that may hold the device semaphore concurrently "
    "(reference: spark.rapids.sql.concurrentGpuTasks)").int_conf(2)
TASK_THREADS = conf("spark.rapids.tpu.sql.taskThreads").doc(
    "Host task-runner threads per process (partition-level data "
    "parallelism)").int_conf(8)
TASK_RETRIES = conf("spark.rapids.tpu.sql.taskRetries").doc(
    "Times a failed partition task is re-executed from its lineage "
    "before the query fails (the engine's analogue of Spark task "
    "rescheduling; 0 disables)").int_conf(1)

# --- batch sizing (:289-309) ---------------------------------------------
BATCH_SIZE_BYTES = conf("spark.rapids.tpu.sql.batchSizeBytes").doc(
    "Target byte size for device batches; coalescing aims for this").long_conf(
    512 * 1024 * 1024)
BATCH_SIZE_ROWS = conf("spark.rapids.tpu.sql.batchSizeRows").doc(
    "Soft cap on rows per device batch").int_conf(1 << 22)
READER_BATCH_SIZE_ROWS = conf("spark.rapids.tpu.sql.reader.batchSizeRows").doc(
    "Soft cap on rows per reader batch (reference: "
    "spark.rapids.sql.reader.batchSizeRows)").int_conf(1 << 21)
READER_BATCH_SIZE_BYTES = conf("spark.rapids.tpu.sql.reader.batchSizeBytes").doc(
    "Soft cap on bytes per reader batch").long_conf(512 * 1024 * 1024)
READER_PREFETCH_BATCHES = conf(
    "spark.rapids.tpu.sql.reader.prefetchBatches").doc(
    "Host batches decoded ahead of the device upload per partition "
    "(decode/upload pipelining; 0 disables the prefetch thread)"
).int_conf(2)
BUCKET_MIN_ROWS = conf("spark.rapids.tpu.sql.bucketMinRows").doc(
    "Device batches are padded to power-of-two row buckets >= this, so XLA "
    "compile caches hit across batches (TPU-specific: static shapes)").int_conf(128)

# --- feature gates (:328-449) --------------------------------------------
SQL_ENABLED = conf("spark.rapids.tpu.sql.enabled").doc(
    "Master enable for the plan-rewrite engine").boolean_conf(True)
INCOMPATIBLE_OPS = conf("spark.rapids.tpu.sql.incompatibleOps.enabled").doc(
    "Allow ops whose results may diverge from the host engine in corner "
    "cases (reference: spark.rapids.sql.incompatibleOps.enabled)").boolean_conf(False)
ALLOW_FLOAT_AGG = conf("spark.rapids.tpu.sql.variableFloatAgg.enabled").doc(
    "Allow floating-point aggregation on device.  The device adds a "
    "group's sorted rows block by block (and a TPU holds a float64 as "
    "two float32), the host oracle row by row, so a float SUM/AVG "
    "equals the host's to rounding, not to the bit, and extreme values "
    "(±max, ±inf) can produce different — equally valid — results.  "
    "Set false where a streaming tick or a degraded rung must match "
    "the device's answer bit for bit (reference: "
    "spark.rapids.sql.variableFloatAgg.enabled; default true here "
    "because the device order is deterministic for a fixed plan)"
).boolean_conf(True)

STRING_COLUMN_BYTES_GUARD = conf(
    "spark.rapids.tpu.sql.stringColumnBytesGuard").doc(
    "Fail a device upload whose string byte-matrix would exceed this "
    "many bytes per column.  Byte-matrix HBM is rows x max_len, so one "
    "pathological long string in a wide batch silently multiplies the "
    "footprint (e.g. a 10KB string in a 10M-row column costs ~100GB); "
    "this turns that OOM into a diagnosable error naming the column.  "
    "Shrink reader.batchSizeRows, filter/substring the column, or "
    "raise this limit").int_conf(2 << 30)

# --- string cast gates (reference: RapidsConf.scala:373-403) --------------
CAST_STRING_TO_INTEGER = conf(
    "spark.rapids.tpu.sql.castStringToInteger.enabled").doc(
    "Cast string->integral on device.  Exact for [+-]?digits[.digits] "
    "(fractions truncate); exponent forms ('1e2') become NULL on device "
    "where the host parses them.  Off by default like the reference "
    "(RapidsConf.scala:397) — enable to keep string-cast pipelines on "
    "device").boolean_conf(False)
CAST_STRING_TO_FLOAT = conf(
    "spark.rapids.tpu.sql.castStringToFloat.enabled").doc(
    "Cast string->float on device.  Horner digit accumulation can be a "
    "few ULPs off the host's correctly-rounded parse on long mantissas "
    "(reference: castStringToFloat, same default)").boolean_conf(False)
CAST_STRING_TO_TIMESTAMP = conf(
    "spark.rapids.tpu.sql.castStringToTimestamp.enabled").doc(
    "Cast string->date/timestamp on device: ISO 'YYYY[-MM[-DD]]"
    "[ T]HH[:MM[:SS[.ffffff]]]' in UTC, malformed -> NULL.  Exotic "
    "host-accepted forms (timezone suffixes, >6 fraction digits, "
    "compact dates) become NULL on device.  Off by default like the "
    "reference (RapidsConf.scala:373-403)").boolean_conf(False)
# (no castFloatToString key: float->string stays host-side by design —
# Spark's shortest-repr formatting has no faithful device analogue, see
# ops/cast.py; the reference gates the same divergence behind its
# castFloatToString conf)

# --- whole-stage fusion / kernel cache (plan/fusion.py,
# exec/kernel_cache.py; reference: the per-operator dispatch overhead
# called out by "Data Path Fusion in GPU for Analytical Query
# Processing" — see docs/perf_tuning.md) ----------------------------------
FUSION_ENABLED = conf("spark.rapids.tpu.sql.fusion.enabled").doc(
    "Collapse maximal chains of row-local device execs (Project, "
    "Filter, Expand, Generate) into one fused segment whose single "
    "jitted kernel composes the member compute bodies — one XLA "
    "dispatch per batch per segment, no intermediate HBM "
    "materialization; results are bit-identical to the unfused plan. "
    "Also lets an update-phase aggregate absorb the Filter/Project "
    "chain directly under it and read the filters' keep mask instead "
    "of compacted rows (a keyless float SUM is then equal to the "
    "unfused plan's to rounding)"
).boolean_conf(True)
FUSION_MAX_SEGMENT_EXECS = conf(
    "spark.rapids.tpu.sql.fusion.maxSegmentExecs").doc(
    "Upper bound on member execs per fused segment; a longer row-local "
    "chain is split into several segments (guards XLA compile time on "
    "pathological plans)").int_conf(16)
KERNEL_CACHE_ENABLED = conf("spark.rapids.tpu.sql.kernelCache.enabled").doc(
    "Share jit-compiled kernels across exec instances through the "
    "process-wide KernelCache, keyed by kernel fingerprint and schema "
    "signature (the row-bucket dimension rides the jax shape cache). "
    "Disabled, each exec instance compiles privately; cache counters "
    "still report").boolean_conf(True)
KERNEL_CACHE_MAX_ENTRIES = conf(
    "spark.rapids.tpu.sql.kernelCache.maxEntries").doc(
    "LRU capacity of the shared kernel cache (entries hold compiled "
    "XLA executables; eviction frees them)").int_conf(256)
KERNEL_CACHE_DONATION = conf(
    "spark.rapids.tpu.sql.kernelCache.donation.enabled").doc(
    "Donate input batch buffers (jax donate_argnums) to kernels whose "
    "input is provably single-consumer — fused segments fed by fresh "
    "file-scan uploads — so XLA reuses the HBM in place.  No-op on the "
    "CPU backend, which ignores donation").boolean_conf(True)

# --- test hooks (:456-463) ------------------------------------------------
TEST_ENABLED = conf("spark.rapids.tpu.sql.test.enabled").doc(
    "Test mode: fail if any operator unexpectedly stays on the host engine "
    "(reference: spark.rapids.sql.test.enabled)").internal().boolean_conf(False)
TEST_ALLOWED_NON_TPU = conf("spark.rapids.tpu.sql.test.allowedNonTpu").doc(
    "Comma-separated operator class names permitted to fall back when test "
    "mode is on").internal().string_conf("")

# --- debug ----------------------------------------------------------------
EXPLAIN = conf("spark.rapids.tpu.sql.explain").doc(
    "Plan-rewrite explain mode: NONE, ALL, or NOT_ON_TPU").string_conf("NONE")

# --- aggregation modes (:483-493) ----------------------------------------
HASH_AGG_REPLACE_MODE = conf("spark.rapids.tpu.sql.hashAgg.replaceMode").doc(
    "Which aggregation modes to replace: all, partial, final").string_conf("all")

# --- shuffle / exchange (spark.rapids.shuffle.* :500-576) -----------------
SHUFFLE_TRANSPORT_CLASS = conf("spark.rapids.tpu.shuffle.transport.class").doc(
    "Transport used for device-to-device exchange, instantiated by "
    "reflection like the reference's makeTransport "
    "(RapidsConf.scala:505); the default rides ICI collectives"
).string_conf("spark_rapids_tpu.parallel.collective.IciCollectiveTransport")
SHUFFLE_PARTITIONS = conf("spark.rapids.tpu.sql.shuffle.partitions").doc(
    "Default number of exchange output partitions").int_conf(8)
BROADCAST_THRESHOLD = conf(
    "spark.rapids.tpu.sql.broadcastSizeThreshold").doc(
    "Max estimated build-side bytes for a broadcast hash join (reference: "
    "spark.sql.autoBroadcastJoinThreshold feeding GpuBroadcastMeta); "
    "set to 0 to force shuffled joins").long_conf(10 * 1024 * 1024)
SHUFFLE_MODE = conf("spark.rapids.tpu.shuffle.mode").doc(
    "Exchange data path: device (shuffle blocks stay resident in HBM as "
    "packed blocks built by one jitted partition-build kernel — no "
    "d2h/h2d round-trip per partition), host (every block is staged to "
    "host memory and CRC32C-stamped immediately, the pre-device "
    "behavior), or auto (device while the HBM arena has headroom, host "
    "under memory pressure).  Range partitioning always uses the host "
    "path (bounds need a full host-side drain); the degradation ladder "
    "re-executes a failed device-shuffle query on the host path before "
    "falling to the CPU rung").string_conf("auto")
SHUFFLE_TARGET_BATCH_ROWS = conf(
    "spark.rapids.tpu.shuffle.targetBatchRows").doc(
    "Exchange writes coalesce sub-target input batches up to this many "
    "rows before the partition-build kernel runs, so a stream of tiny "
    "batches costs one build dispatch instead of N").int_conf(32768)

# --- adaptive query execution (adaptive/; reference: Spark 3.0 AQE —
# AdaptiveSparkPlanExec + ShufflePartitionsUtil + OptimizeSkewedJoin +
# DynamicJoinSelection, re-planned from exact shuffle stats) ---------------
ADAPTIVE_ENABLED = conf("spark.rapids.tpu.sql.adaptive.enabled").doc(
    "Adaptive query execution: re-optimize the unexecuted plan suffix "
    "between stages from exact materialized shuffle statistics — "
    "partition coalescing, skew-join splitting and dynamic broadcast "
    "conversion.  Rewrites are bit-identical to the static plan; "
    "decisions are recorded as aqe_* telemetry events and rendered in "
    "EXPLAIN ANALYZE").boolean_conf(True)
ADAPTIVE_TARGET_PARTITION_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.targetPartitionBytes").doc(
    "Post-shuffle partition coalescing target: adjacent partitions "
    "whose combined estimated bytes stay under this are merged into "
    "one reader partition (reference: "
    "spark.sql.adaptive.advisoryPartitionSizeInBytes)").long_conf(
    64 * 1024 * 1024)
ADAPTIVE_AUTO_BROADCAST_THRESHOLD = conf(
    "spark.rapids.tpu.sql.adaptive.autoBroadcastJoinThreshold").doc(
    "Max OBSERVED build-side bytes for demoting a planned "
    "shuffled-hash join to a broadcast join at runtime, skipping the "
    "stream-side exchange (reference: the runtime re-check of "
    "spark.sql.autoBroadcastJoinThreshold inside AQE; 0 disables "
    "dynamic conversion)").long_conf(10 * 1024 * 1024)
ADAPTIVE_SKEW_FACTOR = conf(
    "spark.rapids.tpu.sql.adaptive.skewedPartitionFactor").doc(
    "A join partition is skewed when its row count exceeds this factor "
    "x the median partition rows (reference: "
    "spark.sql.adaptive.skewJoin.skewedPartitionFactor)").double_conf(4.0)
ADAPTIVE_SKEW_THRESHOLD_BYTES = conf(
    "spark.rapids.tpu.sql.adaptive.skewedPartitionThresholdBytes").doc(
    "Skew splitting additionally requires the skewed partition's "
    "estimated bytes to exceed this floor, so tiny-but-lopsided "
    "partitions are not split for nothing (reference: "
    "spark.sql.adaptive.skewJoin.skewedPartitionThresholdInBytes)"
).long_conf(64 * 1024 * 1024)
ADAPTIVE_MAX_SKEW_SLICES = conf(
    "spark.rapids.tpu.sql.adaptive.maxSkewSlices").doc(
    "Upper bound on the contiguous sub-slices one skewed partition is "
    "cut into (each slice replicates the build-side partition, so this "
    "bounds the replication cost)").int_conf(8)

# --- ML interop -----------------------------------------------------------
EXPORT_COLUMNAR_RDD = conf("spark.rapids.tpu.sql.exportColumnarRdd").doc(
    "Allow zero-copy export of device batches to user code (JAX arrays); "
    "reference: spark.rapids.sql.exportColumnarRdd").boolean_conf(False)

# --- metrics / tracing ----------------------------------------------------
TRACE_ENABLED = conf("spark.rapids.tpu.sql.trace.enabled").doc(
    "Wrap hot-path sections in jax.profiler trace annotations (reference: "
    "NVTX ranges)").boolean_conf(False)

# --- telemetry (telemetry/; reference: the per-exec SQLMetrics surfaced
# in the SQL UI + the Spark event log / history server) --------------------
TELEMETRY_ENABLED = conf("spark.rapids.tpu.telemetry.enabled").doc(
    "Query telemetry: hierarchical spans (query -> stage -> exec -> "
    "attempt), the structured event log, EXPLAIN-ANALYZE profiles "
    "(Session.profile_report()) and the metrics exporters "
    "(telemetry/export.py).  Off by default: every emitter is a no-op "
    "and the metrics snapshot is unchanged").boolean_conf(False)
TELEMETRY_EVENT_LOG_DIR = conf(
    "spark.rapids.tpu.telemetry.eventLog.dir").doc(
    "Directory for the append-only JSONL event log (one "
    "events-<queryId>.jsonl per query — the history-server analogue); "
    "empty keeps events only in the bounded in-memory ring").string_conf("")
TELEMETRY_MAX_QUERY_PROFILES = conf(
    "spark.rapids.tpu.telemetry.maxQueryProfiles").doc(
    "Completed query profiles retained on the Session "
    "(Session.profiles / Session.last_profile); the oldest profile is "
    "dropped first").int_conf(8)
TELEMETRY_SAMPLE_HBM_MS = conf(
    "spark.rapids.tpu.telemetry.sampleHbmMs").doc(
    "HBM-watermark sampling period, milliseconds: a per-query sampler "
    "thread records the DeviceManager's allocated/peak-bytes timeline "
    "into the profile and exporters (0 disables the sampler)").int_conf(0)
TELEMETRY_MAX_EVENTS = conf("spark.rapids.tpu.telemetry.maxEvents").doc(
    "Capacity of the per-query in-memory event ring (oldest events are "
    "dropped first and counted); the JSONL file sink is append-only "
    "and unbounded").int_conf(4096)
TELEMETRY_PROFILER_ENABLED = conf(
    "spark.rapids.tpu.telemetry.profiler.enabled").doc(
    "Per-kernel dispatch profiler: accumulates dispatch count, enqueue "
    "wall, rows/bytes and shape-bucketing padding waste per kernel "
    "fingerprint (telemetry/profiler.py), rendered as the Kernel "
    "dispatches table in Session.profile_report().  "
    "Independent of telemetry.enabled; the disabled hot-path cost is "
    "one attribute read per dispatch").boolean_conf(False)
TELEMETRY_TRACE_DIR = conf("spark.rapids.tpu.telemetry.trace.dir").doc(
    "Directory for Chrome-trace/Perfetto JSON timelines (one "
    "trace-<queryId>.json per query, written atomically at query "
    "finish): span tree as duration events, HBM sampler timeline as a "
    "counter track, scheduler/streaming events as instants.  Empty "
    "disables trace export; requires telemetry.enabled").string_conf("")
TELEMETRY_HISTOGRAM_WINDOW_S = conf(
    "spark.rapids.tpu.telemetry.histogram.windowS").doc(
    "Sliding-window span, seconds, for latency-histogram percentile "
    "readouts (scheduler queue-wait, per-tenant query latency, "
    "streaming batch latency).  Cumulative bucket counts exported to "
    "prometheus are unaffected (they are monotonic by "
    "definition)").int_conf(300)


class TpuConf:
    """Immutable view over a key->value dict with typed accessors.

    ``TpuConf({...})`` or ``TpuConf()`` for defaults."""

    def __init__(self, settings: Optional[Dict[str, Any]] = None):
        self._settings = dict(settings or {})

    def get(self, entry: ConfEntry):
        return entry.get(self._settings)

    def get_key(self, key: str):
        e = lookup(key)
        if e is None:
            return self._settings.get(key)
        return e.get(self._settings)

    def is_operator_enabled(self, kind: str, name: str) -> bool:
        e = lookup(f"spark.rapids.tpu.sql.{kind}.{name}")
        if e is None:
            return True
        return e.get(self._settings)

    def with_settings(self, **kv) -> "TpuConf":
        s = dict(self._settings)
        s.update(kv)
        return TpuConf(s)

    def set(self, key: str, value) -> "TpuConf":
        s = dict(self._settings)
        s[key] = value
        return TpuConf(s)

    # Convenience typed properties used on hot paths
    @property
    def batch_size_bytes(self) -> int:
        return self.get(BATCH_SIZE_BYTES)

    @property
    def batch_size_rows(self) -> int:
        return self.get(BATCH_SIZE_ROWS)

    @property
    def is_sql_enabled(self) -> bool:
        return self.get(SQL_ENABLED)

    @property
    def is_test_enabled(self) -> bool:
        return self.get(TEST_ENABLED)

    @property
    def allowed_non_tpu(self) -> List[str]:
        raw = self.get(TEST_ALLOWED_NON_TPU)
        return [s.strip() for s in raw.split(",") if s.strip()]

    @property
    def explain(self) -> str:
        return str(self.get(EXPLAIN)).upper()

    @property
    def concurrent_tpu_tasks(self) -> int:
        return self.get(CONCURRENT_TPU_TASKS)

    @property
    def shuffle_partitions(self) -> int:
        return self.get(SHUFFLE_PARTITIONS)

    def items(self):
        return self._settings.items()
