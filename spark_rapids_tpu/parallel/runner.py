"""General distributed plan execution over a jax device mesh.

Reference analogue: the full distributed execution capability of the
RAPIDS shuffle — *any* exchange in *any* physical plan can ship any
batch to any peer (GpuShuffleExchangeExec.scala:60-244 map side,
RapidsCachingReader.scala:49-170 + RapidsShuffleClient.scala:452-555
read side).  The TPU-native form keeps the reference's stage model
(Spark cuts the plan DAG at exchanges) but replaces the whole
client/server/bounce-buffer transport with compiled collectives:

    stage     = the maximal exchange-free subtree, lowered to ONE pure
                per-shard function and jitted under shard_map
    exchange  = `lax.all_to_all` at the top of the producing stage
                (parallel/exchange.py), riding ICI
    broadcast = `lax.all_gather` of the build side inside the consuming
                stage (the GpuBroadcastExchangeExec.scala:215 analogue)
    host      = orchestrates *between* stages only — retiling row
                buckets and retrying joins whose static output capacity
                overflowed — the control-plane role the shuffle catalogs
                play in the reference (ShuffleBufferCatalog.scala)

Operators lower through the same pure ``_compute`` kernels the local
engine jits, so local and distributed execution share one kernel
library; only joins need the trace-safe ``join_static`` variant
(output sizing cannot host-sync inside shard_map — capacity is static
with overflow-detect-and-retry instead).

Non-distributable subtrees (host fallbacks, scans, unions of scans)
execute through the local engine and are split row-wise across the
mesh — the analogue of Spark tasks producing the map-side input.
"""
from __future__ import annotations

import itertools
import logging
import random
import time
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..data.column import (DeviceBatch, DeviceColumn, HostBatch,
                           bucket_rows, device_to_host, host_to_device)
from ..exec.kernel_cache import _CachedKernel, jit_kernel
from ..fault.errors import (TpuPayloadCorruption, TpuStageCrash,
                            TpuStageTimeout)
from ..fault.injector import maybe_inject_fault
from ..fault.stats import GLOBAL as _fault_stats
from ..memory.semaphore import DeviceSemaphoreTimeout
from ..ops.kernels.gather import compact
from ..telemetry import spans as tspans
from ..telemetry.events import emit_event
from ..utils import hashing
from ..utils.tracing import device_phase, trace_range
from . import exchange as X
from .mesh import DATA_AXIS

log = logging.getLogger(__name__)

_MAX_JOIN_RETRIES = 4

#: the typed faults a stage/leaf re-execution can recover from — the
#: lineage is explicit in plan_stages, so re-running the failed unit is
#: always safe; anything outside this family is a genuine bug
RECOVERABLE_FAULTS = (TpuStageCrash, TpuStageTimeout,
                      TpuPayloadCorruption, DeviceSemaphoreTimeout)


def _max_dest_count(pids, num_parts: int):
    """Largest per-destination row count — the exchange's true capacity
    demand (rows with the drop sentinel ``num_parts`` excluded)."""
    import jax
    import jax.numpy as jnp

    counts = jax.ops.segment_sum(
        jnp.ones_like(pids, dtype=jnp.int32), pids,
        num_segments=num_parts + 1)
    return counts[:num_parts].max()


class DistributedUnsupported(Exception):
    """Raised when a plan node cannot be lowered to the SPMD form."""


class _LeafRef:
    """Placeholder for a locally-executed input, stacked on the mesh."""

    def __init__(self, idx: int, node):
        self.idx = idx
        self.node = node


class _StageRef:
    """Placeholder for the output of an earlier stage (post-exchange).
    Carries the producing exchange's partitioning so consumers can tell
    whether their distribution requirement is already satisfied."""

    def __init__(self, stage_id: int, partitioning=None):
        self.stage_id = stage_id
        self.partitioning = partitioning


class _ResumedPartitioning:
    """Sentinel partitioning for a stage output restored from a recovery
    checkpoint onto a DIFFERENT-size mesh (elastic shrink).  The restored
    shards no longer satisfy the producing exchange's placement contract,
    so every distribution-sensitive consumer must repair: hash/range/
    single checks all reject this sentinel, and joins see an explicit
    'repair' verdict instead of 'unsupported'."""

    def signature(self):
        return (type(self).__name__,)


class _BcastRef:
    """Placeholder for a precomputed (replicated) broadcast build side —
    gathered ONCE per query, reused across capacity retries and stream
    partitions (reference: GpuBroadcastExchangeExec.scala:215-247
    materializes the relation once and shares it)."""

    def __init__(self, op):
        self.op = op


class _InputRef:
    """The ``slot``-th input of a stage program, in the tree ``_detach``
    cuts loose from its request: what is left of a _LeafRef, of a
    _StageRef (its partitioning, which the lowering reads) or of a
    precomputed broadcast build side."""

    def __init__(self, slot: int, partitioning=None):
        self.slot = slot
        self.partitioning = partitioning


class _Unsignable(Exception):
    """An operator or a partitioning whose ``signature`` is None: its
    stage's program is compiled for the request and not shared."""


def _said(of, signature):
    if signature is None:
        raise _Unsignable(type(of).__name__)
    return signature


class _StageProgram:
    """The body of one stage program: the lowering of a detached stage
    tree under ``shard_map``, with its capacities as a static argument
    (as a join's ``_expand`` takes its own).  It is what the kernel
    cache keeps, and so holds no request: a runner that only lowers,
    twins of the operators, the names of the capacity demands.

    Beside it, by the inputs' shapes: ``used``, the capacities each
    trace was built at (the overflow verdict compares demands with
    them), and ``settled``, those the last request ended on, where the
    next one starts."""

    def __init__(self, lowering, tree, aux_keys: List[str], post):
        self.__name__ = self.__qualname__ = "stage"
        self.lowering = lowering
        self.tree = tree
        self.aux_keys = aux_keys
        self.post = post
        self.used: Dict = {}
        self.settled: Dict = {}

    def __call__(self, caps: Tuple, *stacked):
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        low = self.lowering
        used: Dict[str, int] = {}

        def per_shard(*shards):
            env = [X.squeeze_leading(b) for b in shards]
            aux: Dict = {}
            out = low._lower(self.tree, env, aux, dict(caps), used)
            if self.post is not None:
                out = self.post(out)
            # aux (capacity demands) replicated via pmax so EVERY
            # controller process reads the same overflow verdict and
            # takes the same retry path (multi-process SPMD needs
            # identical host control flow on all controllers).
            # As int32, saturated: the TPU compiler lowers no 64-bit
            # all-reduce but a sum ("Supported lowering only of Sum
            # all reduce"), and a row count past 2^31 must still
            # read as an overflow
            return (X.unsqueeze_leading(out),
                    tuple(jax.lax.pmax(
                        jnp.minimum(aux[k].reshape(()), 2 ** 31 - 1)
                        .astype(jnp.int32), low.axis)
                        for k in self.aux_keys))

        spec = P(low.axis)
        out = jax.shard_map(
            per_shard, mesh=low.mesh, in_specs=(spec,) * len(stacked),
            out_specs=(spec, (P(),) * len(self.aux_keys)))(*stacked)
        self.used[caps, _shapes(stacked)] = used
        return out


def _shapes(batches) -> Tuple:
    import jax

    return tuple(a.shape for a in jax.tree_util.tree_leaves(batches))


class _Stage:
    def __init__(self, sid: int, root):
        self.sid = sid
        self.root = root          # exec tree with _LeafRef/_StageRef leaves
        self.inputs: List[object] = []   # _LeafRef | _StageRef, trace order


class DistributedRunner:
    """Executes a TPU physical plan SPMD over a mesh.

    ``run(plan, ctx)`` returns the collected HostBatch (rows of all
    output partitions concatenated, like ``collect``)."""

    def __init__(self, mesh, min_bucket_rows: int = 128, transport=None):
        from .collective import IciCollectiveTransport

        self.mesh = mesh
        self.axis = mesh.axis_names[0] if mesh.axis_names else DATA_AXIS
        self.n = int(np.prod([d for d in mesh.devices.shape]))
        self.min_bucket = min_bucket_rows
        #: pluggable exchange data path (reference: makeTransport
        #: reflection on spark.rapids.shuffle.transport.class)
        self.transport = transport or IciCollectiveTransport(self.axis)
        #: ids of the devices that hold a shard of a placed leaf — a
        #: mesh run that never left device 0 shows here, as
        #: ``distributed.numShardDevices`` in the session's metrics
        self.shard_device_ids: set = set()
        #: this request's stage-program dispatches: those that traced
        #: nothing, those that traced (and so compiled, or read the
        #: persistent cache), and the attempts a capacity overflow cost
        self.stage_hits = 0
        self.stage_compiles = 0
        self.stage_retries = 0

    def metrics(self) -> Dict[str, int]:
        """The request's ``distributed.*`` counters, for
        ``Session.last_metrics``."""
        return {
            "distributed.numShardDevices": len(self.shard_device_ids),
            "distributed.stagePrograms.hits": self.stage_hits,
            "distributed.stagePrograms.compiles": self.stage_compiles,
            "distributed.stageRetries": self.stage_retries}

    # ---------------- fault tolerance ---------------------------------
    @staticmethod
    def _fault_conf(ctx):
        conf = getattr(ctx, "conf", None)
        if conf is None:
            from ..config import TpuConf

            conf = TpuConf()
        return conf

    def _with_watchdog(self, fn, timeout_ms: int, what: str):
        """Run one stage/leaf attempt under the ``fault.stageTimeoutMs``
        deadline: the attempt runs on a worker thread and a deadline
        miss abandons it with :class:`TpuStageTimeout` (the thread
        itself cannot be killed; the retried attempt races it on pure
        compiled programs, which is safe).  Disabled (direct call) when
        the deadline is 0 — multi-controller deployments must only arm
        it with replicated confs, or recovery control flow desyncs."""
        if not timeout_ms or timeout_ms <= 0:
            return fn()
        import queue as _queue
        import threading as _threading

        box: "_queue.Queue" = _queue.Queue(maxsize=1)
        abandon = _threading.Event()

        def attempt():
            from ..fault.injector import bind_attempt_abandon

            # the abandon flag lets the watchdog reach INTO the
            # attempt: injected delays poll it, so an abandoned
            # straggler terminates instead of orphan-sleeping
            bind_attempt_abandon(abandon)
            try:
                box.put(("ok", fn()))
            except BaseException as e:  # noqa: BLE001
                box.put(("err", e))
            finally:
                bind_attempt_abandon(None)

        # a daemon thread, NOT a ThreadPoolExecutor: futures workers
        # are joined at interpreter exit, so one abandoned hung attempt
        # would block shutdown — the exact hang the watchdog exists to
        # prevent.  The attempt runs off-thread, so the telemetry
        # binding is captured here and attached in the worker.
        t = _threading.Thread(
            target=tspans.bound(tspans.capture(), attempt),
            daemon=True, name="stage-watchdog")
        t.start()
        try:
            kind, val = box.get(timeout=timeout_ms / 1000.0)
        except _queue.Empty:
            abandon.set()
            _fault_stats.add("numWatchdogTrips", 1)
            emit_event("watchdog_trip", site=what,
                       timeout_ms=timeout_ms)
            raise TpuStageTimeout(
                f"{what} exceeded fault.stageTimeoutMs={timeout_ms}ms "
                "— abandoning the hung attempt and re-executing from "
                "lineage", site=what) from None
        if kind == "err":
            raise val
        return val

    def _recover(self, fn, ctx, what: str):
        """Bounded re-execution of one stage/leaf from lineage
        (reference: Spark's task/stage rescheduling; the stage plan is
        the explicit lineage here).  Recoverable faults — crash,
        watchdog trip, payload corruption, semaphore timeout — retry up
        to ``fault.maxStageRetries`` times with PR-1's bounded backoff
        + seeded jitter; exhaustion re-raises for the degradation
        ladder (fault/ladder.py)."""
        from ..config import (FAULT_MAX_STAGE_RETRIES,
                              FAULT_STAGE_TIMEOUT_MS,
                              RETRY_BACKOFF_BASE_MS, RETRY_BACKOFF_MAX_MS,
                              RETRY_BACKOFF_SEED)
        from ..memory.retry import backoff_delay_s

        conf = self._fault_conf(ctx)
        timeout_ms = conf.get(FAULT_STAGE_TIMEOUT_MS)
        max_retries = max(0, conf.get(FAULT_MAX_STAGE_RETRIES))
        rng = random.Random(conf.get(RETRY_BACKOFF_SEED))
        for attempt in range(max_retries + 1):
            try:
                with tspans.span(f"attempt[{attempt}]", kind="attempt",
                                 what=what):
                    return self._with_watchdog(fn, timeout_ms, what)
            except RECOVERABLE_FAULTS as e:
                if attempt == max_retries:
                    raise
                _fault_stats.add("numStageRetries", 1)
                emit_event("stage_retry", site=what, attempt=attempt,
                           error=type(e).__name__)
                log.warning("%s failed (%s: %s) — re-executing from "
                            "lineage (attempt %d/%d)", what,
                            type(e).__name__, e, attempt + 1,
                            max_retries)
                time.sleep(backoff_delay_s(
                    attempt, conf.get(RETRY_BACKOFF_BASE_MS),
                    conf.get(RETRY_BACKOFF_MAX_MS), rng))
        raise AssertionError("stage recovery must return or raise")

    def _verify_host_roundtrip(self, shards: List[HostBatch], ctx,
                               site: str = "host.stack"):
        """Exchange host round-trip integrity: CRC32C-stamp the staged
        per-shard batches on the write side and verify them before mesh
        placement.  A mismatch raises TpuPayloadCorruption, which the
        stage-retry machinery answers by re-draining the leaf from
        lineage.  ``corrupt`` injection damages one staged COPY after
        stamping, so the verify has a genuine mismatch to catch.

        The stamp/verify pass costs a CRC over the staged data, so it
        runs only when forced on (``fault.checksum.hostRoundtrip``) or
        while a corrupt injector is armed (the CI sweep)."""
        from ..config import (FAULT_CHECKSUM_ENABLED,
                              FAULT_HOST_ROUNDTRIP_CHECKSUM)
        from ..fault import injector as FI
        from ..fault import integrity

        conf = self._fault_conf(ctx)
        if not conf.get(FAULT_CHECKSUM_ENABLED):
            return shards
        if not conf.get(FAULT_HOST_ROUNDTRIP_CHECKSUM):
            inj = FI.get_fault_injector()
            if inj is None or inj.fault_type != "corrupt":
                return shards
        stamps = integrity.stamp_host_batches(shards)
        if FI.maybe_corrupt(site):
            shards = list(shards)
            for i, hb in enumerate(shards):
                if hb.num_rows:
                    shards[i] = integrity.corrupted_copy(hb)
                    break
        integrity.verify_host_batches(shards, stamps, site)
        return shards

    # ---------------- stage splitting ---------------------------------
    def _split(self, node, stages: List[_Stage], leaves: List[_LeafRef]):
        from ..exec import basic as B
        from ..exec.aggregate import TpuHashAggregateExec
        from ..exec.coalesce import TpuCoalesceBatchesExec
        from ..exec.exchange import TpuShuffleExchangeExec
        from ..exec.fused import TpuFusedSegmentExec
        from ..exec.generate import TpuGenerateExec
        from ..exec.joins import TpuHashJoinExec
        from ..exec.sort import TpuSortExec
        from ..exec.window import TpuWindowExec

        distributable = (B.TpuProjectExec, B.TpuFilterExec,
                         B.TpuLocalLimitExec, B.TpuExpandExec,
                         B.TpuUnionExec, TpuHashAggregateExec,
                         TpuCoalesceBatchesExec, TpuSortExec,
                         TpuWindowExec, TpuGenerateExec, TpuHashJoinExec,
                         TpuFusedSegmentExec)

        if isinstance(node, TpuShuffleExchangeExec):
            # the exchange terminates its producing stage
            body = self._split(node.children[0], stages, leaves)
            stage = _Stage(len(stages), (node, body))
            stages.append(stage)
            return _StageRef(stage.sid, node.partitioning)
        if isinstance(node, distributable):
            kids = [self._split(c, stages, leaves) for c in node.children]
            return (node, *kids)
        # anything else (host subtree, transitions, scans) runs locally
        ref = _LeafRef(len(leaves), node)
        leaves.append(ref)
        return ref

    def plan_stages(self, root) -> List[Tuple[_Stage, List[object]]]:
        """Split ``root`` (a TpuExec tree; any DeviceToHostExec root is
        stripped) into stages.  The last stage carries the plan root."""
        from ..exec.transitions import DeviceToHostExec

        while isinstance(root, DeviceToHostExec):
            root = root.children[0]
        stages: List[_Stage] = []
        leaves: List[_LeafRef] = []
        top = self._split(root, stages, leaves)
        final = _Stage(len(stages), top)
        stages.append(final)
        return stages, leaves

    # ---------------- leaf execution ----------------------------------
    def _run_leaf(self, node, ctx, data=None) -> DeviceBatch:
        """Execute a non-distributable subtree locally and place it on
        the mesh.  Partitions are drained CONCURRENTLY (task thread
        pool) and assigned round-robin to shards, so input decode
        parallelizes and no global host concat funnels every byte
        through one array (reference: each task reads its own split,
        GpuParquetScan.scala:174).  When the source has too few
        partitions to cover the mesh, rows are re-split evenly.
        ``data``: already-executed partitions of ``node`` (the
        multi-process runner probes the partition count before deciding
        its ownership path — re-executing here would build the subtree
        twice)."""
        from ..exec.base import TpuExec
        from ..plan.physical import _empty_batch

        is_dev = isinstance(node, TpuExec)
        if data is None:
            data = node.execute_columnar(ctx) if is_dev \
                else node.execute(ctx)
        n_parts = data.n_partitions

        sem = None
        if ctx is not None and getattr(ctx, "session", None) is not None \
                and ctx.session.device_manager is not None:
            sem = ctx.session.device_manager.semaphore

        def drain(pid: int) -> List[HostBatch]:
            # task-scoped semaphore release (reference: GpuSemaphore's
            # task-completion listener, GpuSemaphore.scala:101-160) —
            # the H2D iterators inside acquire lazily; without this the
            # pool threads leak every permit and the SECOND leaf of any
            # plan deadlocks (r3 Weak #1)
            maybe_inject_fault("leaf.drain")
            try:
                if is_dev:
                    return [device_to_host(db)
                            for db in data.iterator(pid)]
                return list(data.iterator(pid))
            finally:
                if sem is not None:
                    sem.release_all()

        threads = 1
        if ctx is not None and n_parts > 1:
            from ..config import TASK_THREADS

            threads = min(ctx.conf.get(TASK_THREADS), n_parts)
        spec = None
        if ctx is not None:
            from .elastic import SpeculationMonitor

            spec = SpeculationMonitor.from_conf(ctx.conf)
        if threads > 1 or spec is not None:
            # elastic drain collector (elastic.py): same concurrent
            # semaphore-gated pool as before, plus straggler
            # speculation when ``speculation.enabled`` — a shard whose
            # drain outlives the rolling latency baseline gets ONE
            # duplicate attempt, first result wins, the loser is
            # cancelled through its own token and unwinds zero-leak
            from .elastic import drain_with_speculation

            got = drain_with_speculation(
                list(range(n_parts)), drain, max_threads=threads,
                site="leaf.drain", monitor=spec)
            per_pid = [got[p] for p in range(n_parts)]
        else:
            per_pid = [drain(p) for p in range(n_parts)]

        shard_lists: List[List[HostBatch]] = [[] for _ in range(self.n)]
        for pid, bs in enumerate(per_pid):
            shard_lists[pid % self.n].extend(
                b for b in bs if b.num_rows)
        nonempty = sum(1 for bs in shard_lists if bs)
        if nonempty <= max(1, self.n // 4):
            # too few source partitions to cover the mesh: fall back to
            # an even row split of the (small) concatenated input
            host = [b for bs in shard_lists for b in bs]
            big = (HostBatch.concat(host) if host
                   else _empty_batch(node.schema))
            n_rows = big.num_rows
            chunk = -(-n_rows // self.n) if n_rows else 0
            shards = [big.slice(min(p * chunk, n_rows),
                                min(p * chunk + chunk, n_rows))
                      for p in range(self.n)]
        else:
            shards = [HostBatch.concat(bs) if bs
                      else _empty_batch(node.schema)
                      for bs in shard_lists]
        shards = self._verify_host_roundtrip(shards, ctx)
        with trace_range("MeshPlace"):
            placed = self._place(self._stack_host(shards))
        self.shard_device_ids.update(
            s.device.id for s in placed.num_rows.addressable_shards)
        return placed

    def _place(self, stacked: DeviceBatch) -> DeviceBatch:
        """Put a host-stacked [n, ...] batch onto the mesh (overridden
        by the multi-process runner to place only addressable shards)."""
        return X.stack_to_mesh(self.mesh, stacked)

    def _stack_host(self, shards: List[HostBatch]) -> DeviceBatch:
        """Build the stacked [n_shards, bucket, ...] arrays from one
        HostBatch per shard (string widths unified to the global max so
        every shard's columns are shape-equal)."""
        from .. import types as T
        from ..data import strings as dstrings

        bucket = bucket_rows(
            max(max((b.num_rows for b in shards), default=0), 1),
            self.min_bucket)
        num_rows = np.asarray([b.num_rows for b in shards],
                              dtype=np.int32)
        schema = shards[0].schema
        cols = []
        for ci, f in enumerate(schema):
            validity = np.zeros((self.n, bucket), dtype=np.bool_)
            if f.dtype.id is T.TypeId.STRING:
                encs = [dstrings.encode(b.columns[ci].data,
                                        b.columns[ci].validity)
                        for b in shards]
                w = max(max((e[0].shape[1] for e in encs), default=1), 1)
                data = np.zeros((self.n, bucket, w), dtype=np.uint8)
                lengths = np.zeros((self.n, bucket), dtype=np.int32)
                for p, (b, (bm, ln)) in enumerate(zip(shards, encs)):
                    k = b.num_rows
                    data[p, :k, :bm.shape[1]] = bm
                    lengths[p, :k] = ln
                    validity[p, :k] = b.columns[ci].is_valid()
                cols.append(DeviceColumn(f.dtype, data, validity,
                                         lengths))
            else:
                data = np.zeros((self.n, bucket), dtype=f.dtype.np_dtype)
                for p, b in enumerate(shards):
                    c = b.columns[ci]
                    k = b.num_rows
                    valid = c.is_valid()
                    src = np.where(valid, c.data, np.zeros_like(c.data)) \
                        if c.validity is not None else c.data
                    data[p, :k] = src
                    validity[p, :k] = valid
                cols.append(DeviceColumn(f.dtype, data, validity))
        return DeviceBatch(schema, cols, num_rows)

    # ---------------- lowering ----------------------------------------
    def _exchange_pids(self, exch, batch: DeviceBatch):
        """Partition ids for the distributed exchange: always over the
        mesh size (the distributed partition count), padding rows get
        the drop sentinel."""
        import jax.numpy as jnp

        from ..shuffle.partitioning import (HashPartitioning,
                                            RangePartitioning,
                                            RoundRobinPartitioning,
                                            SinglePartitioning)

        part = exch.partitioning
        n = self.n
        if isinstance(part, SinglePartitioning):
            pids = jnp.zeros(batch.padded_rows, dtype=jnp.int32)
        elif isinstance(part, RoundRobinPartitioning):
            pids = (jnp.arange(batch.padded_rows, dtype=jnp.int32) % n)
        elif isinstance(part, HashPartitioning):
            return self._hash_pids_by_exprs(batch, part.keys, exch.schema)
        elif isinstance(part, RangePartitioning):
            # sampled device bounds (reference:
            # GpuRangePartitioner.scala:33-104) — the same traced
            # sample/all_gather/bounds-compare the distributed sort
            # uses, so rows spread across ALL shards in sort-key order
            # instead of funnelling to shard 0
            pids = self._range_pids(batch, part._bound_keys)
        else:
            raise DistributedUnsupported(
                f"partitioning {type(part).__name__}")
        return jnp.where(batch.row_mask(), pids, n)

    # ----- distribution requirements ----------------------------------
    @staticmethod
    def _source_partitioning(kid):
        """The partitioning a subtree's rows already satisfy, looking
        through passthrough ops (coalesce)."""
        from ..exec.coalesce import TpuCoalesceBatchesExec

        while isinstance(kid, tuple) and isinstance(
                kid[0], TpuCoalesceBatchesExec):
            kid = kid[1]
        return getattr(kid, "partitioning", None)

    def _gather_single(self, batch: DeviceBatch) -> DeviceBatch:
        """Collective: move every row to shard 0 (ordering across source
        shards preserved — all_to_all tiles arrive in peer order)."""
        import jax.numpy as jnp

        pids = jnp.where(batch.row_mask(), 0, self.n)
        return self.transport.exchange(batch, pids, self.n)

    @device_phase("shuffle.hashPids")
    def _hash_pids_by_exprs(self, batch: DeviceBatch, exprs, schema):
        """Hash partition ids on expression keys — the one place the
        runner hashes, for the planned exchanges and for the ones it
        adds itself (join-colocation repair, complete-mode aggregates,
        window partition-by), whose keys no plan rule looked at: a key
        the device cannot hash the way Spark does ends the lowering
        with its reason before anything is traced for it."""
        import jax.numpy as jnp

        from ..ops.expression import as_device_column, bind_references

        bound = [bind_references(k, schema) for k in exprs]
        for k in bound:
            gap = hashing.device_hash_gap(k.dtype)
            if gap is not None:
                raise DistributedUnsupported(
                    f"mesh hash exchange on {k.sql()}: {gap}")
        cols = [as_device_column(k.eval_tpu(batch), batch.padded_rows)
                for k in bound]
        pids = hashing.pmod(hashing.hash_device_batch(cols),
                            self.n).astype(jnp.int32)
        return jnp.where(batch.row_mask(), pids, self.n)

    def _exchange_by_exprs(self, batch: DeviceBatch, exprs,
                           schema) -> DeviceBatch:
        """Collective hash repartition on expression keys (colocates
        equal keys so per-shard group/window computation is globally
        correct)."""
        pids = self._hash_pids_by_exprs(batch, exprs, schema)
        return self.transport.exchange(batch, pids, self.n)

    def _range_pids(self, batch: DeviceBatch, sort_keys):
        """Traced device range partitioning (reference:
        GpuRangePartitioner.scala:33-104 — sample, bounds, device bound
        compare).  Per shard: strided sample of the sort-key uint32
        passes; `all_gather` so every shard sees every sample; global
        quantile bounds; pid = #bounds the row exceeds
        lexicographically.

        Correctness needs only the monotone bound compare (row <=
        bound_i => pid <= i), which holds for ANY bounds — sample
        quality affects balance, never ordering."""
        import jax
        import jax.numpy as jnp

        from ..ops.expression import as_device_column
        from ..ops.kernels import segment as seg

        padded = batch.padded_rows
        rm = batch.row_mask()
        key_cols = [as_device_column(k.expr.eval_tpu(batch), padded)
                    for k in sort_keys]
        key_cols = [type(c)(c.dtype, c.data, c.validity & rm, c.lengths)
                    for c in key_cols]
        passes = seg.key_passes_device(
            key_cols,
            descending=[not k.ascending for k in sort_keys],
            nulls_first=[k.nulls_first for k in sort_keys])
        P = jnp.stack(passes)                      # [np, padded]

        S = 64                                     # samples per shard
        nr = jnp.maximum(batch.num_rows.astype(jnp.int32), 1)
        idx = (jnp.arange(S, dtype=jnp.int32) * nr) // S
        samp = P[:, idx]                           # [np, S]
        samp_valid = jnp.full((S,), True) & (batch.num_rows > 0)

        g = jax.lax.all_gather(samp, self.axis, axis=1, tiled=True)
        gv = jax.lax.all_gather(samp_valid, self.axis, tiled=True)
        n_samp = g.shape[1]

        # sort samples (invalid last) exactly like the lexsort
        sample_passes = [jnp.where(gv, jnp.uint32(0), jnp.uint32(1))] + \
            [g[i] for i in range(g.shape[0])]
        order = seg.sort_permutation(sample_passes, n_samp)

        V = gv.sum()
        bpos = (V * jnp.arange(1, self.n)) // jnp.maximum(self.n, 1)
        bidx = order[jnp.clip(bpos, 0, n_samp - 1)]
        bounds = g[:, bidx]                        # [np, n-1]

        eq = jnp.ones((padded, self.n - 1), dtype=jnp.bool_)
        gt = jnp.zeros((padded, self.n - 1), dtype=jnp.bool_)
        for j in range(P.shape[0]):
            pj = P[j][:, None]
            bj = bounds[j][None, :]
            gt = gt | (eq & (pj > bj))
            eq = eq & (pj == bj)
        pids = gt.sum(axis=1).astype(jnp.int32)
        return jnp.where(rm, pids, self.n)

    def _capped_exchange(self, child: DeviceBatch, pids, key: str,
                         aux: Dict, caps: Dict, used_caps: Dict
                         ) -> DeviceBatch:
        """Exchange with bounded per-destination capacity + overflow
        reporting through the stage retry loop."""
        cap = caps.get(key)
        if cap is None:
            cap = bucket_rows(max(2 * child.padded_rows // self.n, 1),
                              self.min_bucket)
        used_caps[key] = cap
        aux[key] = _max_dest_count(pids, self.n)
        return self.transport.exchange(child, pids, self.n, capacity=cap)

    @staticmethod
    def _is_single(part) -> bool:
        from ..shuffle.partitioning import SinglePartitioning

        return isinstance(part, SinglePartitioning)

    @staticmethod
    def _range_keys(part):
        """The bound SortKeys of a RangePartitioning, else None."""
        from ..shuffle.partitioning import RangePartitioning

        if not isinstance(part, RangePartitioning):
            return None
        return part._bound_keys or part.sort_keys

    def _range_matches_sort(self, part, sort_keys) -> bool:
        """True when the source range exchange partitions by exactly the
        sort's keys — its shards are already in global key order, so a
        per-shard sort + in-order concat is a total order."""
        ks = self._range_keys(part)
        if ks is None:
            return False
        try:
            return [(k.expr.sql(), k.ascending, k.nulls_first)
                    for k in ks] == \
                [(k.expr.sql(), k.ascending, k.nulls_first)
                 for k in sort_keys]
        except Exception:  # noqa: BLE001
            return False

    def _sort_presorted(self, kid, op) -> bool:
        src = self._source_partitioning(kid)
        return self._is_single(src) or \
            self._range_matches_sort(src, op.keys)

    def _join_colocation(self, op, lkid, rkid) -> str:
        """Shared verdict for a shuffled join's child distribution —
        the ONE predicate both _lower and _collect_aux_keys consult, so
        the aux-key mirror can never drift from the lowering (a missed
        aux key silently drops overflowing rows).
        Returns 'ok' | 'repair' (hash re-exchange both sides) |
        'unsupported'."""
        lpart = self._source_partitioning(lkid)
        rpart = self._source_partitioning(rkid)
        keys_ok = (self._hash_keys_match(lpart, op.plan_left_keys)
                   and self._hash_keys_match(rpart, op.plan_right_keys))
        single_ok = self._is_single(lpart) and self._is_single(rpart)
        if keys_ok or single_ok:
            return "ok"
        if isinstance(lpart, _ResumedPartitioning) or \
                isinstance(rpart, _ResumedPartitioning):
            # checkpoint restored onto a different-size mesh: the old
            # placement is meaningless, re-exchange both sides
            return "repair"
        if self._range_keys(lpart) is not None or \
                self._range_keys(rpart) is not None:
            # range exchanges place rows by their OWN sampled bounds,
            # so two range-partitioned children are not colocated with
            # each other
            return "repair"
        return "unsupported"

    @staticmethod
    def _hash_keys_match(part, exprs) -> bool:
        from ..shuffle.partitioning import HashPartitioning

        if not isinstance(part, HashPartitioning):
            return False
        try:
            return [k.sql() for k in part.keys] == \
                [e.sql() for e in exprs]
        except Exception:  # noqa: BLE001
            return False

    def _concat_compact(self, batches: List[DeviceBatch],
                        schema) -> DeviceBatch:
        """Concatenate per-shard batches row-wise and recompact so the
        front-packed-rows invariant holds (expand/union lowering)."""
        import jax.numpy as jnp

        present = jnp.concatenate([b.row_mask() for b in batches])
        cols = []
        for i in range(len(batches[0].columns)):
            dtype = batches[0].columns[i].dtype
            datas = [b.columns[i].data for b in batches]
            if datas[0].ndim == 2:  # string byte matrices: pad widths
                w = max(d.shape[1] for d in datas)
                datas = [jnp.pad(d, ((0, 0), (0, w - d.shape[1])))
                         if d.shape[1] < w else d for d in datas]
            data = jnp.concatenate(datas)
            validity = jnp.concatenate(
                [b.columns[i].validity for b in batches])
            lengths = (jnp.concatenate(
                [b.columns[i].lengths for b in batches])
                if batches[0].columns[i].lengths is not None else None)
            cols.append(DeviceColumn(dtype, data, validity, lengths))
        return compact(DeviceBatch(schema, cols, present.shape[0]), present)

    def _lower(self, node, env: Dict, aux: Dict, caps: Dict,
               used_caps: Dict) -> DeviceBatch:
        """Trace-time recursive lowering of a detached stage tree
        (``_detach``): returns the (traced) output batch of ``node``
        given the program's inputs in ``env``, by slot.  Capacities are
        named by their operator's place in the tree."""
        from ..exec.aggregate import TpuHashAggregateExec
        from ..exec.coalesce import TpuCoalesceBatchesExec
        from ..exec.fused import TpuFusedSegmentExec

        if isinstance(node, _InputRef):
            return env[node.slot]
        if isinstance(node, tuple):
            op, *kids = node
            # children first and outside the operator's scope: an op's
            # outermost ``Tpu...`` scope names the operator it is part of
            inputs = [self._lower(k, env, aux, caps, used_caps)
                      for k in kids]
            if isinstance(op, (TpuHashAggregateExec, TpuFusedSegmentExec,
                               TpuCoalesceBatchesExec)):
                # these name their own bodies (and their members'); a
                # coalesce has none
                return self._lower_op(op, kids, inputs, aux, caps,
                                      used_caps)
            with device_phase(op.span_name):
                return self._lower_op(op, kids, inputs, aux, caps,
                                      used_caps)
        raise DistributedUnsupported(f"cannot lower {node!r}")

    def _lower_op(self, op, kids, inputs, aux: Dict, caps: Dict,
                  used_caps: Dict) -> DeviceBatch:
        """One operator of a stage tree over its lowered ``inputs``
        (``kids``: the subtrees they came from, for what they say of
        partitioning)."""
        import jax.numpy as jnp

        from ..exec import basic as B
        from ..exec.aggregate import TpuHashAggregateExec
        from ..exec.coalesce import TpuCoalesceBatchesExec
        from ..exec.exchange import TpuShuffleExchangeExec
        from ..exec.fused import TpuFusedSegmentExec
        from ..exec.generate import TpuGenerateExec
        from ..exec.joins import (TpuBroadcastHashJoinExec,
                                  TpuHashJoinExec)
        from ..exec.sort import TpuSortExec
        from ..exec.window import TpuWindowExec

        if isinstance(op, TpuShuffleExchangeExec):
            from ..shuffle.partitioning import SinglePartitioning

            body = inputs[0]
            pids = self._exchange_pids(op, body)
            if isinstance(op.partitioning, SinglePartitioning):
                # gather-to-one genuinely needs P x capacity
                return self.transport.exchange(body, pids, self.n)
            # cap the per-destination tile so exchange output stops
            # inflating padded size P-fold (Weak #3): start at ~2x
            # the even share, detect overflow, retry bigger
            return self._capped_exchange(body, pids, f"exch{op.place}",
                                         aux, caps, used_caps)
        if isinstance(op, (TpuCoalesceBatchesExec,)):
            return inputs[0]
        if isinstance(op, TpuHashJoinExec):
            lb = inputs[0]
            # a broadcast join's build side is an input: the
            # replicated batch _prepare_broadcasts gathered
            rb = inputs[1]
            if not isinstance(op, TpuBroadcastHashJoinExec):
                # colocation is a correctness invariant, not a
                # planner courtesy: verify both sides arrive
                # hash-partitioned on the join keys (or single)
                verdict = self._join_colocation(op, kids[0], kids[1])
                if verdict == "repair":
                    # hash re-exchange both sides on the join keys
                    # (capped, so padded size doesn't inflate
                    # P-fold)
                    lb = self._capped_exchange(
                        lb, self._hash_pids_by_exprs(
                            lb, op.plan_left_keys,
                            op.children[0].schema),
                        f"jexl{op.place}", aux, caps, used_caps)
                    rb = self._capped_exchange(
                        rb, self._hash_pids_by_exprs(
                            rb, op.plan_right_keys,
                            op.children[1].schema),
                        f"jexr{op.place}", aux, caps, used_caps)
                elif verdict == "unsupported":
                    raise DistributedUnsupported(
                        "shuffled join children are not colocated "
                        "on the join keys — plan shape would "
                        "produce wrong rows")
            key = f"join{op.place}"
            cap = caps.get(key)
            if cap is None:
                cap = bucket_rows(
                    lb.padded_rows + rb.padded_rows, self.min_bucket)
            used_caps[key] = cap
            out, total = op.join_static(lb, rb, cap)
            aux[key] = total
            return out
        if isinstance(op, (B.TpuExpandExec,)):
            child = inputs[0]
            # raw bodies: the enclosing shard_map trace must not
            # nest the locally-jitted (and cache-counted) kernels
            pieces = [fn(child) for fn in op._kernel_fns]
            return self._concat_compact(pieces, op.schema)
        if isinstance(op, B.TpuUnionExec):
            pieces = inputs
            return self._concat_compact(pieces, op.schema)
        if isinstance(op, B.TpuLocalLimitExec):
            child = inputs[0]
            if isinstance(op, B.TpuGlobalLimitExec) and \
                    not self._is_single(
                        self._source_partitioning(kids[0])):
                child = self._gather_single(child)
            keep = jnp.minimum(child.num_rows,
                               jnp.asarray(op.n, dtype=jnp.int32))
            mask = jnp.arange(child.padded_rows,
                              dtype=jnp.int32) < keep
            cols = [DeviceColumn(c.dtype, c.data, c.validity & mask,
                                 c.lengths) for c in child.columns]
            return DeviceBatch(child.schema, cols, keep)
        if isinstance(op, TpuSortExec):
            # distributed sort: range-exchange rows by sampled key
            # bounds so shard i's rows all order before shard i+1's,
            # then sort each shard locally — no gather-to-one-shard
            # bottleneck (reference: GpuRangePartitioning + per-task
            # sort under Spark's range exchange)
            child = inputs[0]
            if not self._sort_presorted(kids[0], op):
                pids = self._range_pids(child, op.keys)
                child = self._capped_exchange(
                    child, pids, f"rexch{op.place}", aux, caps,
                    used_caps)
            return op._compute(child)
        if isinstance(op, TpuWindowExec):
            child = inputs[0]
            specs = [w.spec for w in op.window_exprs]
            keys = specs[0].partition_by if specs else []
            same = all([k.sql() for k in s.partition_by]
                       == [k.sql() for k in keys] for s in specs)
            part = self._source_partitioning(kids[0])
            if keys and same:
                if not self._hash_keys_match(part, keys) and \
                        not self._is_single(part):
                    child = self._exchange_by_exprs(
                        child, keys, op.children[0].schema)
            elif not self._is_single(part):
                child = self._gather_single(child)
            return op._compute(child)
        if isinstance(op, TpuHashAggregateExec):
            child = inputs[0]
            if op.mode == "complete" and not self._is_single(
                    part := self._source_partitioning(kids[0])):
                # single-phase agg: groups must be colocated first (an
                # exchange the plan has no node for)
                with device_phase(TpuShuffleExchangeExec.SPAN):
                    if not op.keys:
                        child = self._gather_single(child)
                    elif op.absorbed:
                        # the keys are read off the absorbed chain's
                        # rows, so the source's partitioning says
                        # nothing of them; a dropped row goes nowhere
                        # and the raw rows that travel pass again
                        rows, keep = op.prologue(child)
                        pids = self._hash_pids_by_exprs(
                            rows, op.keys, rows.schema)
                        child = self.transport.exchange(
                            child, jnp.where(keep, pids, self.n), self.n)
                    elif not self._hash_keys_match(part, op.keys):
                        child = self._exchange_by_exprs(
                            child, op.keys, op.children[0].schema)
            # compute_batch carries an absorbed chain as its prologue
            return op.compute_batch(child)
        if isinstance(op, (B.TpuProjectExec, B.TpuFilterExec,
                           TpuGenerateExec)):
            child = inputs[0]
            return op._compute(child)
        if isinstance(op, TpuFusedSegmentExec):
            child = inputs[0]
            # same composed body the local jitted segment runs;
            # expand members fan out into multiple streams
            pieces = list(op._compute(child))
            if len(pieces) == 1:
                return pieces[0]
            return self._concat_compact(pieces, op.schema)
        raise DistributedUnsupported(f"cannot lower {op!r}")

    @staticmethod
    def _env_key(ref) -> str:
        if isinstance(ref, _LeafRef):
            return f"leaf{ref.idx}"
        if isinstance(ref, _BcastRef):
            return f"bcast{id(ref.op)}"
        return f"stage{ref.stage_id}"

    # ---------------- stage execution ---------------------------------
    def _detach(self, node, inputs: List, place):
        """The tree a stage program is lowered from, cut loose from
        its request: every operator a children-detached twin numbered
        by its ``place`` in the tree (what its capacities are named
        by), every leaf, earlier stage and broadcast build side an
        _InputRef.  The refs they stood for go to ``inputs``, in slot
        order.  A program outlives its request in the kernel cache; a
        live operator would pin its plan subtree, and through a leaf
        the uploads below it."""
        from ..exec.joins import TpuBroadcastHashJoinExec

        if isinstance(node, (_LeafRef, _StageRef)):
            inputs.append(node)
            return _InputRef(len(inputs) - 1,
                             getattr(node, "partitioning", None))
        op, *kids = node
        twin = op.kernel_twin()
        twin.place = next(place)
        # the lowering calls raw bodies only, and a kernel its operator
        # jitted for itself (key=None: the exchange's) is bound to the
        # live operator, children and all
        for name, held in list(vars(twin).items()):
            if isinstance(held, _CachedKernel):
                delattr(twin, name)
        if isinstance(op, TpuBroadcastHashJoinExec):
            # the build side is gathered once a query, as a program of
            # its own (_prepare_broadcasts), and comes in replicated
            left = self._detach(kids[0], inputs, place)
            inputs.append(_BcastRef(op))
            return (twin, left, _InputRef(len(inputs) - 1))
        return (twin, *[self._detach(k, inputs, place) for k in kids])

    def _signature(self, node) -> Tuple:
        """A detached stage tree as it says itself: each operator's kind
        and own ``signature``, each input's partitioning's."""
        if isinstance(node, _InputRef):
            part = node.partitioning
            return ("in", None if part is None
                    else _said(part, part.signature()))
        op, *kids = node
        return ((type(op).__name__, _said(op, op.signature)),
                *[self._signature(k) for k in kids])

    def _program_key(self, tree, post, what: str):
        """The kernel-cache key of a stage program — all its trace
        reads but the capacities (its static argument) and the inputs'
        shapes (the jit's own): the operators (each one's kind and
        ``TpuExec.signature``), the inputs' partitionings, the mesh,
        the transport, the row bucket, the ``post`` hook.  None where a
        signature is None: that program is compiled for this
        request."""
        try:
            sig = self._signature(tree)
        except _Unsignable as e:
            log.info("%s: no signature for %s; its program is compiled "
                     "for this request only", what, e)
            return None
        devices = self.mesh.devices
        return ("mesh", sig, tuple(int(d.id) for d in devices.flat),
                tuple(devices.shape), tuple(self.mesh.axis_names),
                type(self.transport).__module__,
                type(self.transport).__qualname__, self.min_bucket,
                getattr(post, "__name__", None), "stage")

    def _collect_aux_keys(self, node, out: List[str]):
        """Keys of capacity-checked collectives in a detached stage
        tree: joins (static output capacity) and capped exchanges
        (per-destination tile capacity)."""
        from ..exec.exchange import TpuShuffleExchangeExec
        from ..exec.joins import (TpuBroadcastHashJoinExec,
                                  TpuHashJoinExec)
        from ..exec.sort import TpuSortExec
        from ..shuffle.partitioning import SinglePartitioning

        if isinstance(node, tuple):
            op = node[0]
            if isinstance(op, TpuHashJoinExec):
                out.append(f"join{op.place}")
                if not isinstance(op, TpuBroadcastHashJoinExec) and \
                        self._join_colocation(
                            op, node[1], node[2]) == "repair":
                    out.append(f"jexl{op.place}")
                    out.append(f"jexr{op.place}")
            if isinstance(op, TpuShuffleExchangeExec) and \
                    not isinstance(op.partitioning, SinglePartitioning):
                out.append(f"exch{op.place}")
            if isinstance(op, TpuSortExec) and \
                    not self._sort_presorted(node[1], op):
                out.append(f"rexch{op.place}")
            for k in node[1:]:
                self._collect_aux_keys(k, out)

    def _collect_broadcasts(self, node, out: List):
        """Broadcast joins of this stage in post-order (inner builds
        first, so an outer build side can consume an inner's env key)."""
        from ..exec.joins import TpuBroadcastHashJoinExec

        if isinstance(node, tuple):
            for k in node[1:]:
                self._collect_broadcasts(k, out)
            if isinstance(node[0], TpuBroadcastHashJoinExec):
                out.append((node[0], node[2]))

    def _run_program(self, root, env_stacked: Dict, caps: Dict,
                     what: str, post=None) -> DeviceBatch:
        """Dispatch the stage program that lowers ``root``; retries
        with grown capacities on collective overflow.  ``post`` (traced
        hook) runs on the per-shard output before unstacking — the
        broadcast precompute passes the replicate here.

        The program comes from the kernel cache (``jit_mesh_stage``):
        a request whose stage signs like an earlier one's, over inputs
        in the same row buckets, starts at the capacities that one
        ended on, traces nothing and dispatches once.  A capacity is a
        shape, so an attempt at a new one compiles: each attempt is
        logged under ``what`` as it is dispatched and as it answers,
        with its seconds and the demands that overflowed — a run that
        is cut short still says which program it was in, on which
        attempt."""
        from ..shuffle.device_shuffle import collective_timer
        from .elastic import guarded_call

        # fault checkpoint at the stage boundary (host side, inside the
        # watchdog-timed region): delay injections become stragglers
        # the watchdog trips on, crash injections become recoverable
        # stage deaths
        maybe_inject_fault("stage.run")

        refs: List = []
        tree = self._detach(root, refs, itertools.count())
        ins = tuple(env_stacked[self._env_key(r)] for r in refs)
        aux_keys: List[str] = []
        self._collect_aux_keys(tree, aux_keys)
        aux_keys.sort()

        kern = jit_kernel(
            _StageProgram(DistributedRunner(self.mesh, self.min_bucket,
                                            self.transport),
                          tree, aux_keys, post),
            key=self._program_key(tree, post, what), kind="mesh",
            static_argnums=(0,))
        # on a hit the first caller's body: what it traced is what runs
        program = kern.fn
        shapes = _shapes(ins)
        # this program's capacities, by name: what an earlier attempt
        # of this request grew, else what the last request settled on
        mine = caps.setdefault(what, {})
        if not mine:
            mine.update(program.settled.get(shapes, ()))
        # same dispatch discipline as exchange_step: a cancelled
        # query must not join a mesh-wide collective its peers
        # will wait on, and the dispatch wall of an
        # exchange-bearing program accrues to shuffle.collectiveTime.
        # guarded_call layers the elastic deadline/heartbeat watch on
        # top (fault.peer.collectiveTimeoutMs) so a dead peer turns
        # into TpuPeerLost instead of an indefinite hang.
        collective = post is not None or self._has_collective(tree)

        for attempt in range(_MAX_JOIN_RETRIES):
            at = tuple(sorted(mine.items()))
            # a trace leaves its capacities in ``used``: none there
            # yet means this dispatch traces, and so compiles
            compiles = (at, shapes) not in program.used
            t0 = time.perf_counter()
            log.info("%s attempt %d: dispatching (%d inputs, up to %d "
                     "rows a shard)", what, attempt, len(ins),
                     max((b.columns[0].data.shape[1] for b in ins),
                         default=0))
            if collective:
                def dispatch(at=at):
                    with collective_timer():
                        return kern(at, *ins)
                out, aux_vals = guarded_call(dispatch)
            else:
                out, aux_vals = guarded_call(
                    lambda at=at: kern(at, *ins), site="stage.dispatch")
            if compiles:
                self.stage_compiles += 1
            else:
                self.stage_hits += 1
            used = program.used[at, shapes]
            overflow = {}
            for k, v in zip(aux_keys, aux_vals):
                total = int(np.asarray(v))
                if total > used.get(k, 0):
                    mine[k] = bucket_rows(total, self.min_bucket)
                    overflow[k.rstrip("0123456789")] = (
                        used.get(k, 0), total)
            log.info("%s attempt %d: answered in %.1f s%s%s", what,
                     attempt, time.perf_counter() - t0,
                     " (trace, compile and run)" if compiles else "",
                     f"; (capacity, demand) overflowed: {overflow}"
                     if overflow else "")
            if not overflow:
                program.settled[shapes] = dict(mine)
                return out
            self.stage_retries += 1
        raise RuntimeError(
            f"{what}: collective capacity retries exhausted")

    @staticmethod
    def _has_collective(node) -> bool:
        """True when lowering ``node`` dispatches a mesh collective (a
        shuffle exchange inside the program).  Broadcast replicates run
        as programs of their own and are timed there via ``post``."""
        from ..exec.exchange import TpuShuffleExchangeExec

        stack = [node]
        while stack:
            n = stack.pop()
            if isinstance(n, tuple):
                op, *kids = n
                if isinstance(op, TpuShuffleExchangeExec):
                    return True
                stack.extend(kids)
        return False

    def _prepare_broadcasts(self, stage: _Stage, env_stacked: Dict,
                            caps: Dict) -> None:
        """Gather each broadcast build side ONCE per query, as its own
        compiled program, so stage capacity retries and repeated stage
        executions reuse the replicated batch instead of re-running the
        all_gather (reference: one broadcast relation per exchange,
        GpuBroadcastExchangeExec.scala:215-247)."""
        ops: List = []
        self._collect_broadcasts(stage.root, ops)
        for i, (op, build_kid) in enumerate(ops):
            key = f"bcast{id(op)}"
            if key in env_stacked:
                continue
            # replicated rows are front-packed like any stage output
            # (every shard holds all of them, num_rows alike), so the
            # same trim applies: the consuming stage is traced at the
            # build side's row count, not at n_shards x its bucket
            with trace_range("MeshStage"):
                out = self._run_program(
                    build_kid, env_stacked, caps,
                    f"stage[{stage.sid}].broadcast[{i}]",
                    post=self.transport.replicate)
            with trace_range("MeshTrim"):
                env_stacked[key] = self._retile(out)

    def _run_stage(self, stage: _Stage, env_stacked: Dict,
                   caps: Dict) -> DeviceBatch:
        """jit + shard_map one stage; returns the stacked output batch.
        Retries with doubled join capacity on overflow."""
        self._prepare_broadcasts(stage, env_stacked, caps)
        # trace, compile, dispatch and the overflow verdict's readback
        # (the wait for the stage), every attempt
        with trace_range("MeshStage"):
            out = self._run_program(
                stage.root, env_stacked, caps, f"stage[{stage.sid}]")
        with trace_range("MeshTrim"):
            return self._retile(out)

    def _retile(self, stacked: DeviceBatch) -> DeviceBatch:
        """Host-side bucket trim between stages: shapes grow through
        exchanges (P tiles) and join capacities; rows are front-packed,
        so trimming to the max shard count's bucket is lossless."""
        nrows = np.asarray(stacked.num_rows)
        # stage-boundary statistics ride this EXISTING readback — the
        # per-shard row counts are the distributed stage's partition
        # histogram (adaptive/stats.py); no extra device sync
        self._last_stage_rows = nrows
        need = bucket_rows(int(nrows.max()) if nrows.size else 1,
                           self.min_bucket)
        # a stacked batch is [n_shards, padded, ...]: the row bucket is
        # axis 1, not ``padded_rows`` (axis 0, the shard count, which no
        # bucket is below: compared with that, nothing is ever trimmed)
        if not stacked.columns or \
                need >= stacked.columns[0].data.shape[1]:
            return stacked
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        sharding = NamedSharding(self.mesh, P(self.axis))
        cols = []
        for c in stacked.columns:
            data = jax.device_put(c.data[:, :need], sharding)
            validity = jax.device_put(c.validity[:, :need], sharding)
            lengths = (jax.device_put(c.lengths[:, :need], sharding)
                       if c.lengths is not None else None)
            cols.append(DeviceColumn(c.dtype, data, validity, lengths))
        return DeviceBatch(stacked.schema, cols, stacked.num_rows)

    # ---------------- driver ------------------------------------------
    def run(self, root, ctx) -> HostBatch:
        """Execute ``root`` distributed; collect to one HostBatch (rows
        of shard 0..n-1 concatenated in order)."""
        from ..data.column import register_pytrees
        from ..scheduler.cancel import check_cancel

        register_pytrees()
        stages, leaves = self.plan_stages(root)
        env_stacked: Dict[str, DeviceBatch] = {}
        # leaves and stages each run under the bounded fault-recovery
        # protocol: watchdog deadline, typed-fault retry from lineage,
        # exhaustion escalating to the degradation ladder.  A stage
        # boundary is also a cancellation/deadline checkpoint — a
        # cancelled or past-deadline query stops between stages instead
        # of launching the next one.
        for leaf in leaves:
            check_cancel(f"runner.leaf[{leaf.idx}]")
            # one device runs the subtree, the host takes its rows and
            # stacks them, the mesh gets the stack (MeshPlace inside)
            with tspans.span(f"leaf[{leaf.idx}]", kind="stage",
                             node=leaf.node.name), trace_range("MeshLeaf"):
                env_stacked[self._env_key(leaf)] = self._recover(
                    lambda leaf=leaf: self._run_leaf(leaf.node, ctx),
                    ctx, f"leaf[{leaf.idx}]")
        caps: Dict = {}
        out = None
        for stage in stages:
            check_cancel(f"runner.stage[{stage.sid}]")
            resumed = self._try_resume_stage(ctx, stage, stages)
            if resumed is not None:
                out = resumed
                env_stacked[f"stage{stage.sid}"] = out
                continue
            with tspans.span(f"stage[{stage.sid}]", kind="stage"):
                out = self._recover(
                    lambda stage=stage: self._run_stage(
                        stage, env_stacked, caps),
                    ctx, f"stage[{stage.sid}]")
            env_stacked[f"stage{stage.sid}"] = out
            self._record_stage_stats(ctx, stage.sid)
            self._maybe_checkpoint_stage(ctx, stage, out)
        with trace_range("MeshCollect"):
            return self._collect_output(out, stages)

    # ---------------- elastic checkpoint / resume ---------------------
    def _try_resume_stage(self, ctx, stage, stages):
        """Restore a checkpointed stage output instead of re-executing
        it (the elastic re-execution path).  The checkpoint may come
        from a previous attempt of the SAME query on a LARGER mesh — a
        peer died and the surviving devices re-formed — in which case
        the checkpointed partitions are folded onto this mesh
        (``p -> p % n``) and every later consumer of the stage sees a
        ``_ResumedPartitioning`` sentinel, forcing a repair
        re-exchange: placement is re-derived, never assumed."""
        rec = getattr(ctx, "recovery", None)
        root = stage.root
        if rec is None or not isinstance(root, tuple):
            return None
        rfp = getattr(root[0], "_recovery_fp", None)
        if rfp is None:
            return None
        from ..native import serializer
        from ..plan.physical import _empty_batch
        from ..recovery.manager import schema_signature

        exch = root[0]
        schema = exch.schema
        res = rec.try_resume(rfp, n_out=None,
                             schema_sig=schema_signature(schema))
        if res is None:
            return None
        m, frames = res
        n_ck = int(m.get("n_out", len(frames)))
        try:
            per_shard: List[List[HostBatch]] = \
                [[] for _ in range(self.n)]
            for p, plist in enumerate(frames):
                for frame in plist:
                    hb = serializer.deserialize(frame, schema)
                    if hb.num_rows:
                        per_shard[p % self.n].append(hb)
            shards = [HostBatch.concat(bs) if bs
                      else _empty_batch(schema)
                      for bs in per_shard]
            placed = self._place(self._stack_host(shards))
        except Exception as e:  # noqa: BLE001 — re-execute, never fail
            rec.disable(f"stage resume failed "
                        f"({type(e).__name__}: {e})")
            return None
        if n_ck != self.n:
            mark = _ResumedPartitioning()
            for st in stages:
                self._mark_resumed_refs(st.root, stage.sid, mark)
        return placed

    def _mark_resumed_refs(self, node, sid: int, mark) -> None:
        """Stamp the resumed-partitioning sentinel on every _StageRef
        of stage ``sid`` (the restored output's placement contract is
        void on a different-size mesh)."""
        if isinstance(node, _StageRef):
            if node.stage_id == sid:
                node.partitioning = mark
            return
        if isinstance(node, _BcastRef):
            self._mark_resumed_refs(node.op, sid, mark)
            return
        if isinstance(node, tuple):
            for kid in node[1:]:
                self._mark_resumed_refs(kid, sid, mark)

    def _maybe_checkpoint_stage(self, ctx, stage, out) -> None:
        """Persist a completed stage's post-exchange output as a
        durable checkpoint — the distributed analogue of the local
        exchange's ``_maybe_checkpoint`` (exec/exchange.py), keyed by
        the SAME exchange fingerprint so a surviving mesh can resume
        what a lost one produced.  Serialization runs under the
        injection shield (a fault drill must not fire inside framework
        persistence) and any failure disables checkpointing for the
        rest of the query instead of failing it."""
        rec = getattr(ctx, "recovery", None)
        root = stage.root
        if rec is None or not isinstance(root, tuple):
            return
        rfp = getattr(root[0], "_recovery_fp", None)
        if rfp is None or not rec.should_checkpoint(rfp):
            return
        from ..fault import injector as F
        from ..native import serializer
        from ..recovery.manager import schema_signature

        exch = root[0]
        frames: List[List] = []
        try:
            with F._shield():
                for hb in self._stage_host_parts(out):
                    plist = []
                    if hb.num_rows:
                        plist.append((serializer.serialize(hb),
                                      hb.num_rows))
                    frames.append(plist)
        except Exception as e:  # noqa: BLE001
            rec.disable(f"stage checkpoint read-back failed "
                        f"({type(e).__name__}: {e})")
            return
        written = rec.checkpoint_exchange(
            rfp, schema_sig=schema_signature(exch.schema),
            n_out=len(frames),
            part_rows=[sum(r for _f, r in plist) for plist in frames],
            total_bytes=sum(int(f.nbytes) for plist in frames
                            for f, _r in plist),
            partitioning=type(exch.partitioning).__name__,
            frames=frames)
        if written:
            from ..shuffle.device_shuffle import GLOBAL as _DS

            _DS.add("checkpointBytes", written)

    def _stage_host_parts(self, out: DeviceBatch) -> List[HostBatch]:
        """One trimmed HostBatch per mesh partition of a stacked stage
        output (overridden by the multi-process runner, which must
        gather non-addressable shards first)."""
        return [device_to_host(p, trim=True)
                for p in X.unstack_partitions(out)]

    def _record_stage_stats(self, ctx, sid: int) -> None:
        """Record the stage's per-shard row histogram from _retile's
        already-host-resident count vector.  The SPMD program is
        compiled as a whole, so no plan rewrite applies here — but the
        histogram feeds profiles/metrics, a re-executed stage
        re-records fresh numbers, and the scheduler reservation can
        re-base off observed output."""
        nrows = getattr(self, "_last_stage_rows", None)
        self._last_stage_rows = None
        stats = getattr(ctx, "stage_stats", None)
        if nrows is None or stats is None \
                or not getattr(nrows, "size", 0):
            return
        eid = stats.allocate_id()
        obs = stats.record_exchange(
            eid, items=[(None, nrows, None)], n_out=int(nrows.size),
            device_path=True, total_bytes=0,
            partitioning="MeshStage", name=f"stage[{sid}]")
        fields = {"exchange": eid, "stage": sid,
                  "partitions": obs.n_out, "rows": obs.total_rows,
                  "device_path": True}
        h = obs.histogram()
        if h is not None:
            fields.update(rows_min=h["min"], rows_p50=h["p50"],
                          rows_max=h["max"], skew_pct=h["skewPct"])
        emit_event("aqe_stage_stats", **fields)
        from ..adaptive.executor import _rebase_reservation

        _rebase_reservation(ctx)

    def _collect_output(self, out: DeviceBatch, stages) -> HostBatch:
        """Download the final stacked stage output to one HostBatch
        (overridden by the multi-process runner, which must first
        gather non-addressable shards)."""
        parts = X.unstack_partitions(out)
        host = [device_to_host(p) for p in parts]
        host = [h for h in host if h.num_rows]
        if not host:
            from ..plan.physical import _empty_batch

            return _empty_batch(self._schema_of(stages[-1].root))
        return HostBatch.concat(host)

    def _schema_of(self, node):
        if isinstance(node, tuple):
            return node[0].schema
        if isinstance(node, _LeafRef):
            return node.node.schema
        raise DistributedUnsupported("schema of stage ref")


def run_distributed(session, df, mesh=None, n_devices: int = 8,
                    recovery=None) -> HostBatch:
    """Convenience: plan ``df`` through the session's rewrite pipeline
    and execute it SPMD over ``mesh`` (or a fresh n-device mesh).

    ``recovery``: an already-attached RecoveryManager (the elastic
    shrunken-mesh rung passes the failed attempt's manager here so
    completed stages resume from its checkpoints instead of
    re-executing).  When None, no stage checkpointing happens — the
    behaviour existing callers rely on."""
    # the request inside the program, planning to rows on the host
    # (as Session._execute_native opens it)
    with trace_range("Query", query_id=next(session._query_ids)):
        return _run_request(session, df, mesh, n_devices, recovery)


def _run_request(session, df, mesh, n_devices, recovery) -> HostBatch:
    from ..config import FAULT_PEER_COLLECTIVE_TIMEOUT_MS
    from ..plan.physical import ExecContext
    from .mesh import make_mesh

    from . import elastic
    from .collective import make_transport
    from .mesh import DATA_AXIS as _AX

    mesh = mesh or make_mesh(n_devices)
    phys = session.physical_plan(df.plan)
    ctx = ExecContext(session.conf, session)
    if recovery is not None:
        recovery.stamp_plan(phys)
        ctx.recovery = recovery
    axis = mesh.axis_names[0] if mesh.axis_names else _AX
    prev_deadline = elastic.install_collective_deadline(
        session.conf.get(FAULT_PEER_COLLECTIVE_TIMEOUT_MS))
    # a runner a request: what outlives it, the stage programs and the
    # capacities they settled on, is in the kernel cache
    runner = DistributedRunner(
        mesh, transport=make_transport(session.conf, axis))
    try:
        return runner.run(phys, ctx)
    finally:
        elastic.install_collective_deadline(prev_deadline)
        # the fault counters must be visible even on a direct
        # run_distributed call (the ladder driver re-merges on top)
        session.last_metrics = dict(
            getattr(session, "last_metrics", None) or {})
        session.last_metrics.update(_fault_stats.snapshot())
        session.last_metrics.update(runner.metrics())
        from ..plan.fusion import count_absorbed
        from ..shuffle.device_shuffle import GLOBAL as _shuffle_stats

        session.last_metrics["fusion.filtersAbsorbed"] = \
            count_absorbed(phys)

        session.last_metrics.update(_shuffle_stats.metrics_since(
            getattr(ctx, "shuffle_stats_mark", None)))
        if recovery is not None:
            session.last_metrics.update(recovery.metrics())
        from ..telemetry import finish_query

        # profile metrics default to THIS query's ctx snapshot — the
        # session.last_metrics merge above intentionally carries prior
        # state for the ladder driver and must not back-fill spans
        finish_query(session, ctx, phys=phys)
