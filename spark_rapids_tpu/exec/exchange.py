"""Device shuffle exchange.

Reference analogue: GpuShuffleExchangeExec.scala:60-244 — partition ids
are computed on device (cudf hash-partition kernel) and batches are
sliced on device (`Table.contiguousSplit`, Plugin.scala:54-83) so data
never visits the host.  Here the same: partition ids come from the
device murmur3 (bit-identical row placement to the host oracle), and
each output partition's batch is a masked compaction of the input —
the static-shape contiguousSplit.  Local (in-process) exchange keeps
batches in HBM end to end, the analogue of the RapidsShuffleManager's
device-store caching path (RapidsCachingWriter,
RapidsShuffleInternalManager.scala:90-138); the mesh-collective
exchange for true multi-chip runs lives in parallel/exchange.py.

Partitionings: hash / single / round-robin / range all run on device.
Range mirrors the reference's split of work (GpuRangePartitioner.scala:
33-104 — driver-side sampled bounds, device-side bound compare): key
samples are taken on device during the shuffle write, the quantile
bounds are picked on host from the tiny sample, and row placement is a
compiled lexicographic bound-compare over order-preserving uint32 key
words.  String keys are coarsened to a fixed byte prefix for
placement only — prefix compare is a monotone coarsening of the true
order, so per-partition sort + in-order concat still yields a total
order (balance, never correctness, depends on the prefix).
"""
from __future__ import annotations

import functools
from typing import List

import numpy as np

from ..config import BUCKET_MIN_ROWS
from ..data.column import DeviceBatch, DeviceColumn, bucket_rows
from ..fault import injector as F
from ..fault.errors import TpuPayloadCorruption
from ..memory import retry as R
from ..ops.expression import as_device_column
from ..ops.kernels import segment as seg
from ..ops.kernels.gather import compact
from ..shuffle.partitioning import (HashPartitioning, RangePartitioning,
                                    RoundRobinPartitioning,
                                    SinglePartitioning)
from ..utils import hashing
from ..utils import metrics as M
from ..utils.tracing import device_phase, trace_range
from .base import DevicePartitionedData, TpuExec

#: string keys are truncated to this byte prefix for range PLACEMENT
#: (not for the sort itself) — 9 uint32 words per string key
RANGE_PREFIX_BYTES = 32

#: per-batch device key samples taken for the range bounds
RANGE_SAMPLES_PER_BATCH = 128


def range_key_passes(batch: DeviceBatch, bound_keys):
    """Stacked order-preserving uint32 words [n_passes, padded] of the
    range sort keys, with string keys truncated to RANGE_PREFIX_BYTES
    (monotone coarsening — see module docstring).

    No key AFTER the first string key contributes passes: a string may
    be truncated by the prefix, and rows whose strings agree on the
    prefix but differ beyond it would then be placed by the later key —
    not a monotone coarsening of the true lexicographic order (a bound
    landing inside the prefix-equal group would route rows against the
    global order).  The cut is unconditional (not "only when this
    batch's strings are wide") so the pass LAYOUT is static: bounds,
    samples and the pid compare are shared across batches, and a
    per-batch pass count would desync them.  Placement by the prefix
    alone stays monotone — only balance suffers, and only for data
    whose 32-byte prefixes collide."""
    import jax.numpy as jnp

    cols = []
    used_keys = []
    for k in bound_keys:
        c = as_device_column(k.expr.eval_tpu(batch), batch.padded_rows)
        if c.dtype.is_string:
            bm, w = c.data, c.data.shape[1]
            if w < RANGE_PREFIX_BYTES:
                bm = jnp.pad(bm, ((0, 0), (0, RANGE_PREFIX_BYTES - w)))
            else:
                bm = bm[:, :RANGE_PREFIX_BYTES]
            pos = jnp.arange(RANGE_PREFIX_BYTES, dtype=jnp.int32)[None, :]
            bm = jnp.where(pos < c.lengths[:, None], bm, 0)
            c = DeviceColumn(c.dtype, bm, c.validity,
                             jnp.minimum(c.lengths, RANGE_PREFIX_BYTES))
        cols.append(c)
        used_keys.append(k)
        if c.dtype.is_string:
            break
    passes = seg.key_passes_device(
        cols,
        descending=[not k.ascending for k in used_keys],
        nulls_first=[k.nulls_first for k in used_keys])
    return jnp.stack(passes)


def range_pids_from_bounds(passes, bounds):
    """pid = number of bounds the row exceeds lexicographically
    (passes[j] dominates passes[j+1]); monotone in the sort order for
    ANY bounds, so sample quality affects balance, never ordering."""
    import jax.numpy as jnp

    padded = passes.shape[1]
    nb = bounds.shape[1]
    eq = jnp.ones((padded, nb), dtype=jnp.bool_)
    gt = jnp.zeros((padded, nb), dtype=jnp.bool_)
    for j in range(passes.shape[0]):
        pj = passes[j][:, None]
        bj = bounds[j][None, :]
        gt = gt | (eq & (pj > bj))
        eq = eq & (pj == bj)
    return gt.sum(axis=1).astype(jnp.int32)


def pick_bounds_host(samples: np.ndarray, n_out: int) -> np.ndarray:
    """Quantile bounds from the gathered uint32 sample words
    [n_passes, n_samples] (host side, like the reference's driver-side
    bounds — GpuRangePartitioner.scala:68-104)."""
    order = np.lexsort(samples[::-1])  # passes[0] dominates
    v = samples.shape[1]
    cuts = [min(max((v * (i + 1)) // n_out, 0), v - 1)
            for i in range(n_out - 1)]
    return samples[:, order[cuts]]


def _free_shuffle_buffers(fw, store, spill_listener=None,
                          catalog=None, shuffle_id=None):
    if catalog is not None and shuffle_id is not None:
        catalog.unregister_shuffle(shuffle_id)  # idempotent
    else:
        # entries are (buf_id, rr, num_rows) on the host path and
        # (buf_id, counts, starts) on the device path
        for entry in (store[0] if store else ()):
            fw.remove_batch(entry[0])
    if spill_listener is not None:
        try:
            fw.spill_listeners.remove(spill_listener)
        except ValueError:
            pass


@device_phase("shuffle.hashPids")
def _hash_pids(bound, n_out, batch: DeviceBatch):
    import jax.numpy as jnp

    cols = [as_device_column(k.eval_tpu(batch), batch.padded_rows)
            for k in bound]
    h = hashing.hash_device_batch(cols)
    return hashing.pmod(h, n_out).astype(jnp.int32)


def _slice(batch: DeviceBatch, pids, p) -> DeviceBatch:
    return compact(batch, pids == p)


def _range_pids(bound_keys, batch: DeviceBatch, bounds):
    return range_pids_from_bounds(range_key_passes(batch, bound_keys),
                                  bounds)


def _sample(passes, nr):
    import jax.numpy as jnp

    idx = (jnp.arange(RANGE_SAMPLES_PER_BATCH, dtype=jnp.int32)
           * jnp.maximum(nr, 1)) // RANGE_SAMPLES_PER_BATCH
    return passes[:, idx]


class TpuShuffleExchangeExec(TpuExec):
    SPAN = "TpuShuffleWrite"

    def __init__(self, child, plan):
        super().__init__([child])
        self.plan = plan  # physical.ShuffleExchangeExec
        self.partitioning = plan.partitioning
        self.n_out = plan.n_out
        from .kernel_cache import (expr_signature, jit_kernel,
                                   schema_signature, sort_key_signature)

        said = self.partitioning.signature()
        #: a mesh stage reads the partitioning; an unknown one is private
        self.signature = None if said is None else (
            schema_signature(child.schema), said)

        # the exchange's own programs are keyed by what their bodies
        # read (the child's layout, the bound keys, the fan-out) and
        # bound to none of this exec: a plan built anew for the same
        # query finds them compiled (a kernel made privately here cost
        # every new plan one compile an exchange program).  Each key
        # ends in the program's name (kernel_cache.program_name)
        layout = schema_signature(child.schema)
        self._slice_kernel = jit_kernel(_slice, key=("shuffle", "_slice"))
        if isinstance(self.partitioning, HashPartitioning):
            self._hash_kernel = jit_kernel(
                functools.partial(_hash_pids, self.partitioning._bound,
                                  self.n_out),
                key=("shuffle", layout,
                     expr_signature(self.partitioning._bound), self.n_out,
                     "_hash_pids"))
        # device-resident path: trim, packed partition-build and slice
        # kernels, shared across execs through the kernel cache
        # (module-level bodies keyed by schema layout + fan-out).
        # Range partitioning never takes the packed path (its
        # placement needs sampled bounds that only exist after the
        # full write drain).
        if not isinstance(self.partitioning, RangePartitioning):
            from ..shuffle import device_shuffle as DS

            self._trim_kernel = DS.trim_kernel(self.schema)
            self._build_kernel = DS.packed_build_kernel(
                self.schema, self.n_out)
            self._packed_slice_kernel = DS.packed_slice_kernel(
                self.schema)
        if isinstance(self.partitioning, RangePartitioning):
            bound_keys = self.partitioning._bound_keys
            key_sig = sort_key_signature(bound_keys)
            self._passes_kernel = jit_kernel(
                functools.partial(range_key_passes, bound_keys=bound_keys),
                key=("shuffle", layout, key_sig, "rangePasses"))
            self._range_pid_kernel = jit_kernel(
                functools.partial(_range_pids, bound_keys),
                key=("shuffle", layout, key_sig, "rangePids"))
            # a module-level body with no closure: shared by its key
            self._bounds_pid_kernel = jit_kernel(
                range_pids_from_bounds,
                key=("shuffle.rangePidsFromBounds",))
            self._sample_kernel = jit_kernel(
                _sample, key=("shuffle", "_sample"))

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def children_coalesce_goal(self):
        # coalesce sub-target input batches to shuffle.targetBatchRows
        # before the partition-build kernel runs: a stream of tiny scan
        # batches costs ONE build dispatch instead of N (rows=None
        # resolves the conf at execute time)
        from .base import TargetRows

        return [TargetRows(None)]

    # ------------------------------------------------------------------
    def _pids(self, batch: DeviceBatch, rr_start: int = 0, bounds=None):
        import jax.numpy as jnp

        if isinstance(self.partitioning, SinglePartitioning):
            return jnp.zeros(batch.padded_rows, dtype=jnp.int32)
        if isinstance(self.partitioning, RoundRobinPartitioning):
            return ((jnp.arange(batch.padded_rows, dtype=jnp.int32)
                     + rr_start) % self.n_out)
        if isinstance(self.partitioning, RangePartitioning):
            if bounds is None:  # no sample (empty input): one partition
                return jnp.zeros(batch.padded_rows, dtype=jnp.int32)
            return self._range_pid_kernel(batch, bounds)
        return self._hash_kernel(batch)

    # ------------------------------------------------------------------
    def execute_columnar(self, ctx):
        import weakref

        from ..memory.spill import SpillFramework

        import threading

        from ..config import SHUFFLE_MODE
        from ..shuffle import device_shuffle as DS
        from ..telemetry.events import emit_event

        # stage-level recovery: a valid checkpoint for this exchange
        # (fingerprint-stamped by RecoveryManager.stamp_plan, validated
        # + CRC-verified eagerly in try_resume) replaces the ENTIRE
        # subtree below — the child is never executed
        rec = getattr(ctx, "recovery", None)
        rfp = getattr(self, "_recovery_fp", None)
        if rec is not None and rfp is not None:
            from ..recovery.manager import schema_signature

            resumed = rec.try_resume(
                rfp, n_out=self.n_out,
                schema_sig=schema_signature(self.schema))
            if resumed is not None:
                return self._resumed_result(ctx, *resumed)

        child = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        is_range = isinstance(self.partitioning, RangePartitioning)
        # exchange data path: device (packed blocks stay in HBM), host
        # (every block staged + CRC-stamped immediately — the
        # pre-device behavior and the ladder's host-shuffle rung), auto
        # (device while the arena has headroom)
        dm = ctx.session.device_manager \
            if getattr(ctx, "session", None) is not None else None
        mode = DS.resolve_mode(
            ctx.conf.get(SHUFFLE_MODE),
            force_host=getattr(ctx, "force_host_shuffle", False),
            headroom=dm.headroom() if dm is not None else 1)
        # range never packs (bounds exist only after the full drain) —
        # it runs the legacy device-resident write, staging under host
        device_path = mode == "device" and not is_range
        store: List[list] = []
        # AQE stage statistics: the write drain records its (already
        # host-resident) per-block count vectors + byte sizes here —
        # id allocated per EXECUTION so a re-drained retry overwrites
        # with fresh numbers instead of appending stale ones
        stage_stats = getattr(ctx, "stage_stats", None)
        exchange_id = (stage_stats.allocate_id()
                       if stage_stats is not None else 0)
        stat_state = {"bytes": 0}
        # shuffle-scoped buffer group (reference: ShuffleBufferCatalog
        # shuffleId->mapId->buffers index + per-shuffle cleanup)
        catalog = shuffle_id = None
        if ctx is not None and getattr(ctx, "session", None) is not None:
            catalog = getattr(ctx.session, "shuffle_catalog", None)
        if catalog is not None:
            shuffle_id = catalog.register_shuffle()
            if hasattr(ctx, "shuffle_ids"):
                ctx.shuffle_ids.append(shuffle_id)
        # Writer election instead of a lock held across the child drain:
        # the old form (write_lock around the drain) deadlocked under
        # the device semaphore — the writer blocked inside the child on
        # a permit while permit-holding readers blocked on the lock
        # (lock-order inversion, r3 Weak #2).  Now the loser threads
        # drop their ENTIRE device hold before waiting on the event, so
        # the writer can always admit the child's device work.
        elect_lock = threading.Lock()
        done = threading.Event()
        state = {"writer": False, "error": None, "bounds": None}
        sem = self._sem(ctx)
        # buf_id -> (id(device_batch), pids): partition ids are computed
        # once per resident batch and reused by all n_out readers; a
        # spill+promote cycle yields a new batch object and recomputes
        pid_cache: dict = {}
        # buf_id -> block bytes for DEVICE-path blocks still resident:
        # a spill of one of these is the device-shuffle → host-staging
        # degradation, surfaced as hostBytes + a shuffle_fallback event
        device_sizes: dict = {}
        fw = SpillFramework.get()
        rctx = R.RetryContext.for_exec(ctx, "TpuShuffleExchangeExec")
        min_bucket = ctx.conf.get(BUCKET_MIN_ROWS)

        def drop(ids):
            """Forget buffers of this exchange: their catalog slots,
            their spill entries and what the exec caches per id."""
            for bid in ids:
                pid_cache.pop(bid, None)
                device_sizes.pop(bid, None)
            if catalog is not None:
                catalog.drop_buffers(shuffle_id, ids)
            else:
                for bid in ids:
                    fw.remove_batch(bid)

        def write_one(b):
            # registering a map-output batch is the write-side
            # allocation checkpoint; an OOM retries after spill+backoff
            # (the batch itself is the checkpointed input).  The fault
            # checkpoint covers delay/crash injection; corruption is
            # injected inside add_batch at the write site — the device
            # path's ".device" suffix lets a sweep target one data path
            # while a plain "exchange.write" filter matches both.
            R.maybe_inject_oom("TpuShuffleExchange.write")
            if not device_path:
                F.maybe_inject_fault("exchange.write")
                return fw.add_batch(b, site="exchange.write")
            F.maybe_inject_fault("exchange.write.device")
            # the device path only parks its input here, spillable and
            # accounted like the block it will become: nothing is
            # hashed or packed before the flush has read the row count
            # (pack_one, whose block is the device write site's payload)
            buf_id = fw.add_batch(b)
            device_sizes[buf_id] = b.device_bytes()
            return buf_id

        def pack_one(buf_id, n, rr_start):
            """Build one parked input's packed block, at the bucket of
            its ``n`` live rows when that is smaller than its padding
            (live rows are at the front, so the trim cuts padding
            only).  Returns the block's id, its count/start handles,
            its bytes and the padded rows the trim saved."""
            b = fw.acquire_batch(buf_id)
            try:
                padded = b.padded_rows
                bucket = bucket_rows(n, min_bucket)
                if bucket < padded:
                    cut = self._trim_kernel(b, bucket,
                                            metrics=self.metrics)
                    b = DeviceBatch(cut.schema, cut.columns, n)
                pids = self._pids(b, rr_start, None)
                block, counts, starts = self._build_kernel(
                    b, pids, self.n_out, metrics=self.metrics)
            finally:
                fw.release_batch(buf_id)
            return (fw.add_batch(block, site="exchange.write.device"),
                    counts, starts, block.device_bytes(),
                    padded - block.padded_rows)

        def _drain_child():
            import jax

            import jax.numpy as jnp

            # device path: (buf_id, counts np, starts np)
            # host path:   (buf_id, round-robin start offset, num_rows)
            items = []
            rr = 0
            samples = []   # host key samples for the range bounds
            pending = []   # (buf_id, id(batch), passes) for pid prefill
            # passes are unspillable HBM; cap what the prefill may pin
            # so a long shuffle write can't defeat the spill framework
            # (batches past the cap recompute pids at first read)
            pend_budget = 64 * 1024 * 1024
            # chunk entries hold NO batch reference — only the buffer
            # id plus tiny handles (the row count, the sample tile) —
            # so a spill of a chunk member actually frees its HBM
            chunk = []
            stat_state["bytes"] = 0  # fresh per attempt (re-drains)

            def flush():
                # batched readbacks of the chunk's tiny handles — a
                # per-batch int(num_rows) is a full device sync each
                nonlocal rr
                if not chunk:
                    return
                if device_path:
                    # the row counts first: they decide each block's
                    # bucket.  This is where the client waits for the
                    # device to finish the child's programs.
                    with trace_range("TpuShuffleWrite.counts"):
                        ns = DS.fetch_counts([nr for _b, _p, nr in chunk])
                    packed = []
                    for (buf_id, pid, _nr), n in zip(chunk, ns):
                        n = int(n)
                        if n:
                            block_id, counts, starts, size, cut = \
                                R.retry_call(functools.partial(
                                    pack_one, buf_id, n, rr), rctx)
                            # round-robin offset: same write order as
                            # the host path → bit-identical placement
                            rr = (rr + n) % self.n_out
                            added.append(block_id)
                            if catalog is not None:
                                catalog.add_buffer(shuffle_id, pid,
                                                   block_id)
                            device_sizes[block_id] = size
                            DS.GLOBAL.add("deviceBytes", size)
                            if cut:
                                DS.GLOBAL.add("trimmedBlocks")
                                DS.GLOBAL.add("trimmedRows", cut)
                            packed.append((block_id, size, counts,
                                           starts))
                        # the input goes, packed or empty (and then
                        # never built): its memory now, its slot below
                        fw.remove_batch(buf_id)
                    drop([buf_id for buf_id, _p, _nr in chunk])
                    chunk.clear()
                    with trace_range("TpuShuffleWrite.counts"):
                        got = DS.fetch_counts(
                            [(c, s) for _b, _z, c, s in packed])
                    for (block_id, size, _c, _s), (counts, starts) in \
                            zip(packed, got):
                        items.append((block_id, np.asarray(counts),
                                      np.asarray(starts)))
                        # arena-accounting block size: metadata math,
                        # no device touch — AQE's byte estimate
                        stat_state["bytes"] += size
                    return
                with trace_range("TpuShuffleWrite.counts"):
                    got = jax.device_get([(nr, samp)
                                          for _b, nr, samp in chunk])
                for (buf_id, _nr, _s), (n, samp) in zip(chunk, got):
                    n = int(n)
                    if n == 0:
                        fw.remove_batch(buf_id)
                        continue
                    if samp is not None:
                        samples.append(np.asarray(samp))
                    items.append((buf_id, rr, n))
                    rr = (rr + n) % self.n_out
                chunk.clear()

            added = []  # every buffer this ATTEMPT registered
            try:
                with trace_range(self.SPAN,
                                 self.metrics[M.TOTAL_TIME]):
                    for pid in range(child.n_partitions):
                        for b in child.iterator(pid):
                            buf_id = R.retry_call(
                                lambda b=b: write_one(b), rctx)
                            added.append(buf_id)
                            if catalog is not None:
                                catalog.add_buffer(shuffle_id, pid,
                                                   buf_id)
                            if device_path:
                                chunk.append((buf_id, pid, b.num_rows))
                            else:
                                if mode == "host":
                                    # the host-staged path: serialize +
                                    # CRC-stamp NOW, not at spill time
                                    staged = fw.stage_to_host(buf_id)
                                    if staged:
                                        DS.GLOBAL.add("hostBytes",
                                                      staged)
                                samp = None
                                if is_range:
                                    passes = self._passes_kernel(b)
                                    nr = jnp.asarray(b.num_rows,
                                                     dtype=jnp.int32)
                                    samp = self._sample_kernel(passes,
                                                               nr)
                                    if pend_budget > 0:
                                        pending.append((buf_id, id(b),
                                                        passes))
                                        pend_budget -= passes.size * 8
                                chunk.append((buf_id,
                                              jnp.asarray(
                                                  b.num_rows,
                                                  dtype=jnp.int32),
                                              samp))
                                # metadata-only size estimate (host
                                # path has no packed-block accounting)
                                stat_state["bytes"] += int(
                                    b.device_bytes())
                            if len(chunk) >= 32:
                                flush()
                    flush()
            except BaseException:
                # a failed attempt must not leave its partial map
                # output resident until query end — the re-armed retry
                # registers a full fresh set.  The catalog slots go
                # with the buffers: a retried stage must not leak the
                # dead attempt's ids in the shuffle index.
                drop(added)
                raise
            if is_range and samples:
                import jax.numpy as jnp

                bounds = jnp.asarray(pick_bounds_host(
                    np.concatenate(samples, axis=1), self.n_out))
                state["bounds"] = bounds
                # reuse the write-time key passes: pid prefill while the
                # batches are still resident (a spilled+promoted batch
                # misses on the id check and recomputes via the kernel).
                # Only for buffers that survived flush() — empty batches
                # were removed there, and a pid entry for a dead buf_id
                # would pin unspillable HBM forever (no spill listener
                # ever fires for it).
                live = {it[0] for it in items}
                for buf_id, bid, passes in pending:
                    if buf_id in live:
                        pid_cache[buf_id] = (
                            bid, self._bounds_pid_kernel(passes, bounds))
            store.append(items)
            if stage_stats is not None:
                # the numbers below are ALL host-resident already (the
                # gated flush pulled them); recording is pure host math
                stage_stats.record_exchange(
                    exchange_id, items=items, n_out=self.n_out,
                    device_path=device_path,
                    total_bytes=stat_state["bytes"],
                    partitioning=type(self.partitioning).__name__,
                    name=self.describe())

        def materialized():
            """Shuffle write: batches registered as spillable in the
            device store (reference: RapidsCachingWriter keeps map
            output in HBM, spillable under pressure).  A FAILED write
            re-arms the election instead of caching the error forever,
            so a task-level retry (collect_batches) re-executes the
            write from lineage — without this, taskRetries would be a
            no-op below any exchange."""
            # `store` is appended ONLY on success and success is
            # permanent — gating on it is race-free, unlike reading the
            # done/error pair outside the lock
            if store:
                return store[0]
            with elect_lock:
                if store:
                    return store[0]
                if done.is_set():
                    # failed write: reset so THIS task re-drains
                    state["error"] = None
                    state["writer"] = False
                    done.clear()
                i_write = not state["writer"]
                state["writer"] = True
            if i_write:
                try:
                    _drain_child()
                    _maybe_checkpoint()
                except BaseException as e:  # noqa: BLE001
                    state["error"] = e
                    raise
                finally:
                    done.set()
            else:
                # never wait on another task's progress while holding
                # the device (reference: GpuSemaphore released during
                # host-side waits, GpuSemaphore.scala:58-98).  The wait
                # itself is unbounded ON PURPOSE: a wedged writer fails
                # through its own semaphore watchdog, which propagates
                # here via state["error"] — a long legitimate shuffle
                # write (big scan + first compiles) must not be capped.
                if sem is not None:
                    sem.release_all()
                done.wait()
                if not store:
                    raise RuntimeError(
                        "shuffle write failed in peer task"
                    ) from state["error"]
                # re-enter device admission before the reader-side
                # slice kernels run on the resident batches (nothing
                # downstream re-acquires for already-on-device data)
                if sem is not None:
                    sem.acquire_if_necessary()
            return store[0]

        def _maybe_checkpoint():
            """Persist the completed exchange as a durable stage
            checkpoint (recovery/).  Runs in the writer branch right
            after a SUCCESSFUL drain, under the injection shield (a
            fault drill must not fire inside framework persistence),
            and never fails the query — any error disables
            checkpointing for the rest of the query instead."""
            if rec is None or rfp is None \
                    or not rec.should_checkpoint(rfp):
                return
            from ..data.column import device_to_host
            from ..native import serializer
            from ..recovery.manager import schema_signature

            frames = []
            try:
                with F._shield():
                    for p in range(self.n_out):
                        plist = []
                        for b in make(p)():
                            hb = device_to_host(b, trim=True)
                            plist.append((serializer.serialize(hb),
                                          hb.num_rows))
                        frames.append(plist)
            except Exception as e:  # noqa: BLE001
                rec.disable(f"checkpoint read-back failed "
                            f"({type(e).__name__}: {e})")
                return
            written = rec.checkpoint_exchange(
                rfp, schema_sig=schema_signature(self.schema),
                n_out=self.n_out,
                part_rows=[sum(r for _f, r in plist)
                           for plist in frames],
                total_bytes=stat_state["bytes"],
                partitioning=type(self.partitioning).__name__,
                frames=frames)
            if written:
                DS.GLOBAL.add("checkpointBytes", written)

        # drop cached pids the moment their batch is spilled off the
        # device — they are unspillable HBM and would defeat the spill.
        # A spilled DEVICE-path block is the per-buffer degradation
        # rung: the block serializes + CRC-stamps on the way down, so
        # account its bytes to the host side and surface the fallback.
        def on_spill(bid):
            pid_cache.pop(bid, None)
            size = device_sizes.pop(bid, None)
            if size:
                DS.GLOBAL.add("hostBytes", size)
                DS.GLOBAL.add("numFallbacks")
                emit_event("shuffle_fallback", reason="spill",
                           buf_id=bid, bytes=size)

        fw.spill_listeners.append(on_spill)

        def pids_of(buf_id, b, rr_start):
            cached = pid_cache.get(buf_id)
            if cached is not None and cached[0] == id(b):
                return cached[1]
            pids = self._pids(b, rr_start, state["bounds"])
            pid_cache[buf_id] = (id(b), pids)
            return pids

        def recompute_from_lineage(cause):
            """A corrupt map-output payload was detected on read: free
            the whole attempt's buffers (slots included) and re-arm the
            writer election, so the task-level retry re-executes the
            shuffle write from lineage instead of consuming garbage
            (the recompute contract of TpuPayloadCorruption)."""
            with elect_lock:
                old = store[0] if store else []
                store.clear()
                state["writer"] = False
                state["error"] = cause
                done.clear()
            drop([it[0] for it in old])

        def acquire_block(buf_id):
            # promotion of a spilled map-output batch is an
            # allocation: route it through the retry framework
            try:
                return R.retry_call(
                    lambda bid=buf_id: fw.acquire_batch(bid),
                    rctx)
            except TpuPayloadCorruption as corrupt:
                recompute_from_lineage(corrupt)
                raise
            except KeyError as gone:
                # a peer reader already invalidated this
                # attempt (its corruption recovery freed the
                # buffers while we iterated the old id list):
                # surface a TYPED recoverable fault so task
                # retry / the ladder re-execute from lineage
                # instead of dying on a bare KeyError
                from ..fault.errors import TpuStageCrash

                raise TpuStageCrash(
                    "shuffle map output invalidated by a "
                    "peer's corruption recovery — re-reading "
                    "from the re-executed write",
                    site="exchange.read") from gone

        def make(p, segments=None):
            """Reader for partition ``p``.  With ``segments`` (AQE skew
            split, device path only) only the given contiguous
            ``(item_idx, row_lo, row_hi)`` chunks of the partition are
            sliced out — in order, so concatenating every slice of a
            split reproduces the partition's exact row sequence."""
            def it():
                import jax
                import jax.numpy as jnp

                def read_packed(buf_id, start, n):
                    # one block's read: promotion if it was spilled and
                    # the slice kernel's dispatch (no sync: ``n`` is a
                    # host int already)
                    with trace_range("TpuShuffleRead"):
                        b = acquire_block(buf_id)
                        try:
                            return self._packed_slice_kernel(
                                b, jnp.int32(start), jnp.int32(n),
                                metrics=self.metrics)
                        finally:
                            fw.release_batch(buf_id)

                if segments is not None:
                    assert device_path, "segment reads are device-path"
                    items_now = materialized()
                    for item_idx, row_lo, row_hi in segments:
                        buf_id, counts, starts = items_now[item_idx]
                        n = int(row_hi) - int(row_lo)
                        if n <= 0:
                            continue
                        F.maybe_inject_fault("exchange.read")
                        out = read_packed(
                            buf_id, int(starts[p]) + int(row_lo), n)
                        self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                        yield DeviceBatch(out.schema, out.columns, n)
                    return

                # chunked streaming: one count sync per K slices (vs a
                # device RTT per (partition, batch) pair) WITHOUT
                # materializing the whole partition's slices at once —
                # at most K unspillable slice batches are live
                outs = []

                def drain_outs():
                    with trace_range("TpuShuffleRead"):
                        counts = jax.device_get(
                            [o.num_rows for o in outs])
                    for out, n in zip(outs, counts):
                        if int(n):
                            self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                            yield out
                    outs.clear()

                # the write (TpuShuffleWrite) happens inside
                # materialized(), before the first read range opens;
                # each read range closes before its batch is handed on
                for item in materialized():
                    F.maybe_inject_fault("exchange.read")
                    buf_id = item[0]
                    if device_path:
                        # packed block: counts are already on host from
                        # the write-side flush — skip empty partitions
                        # without touching the device at all
                        counts, starts = item[1], item[2]
                        n = int(counts[p])
                        if n == 0:
                            continue
                        # slice the contiguous row range out of the
                        # packed block; count is a HOST int already, so
                        # the yielded batch needs no num_rows sync
                        out = read_packed(buf_id, int(starts[p]), n)
                        self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                        yield DeviceBatch(out.schema, out.columns, n)
                        continue
                    rr_start = item[1]
                    with trace_range("TpuShuffleRead"):
                        b = acquire_block(buf_id)
                        try:
                            outs.append(self._slice_kernel(
                                b, pids_of(buf_id, b, rr_start),
                                jnp.int32(p)))
                        finally:
                            fw.release_batch(buf_id)
                    if len(outs) >= 8:
                        yield from drain_outs()
                if outs:
                    yield from drain_outs()

            return it

        result = DevicePartitionedData([make(i) for i in range(self.n_out)])
        # AQE handles: the adaptive executor materializes this exchange
        # eagerly (aqe_materialize == the writer election) and builds
        # re-grouped readers over the SAME resident buffers via
        # aqe_read(p, segments) — see adaptive/executor.py
        result.aqe_materialize = materialized
        result.aqe_read = make
        result.aqe_exchange_id = exchange_id
        result.aqe_device_path = device_path
        result.aqe_exchange = self
        # free the shuffle buffers when the read side is dropped — the
        # backstop behind the query-end per-shuffle cleanup in
        # Session.execute (reference: ShuffleBufferCatalog cleanup;
        # without either, every query's shuffle data stays resident for
        # the life of the process)
        weakref.finalize(result, _free_shuffle_buffers, fw, store,
                         on_spill, catalog, shuffle_id)
        return result

    def _resumed_result(self, ctx, manifest, parts):
        """Build this exchange's result from checkpointed host frames
        (already CRC-verified by ``try_resume``): readers deserialize +
        upload on demand, the AQE handles stay intact — a resumed
        exchange is a first-class materialized stage (exact per-
        partition rows recorded into ``ctx.stage_stats``, so
        coalescing/broadcast rewrites still fire; ``device_path`` is
        False, which correctly disables segment/skew reads — there are
        no live packed blocks to slice)."""
        self._init_metrics(ctx)
        stage_stats = getattr(ctx, "stage_stats", None)
        exchange_id = (stage_stats.allocate_id()
                       if stage_stats is not None else 0)
        if stage_stats is not None:
            stage_stats.record_resumed(
                exchange_id, n_out=self.n_out,
                part_rows=manifest.get("part_rows") or [],
                total_bytes=int(manifest.get("total_bytes", 0)),
                partitioning=type(self.partitioning).__name__,
                name=self.describe())
        schema = self.schema

        def make(p, segments=None):
            # segment (skew-split) reads need live packed device
            # blocks; record_resumed reports device_path=False so the
            # adaptive planner never requests them here
            assert segments is None, \
                "segment reads are impossible on a resumed exchange"

            def it():
                from ..data.column import host_to_device
                from ..native import serializer

                for frame in parts[p]:
                    hb = serializer.deserialize(frame, schema)
                    if hb.num_rows == 0:
                        continue
                    self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                    yield host_to_device(hb)

            return it

        result = DevicePartitionedData(
            [make(i) for i in range(self.n_out)])
        result.aqe_materialize = lambda: None  # nothing left to drain
        result.aqe_read = make
        result.aqe_exchange_id = exchange_id
        result.aqe_device_path = False
        result.aqe_exchange = self
        return result

    def describe(self):
        return f"TpuShuffleExchange[{self.partitioning.describe()}]"


# ==========================================================================
# rule registration
# ==========================================================================
def register(register_exec):
    from ..plan import physical as P

    def exprs_of(plan: P.ShuffleExchangeExec):
        part = plan.partitioning
        if isinstance(part, RangePartitioning):
            keys = part._bound_keys or part.sort_keys
            return [k.expr for k in keys]
        return list(getattr(part, "_bound", None)
                    or getattr(part, "keys", []) or [])

    def tag(meta):
        if isinstance(meta.plan.partitioning, HashPartitioning):
            for e in exprs_of(meta.plan):
                gap = hashing.device_hash_gap(e.dtype)
                if gap is not None:
                    meta.will_not_work_on_tpu(
                        f"hash partitioning on {e.sql()}: {gap}")

    register_exec(
        P.ShuffleExchangeExec,
        convert=lambda meta, ch: TpuShuffleExchangeExec(ch[0], meta.plan),
        desc="device hash/single/round-robin/range exchange",
        tag=tag,
        exprs_of=exprs_of)
