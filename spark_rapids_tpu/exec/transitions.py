"""Host<->device columnar transitions.

Reference analogue: GpuRowToColumnarExec (upload), GpuColumnarToRowExec
(download), HostColumnarToGpu, GpuBringBackToHost.  The host engine here
is already columnar, so the transitions are HostBatch <-> DeviceBatch
transfers: HostToDeviceExec acquires the device semaphore just before
upload (the reference acquires just before GPU decode,
GpuParquetScan.scala:554)."""
from __future__ import annotations

from ..data.column import bucket_rows, device_to_host, host_to_device
from ..config import (BUCKET_MIN_ROWS, FAULT_QUEUE_PUT_TIMEOUT_MS,
                      READER_BATCH_SIZE_BYTES, READER_BATCH_SIZE_ROWS,
                      READER_PREFETCH_BATCHES, STRING_COLUMN_BYTES_GUARD)
from ..fault.errors import TpuPayloadCorruption, TpuStageTimeout
from ..memory import retry as R
from ..plan.physical import PartitionedData
from ..utils import metrics as M
from ..utils.tracing import trace_range
from .base import DevicePartitionedData, TpuExec


def _split_host_batch(batch, max_rows: int, max_bytes: int):
    """Slice an oversize host batch to the reader size targets before
    upload (reference: populateCurrentBlockChunk batching row groups by
    reader.batchSizeRows/Bytes, GpuParquetScan.scala:571) — this is what
    makes multi-batch partitions, and with them the out-of-core operator
    paths, actually occur."""
    n = batch.num_rows
    if n == 0:
        yield batch
        return
    rows_cap = max(1, max_rows)
    est = batch.estimate_bytes()
    if est > max_bytes:
        rows_cap = min(rows_cap, max(1, int(n * max_bytes / est)))
    if rows_cap >= n:
        yield batch
        return
    for start in range(0, n, rows_cap):
        yield batch.slice(start, min(start + rows_cap, n))


def _bounded_put(q, item, stop, timeout_s: float) -> bool:
    """Producer-side put into a bounded prefetch queue that (a) honors
    the consumer's stop flag and (b) surfaces a watchdog error instead
    of busy-looping silently when the queue stays full past
    ``timeout_s`` (the consumer has died or wedged — satellite of the
    r3 prefetch-deadlock family).  Returns False when stopped; raises
    :class:`TpuStageTimeout` on deadline; True when delivered."""
    import queue as _queue
    import time as _time

    from ..scheduler.cancel import check_cancel

    deadline = (_time.monotonic() + timeout_s) if timeout_s > 0 else None
    while not stop.is_set():
        check_cancel("h2d.prefetch")
        try:
            q.put(item, timeout=0.1)
            return True
        except _queue.Full:
            if deadline is not None and _time.monotonic() > deadline:
                raise TpuStageTimeout(
                    f"h2d prefetch queue stayed full for {timeout_s:.0f}s"
                    " — the consumer stopped draining (died or wedged); "
                    "abandoning the producer instead of spinning",
                    site="h2d.prefetch")
    return False


def _next_prefetched(q, producer, err):
    """Consumer-side bounded get: returns the next queue item, or
    raises when the producer died without delivering its END sentinel
    (``err`` is the producer's one-slot error box).  Never blocks
    forever on a dead producer."""
    import queue as _queue

    from ..scheduler.cancel import check_cancel

    while True:
        check_cancel("h2d.prefetch")
        try:
            return q.get(timeout=1.0)
        except _queue.Empty:
            if err[0] is not None:
                raise err[0]
            if not producer.is_alive():
                # the producer may have delivered its last item (or
                # END) and exited between our get() expiry and the
                # liveness check: drain once more before declaring it
                # dead, or a healthy partition retries spuriously
                try:
                    return q.get_nowait()
                except _queue.Empty:
                    pass
                if err[0] is not None:
                    raise err[0]
                raise TpuStageTimeout(
                    "h2d prefetch producer died without delivering a "
                    "result or error", site="h2d.prefetch")


def _free_cached_uploads(fw, store):
    for entries in store.values():
        for buf_id, _n in entries:
            try:
                fw.remove_batch(buf_id)
            except Exception:  # noqa: BLE001 - interpreter teardown
                pass


class HostToDeviceExec(TpuExec):
    """Upload host batches to device HBM (GpuRowToColumnarExec /
    HostColumnarToGpu analogue).

    Uploads of IMMUTABLE in-memory sources (LocalScanExec) are cached
    as spill-registered device batches, so repeated collects of the
    same plan skip the encode+transfer entirely — the analogue of the
    reference keeping hot tables device-resident via the device store.
    Only fully-drained partitions are published (a limit() that
    abandons a partition early must not cache a partial read); file
    scans are never cached (files can change on disk)."""

    def __init__(self, child):
        super().__init__([child])

    def drop_cached_uploads(self) -> None:
        """Unregister every cached upload (cancellation unwind): a
        cancelled query must leave zero tracked device bytes behind,
        and a cached upload is the one device artifact that outlives
        its query by design.  The ``weakref.finalize`` hook stays armed
        but finds the stores empty."""
        caches = getattr(self, "_upload_caches", None)
        if not caches:
            return
        from ..memory.spill import SpillFramework

        fw = SpillFramework.get()
        for store in caches.values():
            _free_cached_uploads(fw, store)
            store.clear()
        caches.clear()

    @property
    def schema(self):
        return self.children[0].schema

    @property
    def coalesce_after(self) -> bool:
        return True

    def execute_columnar(self, ctx) -> DevicePartitionedData:
        child_data = self.children[0].execute(ctx)
        self._init_metrics(ctx)
        sem = self._sem(ctx)
        min_rows = ctx.conf.get(BUCKET_MIN_ROWS)
        max_rows = ctx.conf.get(READER_BATCH_SIZE_ROWS)
        max_bytes = ctx.conf.get(READER_BATCH_SIZE_BYTES)
        prefetch = ctx.conf.get(READER_PREFETCH_BATCHES)
        put_timeout_s = ctx.conf.get(FAULT_QUEUE_PUT_TIMEOUT_MS) / 1000.0

        fw = store = None
        from ..plan.physical import LocalScanExec

        if isinstance(self.children[0], LocalScanExec) \
                and ctx.session is not None \
                and ctx.session.spill_framework is not None:
            import weakref

            fw = ctx.session.spill_framework
            key = (min_rows, max_rows, max_bytes)
            caches = getattr(self, "_upload_caches", None)
            if caches is None:
                caches = self._upload_caches = {}
            store = caches.get(key)
            if store is None:
                # pid -> [(buf id, row count)], complete drains only
                store = caches[key] = {}
                weakref.finalize(self, _free_cached_uploads, fw, store)

        str_guard = ctx.conf.get(STRING_COLUMN_BYTES_GUARD)
        rctx = R.RetryContext.for_exec(ctx, "HostToDeviceExec")
        waits = ctx.metrics.metric(f"{self.name}.prefetchWaits")
        # rows x width of the string columns' byte matrices as uploaded
        matrix_bytes = ctx.metrics.metric(f"{self.name}.stringMatrixBytes")
        # string columns uploaded from Arrow's buffers (a scan's), and
        # from python objects (every other producer's)
        from_arrow = ctx.metrics.metric(
            f"{self.name}.stringColumnsFromArrow")
        from_objects = ctx.metrics.metric(
            f"{self.name}.stringColumnsFromObjects")

        def upload(hb):
            import time as _time

            if sem:
                sem.acquire_if_necessary()
            R.maybe_inject_oom("HostToDeviceExec.upload")
            t0 = _time.perf_counter_ns()
            with trace_range("HostToDevice",
                             self.metrics[M.TOTAL_TIME]):
                db = host_to_device(hb, min_rows,
                                    string_guard_bytes=str_guard)
            dt = _time.perf_counter_ns() - t0
            sync = self.metrics.get(M.DEVICE_SYNC_TIME)
            if sync is not None:  # registered only under telemetry
                sync.add(dt)
            self.metrics[M.NUM_OUTPUT_ROWS].add(hb.num_rows)
            self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
            matrix_bytes.add(sum(c.data.size for c in db.columns
                                 if c.lengths is not None))
            n_arrow = sum(c.arrow_strings() is not None
                          for c in hb.columns)
            from_arrow.add(n_arrow)
            from_objects.add(sum(c.lengths is not None
                                 for c in db.columns) - n_arrow)
            return db

        def upload_retry(hb):
            # an upload that OOMs is retried after spill+backoff; a
            # split request halves the host batch (down to the
            # minSplitRows floor) and uploads the pieces in row order
            return R.with_split_retry(hb, upload, ctx=rctx)

        def make(pid):
            def it_cached():
                # the pin is held while the CONSUMER uses the batch
                # (released when the next one is acquired), so the
                # spiller can never evict an in-use buffer and
                # undercount real HBM
                held = None
                try:
                    for buf_id, n_rows in store[pid]:
                        if sem:
                            sem.acquire_if_necessary()
                        # promote if spilled (a promotion is an
                        # allocation: OOMs recover via spill+backoff)
                        try:
                            b = R.retry_call(
                                lambda bid=buf_id: fw.acquire_batch(bid),
                                rctx)
                        except TpuPayloadCorruption:
                            # a cached upload rotted on a spill tier:
                            # drop the partition's cache entries and let
                            # the task-level retry re-upload from the
                            # source (recompute-from-lineage)
                            entries = store.pop(pid, [])
                            held = None
                            for bid, _n in entries:
                                fw.remove_batch(bid)
                            raise
                        if held is not None:
                            fw.release_batch(held)
                        held = buf_id
                        self.metrics[M.NUM_OUTPUT_ROWS].add(n_rows)
                        self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                        yield b
                finally:
                    if held is not None:
                        fw.release_batch(held)

            def it_recording(inner):
                # each batch registers with the spill framework AS IT
                # STREAMS (an unregistered accumulation would pin the
                # whole partition in HBM, invisible to the spiller);
                # only a fully-drained partition publishes its entries
                import jax

                ids = []
                nrs = []
                complete = False
                try:
                    for db in inner:
                        ids.append(R.retry_call(
                            lambda d=db: fw.add_batch(
                                d, site="upload.cache"), rctx))
                        nrs.append(db.num_rows)
                        yield db
                    complete = True
                finally:
                    if complete:
                        counts = [int(n) for n in jax.device_get(nrs)] \
                            if nrs else []
                        entries = list(zip(ids, counts))
                        if store.setdefault(pid, entries) is not entries:
                            # someone else published first (concurrent
                            # drain of the same partition): drop ours
                            for i in ids:
                                fw.remove_batch(i)
                    else:
                        for i in ids:  # abandoned drain (limit)
                            fw.remove_batch(i)

            def it_inline():
                for batch in child_data.iterator(pid):
                    for hb in _split_host_batch(batch, max_rows,
                                                max_bytes):
                        yield from upload_retry(hb)

            def it_pipelined():
                # decode/upload overlap: a host-only producer thread
                # decodes ahead (bounded queue) while this task uploads
                # and computes — the scan-bound analogue of the
                # reference holding the semaphore only for device work
                # (GpuParquetScan.scala:554-556).  The producer never
                # touches the device, so it needs no semaphore.
                import queue
                import threading

                from ..telemetry import spans as tspans

                q: "queue.Queue" = queue.Queue(maxsize=max(prefetch, 1))
                stop = threading.Event()
                END = object()
                err = [None]  # producer's error box (queue-independent:
                # a full queue must not swallow the failure)

                def produce():
                    try:
                        for batch in child_data.iterator(pid):
                            for hb in _split_host_batch(
                                    batch, max_rows, max_bytes):
                                if not _bounded_put(q, hb, stop,
                                                    put_timeout_s):
                                    return
                        _bounded_put(q, END, stop, put_timeout_s)
                    except BaseException as e:  # noqa: BLE001
                        err[0] = e

                # the producer thread inherits no thread-locals: the
                # telemetry binding is captured here and attached in
                # the worker (the thread-capture analysis rule
                # enforces this at every spawn site)
                t = threading.Thread(
                    target=tspans.bound(tspans.capture(), produce),
                    daemon=True, name=f"h2d-prefetch-{pid}")
                from ..scheduler.cancel import check_cancel

                t.start()
                try:
                    while True:
                        check_cancel("h2d.consume")
                        try:
                            item = q.get_nowait()
                        except queue.Empty:
                            # never block on the producer while holding
                            # the device — the producer may itself need
                            # a permit (host-fallback sandwich plans run
                            # device sections inside the child), and a
                            # held-while-blocked permit is the exact
                            # shape of the r3 deadlocks
                            if sem:
                                sem.release_all()
                            # the consumer blocked on the decode queue:
                            # whatever the device idles here, it idles
                            # for the producer's ScanDecode
                            waits.add(1)
                            with trace_range("PrefetchWait"):
                                item = _next_prefetched(q, t, err)
                        if item is END:
                            break
                        yield from upload_retry(item)
                finally:
                    stop.set()

            def it():
                if store is not None and pid in store:
                    return it_cached()
                inner = it_pipelined() if prefetch > 0 else it_inline()
                if store is not None:
                    return it_recording(inner)
                return inner

            return it

        return DevicePartitionedData(
            [make(i) for i in range(child_data.n_partitions)])

    def describe(self):
        return "HostToDevice"


class DeviceToHostExec(TpuExec):
    """Download device batches to the host engine (GpuColumnarToRowExec /
    GpuBringBackToHost analogue).  Releases the device semaphore after
    download so queued tasks can enter."""

    def __init__(self, child):
        super().__init__([child])

    @property
    def schema(self):
        return self.children[0].schema

    def execute(self, ctx) -> PartitionedData:
        child_data = self.children[0].execute_columnar(ctx)
        self._init_metrics(ctx)
        sem = self._sem(ctx)
        # what DeviceToHost.copy brought back (estimated: strings sampled)
        copied = ctx.metrics.metric(f"{self.name}.copiedBytes")

        def make(pid):
            def it():
                import time as _time

                from ..data.column import device_to_host_many

                # chunked drain: one batched download per K batches —
                # a per-batch device_to_host pays 2 device syncs each.
                # K bounds how many device batches the chunk pins at
                # once.
                chunk = []

                def drain():
                    t0 = _time.perf_counter_ns()
                    with trace_range("DeviceToHost",
                                     self.metrics[M.TOTAL_TIME]):
                        hbs = device_to_host_many(chunk)
                    copied.add(sum(hb.estimate_bytes() for hb in hbs))
                    sync = self.metrics.get(M.DEVICE_SYNC_TIME)
                    if sync is not None:  # telemetry-only metric
                        sync.add(_time.perf_counter_ns() - t0)
                    if sem:
                        sem.release_if_necessary()
                    for hb in hbs:
                        self.metrics[M.NUM_OUTPUT_ROWS].add(hb.num_rows)
                        self.metrics[M.NUM_OUTPUT_BATCHES].add(1)
                        yield hb
                    chunk.clear()

                for db in child_data.iterator(pid):
                    chunk.append(db)
                    if len(chunk) >= 8:
                        yield from drain()
                if chunk:
                    yield from drain()
                if sem:
                    sem.release_if_necessary()

            return it

        return PartitionedData(
            [make(i) for i in range(child_data.n_partitions)])

    def execute_columnar(self, ctx):
        raise RuntimeError("DeviceToHostExec is a host boundary")

    def describe(self):
        return "DeviceToHost"
