"""Multi-process / multi-host distributed execution.

Reference analogue: the executor model of the RAPIDS shuffle — one JVM
per node, each owning one GPU, with shuffle data moving BETWEEN
processes over UCX (Plugin.scala:219-247 executor bootstrap,
UCX.scala:54-86 worker/endpoint plumbing, RapidsShuffleClient.scala:452
fetch protocol).  The TPU-native form is jax's multi-controller SPMD:

    * every process calls ``jax.distributed.initialize`` (the TCP
      handshake the reference does over its management port,
      UCXConnection.scala:354)
    * the global mesh spans every process's local devices; the SAME
      stage program runs on every controller
    * exchanges stay the SAME compiled ``all_to_all`` — XLA routes
      lanes over ICI within a host and DCN across hosts; the entire
      client/server/bounce-buffer machinery of the reference collapses
      into the runtime (SURVEY §5 "Distributed communication backend")

Host-side control flow (stage loop, capacity retries) is replicated on
every controller, so every decision must derive from replicated values
— the runner pmax-replicates capacity aux outputs for exactly this
reason (see DistributedRunner._run_stage).

Per-process split ownership: each controller decodes ONLY the leaf
partitions assigned to shards on its own devices (reference: every
executor reads its own splits, GpuParquetScan.scala:174; per-map-task
shuffle outputs, RapidsShuffleInternalManager.scala:90-138) and
materializes them as its addressable shards
(``jax.make_array_from_callback``).  Global shard shapes are agreed via
one tiny host allgather of (row-count, string-width) maxima, so every
process compiles the identical program without seeing peer bytes.
Sources with fewer partitions than the mesh are small by construction
and replicate deterministically through the base path instead.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from ..data.column import DeviceBatch, HostBatch, device_to_host
from . import exchange as X
from .runner import DistributedRunner


def init_multiprocess(coordinator: str, num_processes: int,
                      process_id: int,
                      local_cpu_devices: Optional[int] = None):
    """Join the multi-controller job and return the global mesh.

    ``local_cpu_devices``: for tests/CI — force this process onto the
    local CPU backend with that many virtual devices BEFORE the backend
    initializes (the 2-process CPU fixture the reference never had for
    its UCX path, SURVEY §4 "TPU-build implication")."""
    import os
    import re

    if local_cpu_devices:
        os.environ["JAX_PLATFORMS"] = "cpu"
        flags = os.environ.get("XLA_FLAGS", "")
        want = (f"--xla_force_host_platform_device_count="
                f"{local_cpu_devices}")
        if "host_platform_device_count" in flags:
            # an inherited count (e.g. the pytest conftest's 8) must be
            # REPLACED, not kept — otherwise every worker gets the
            # inherited device count and the mesh silently changes size
            flags = re.sub(
                r"--xla_force_host_platform_device_count=\d+", want,
                flags)
            os.environ["XLA_FLAGS"] = flags
        else:
            os.environ["XLA_FLAGS"] = (flags + " " + want).strip()
    import jax

    if local_cpu_devices:
        jax.config.update("jax_platforms", "cpu")
    jax.distributed.initialize(coordinator, num_processes=num_processes,
                               process_id=process_id)
    # single-device work (leaf uploads) must land on a device THIS
    # process owns, never a peer's (the executor-local GPU rule,
    # GpuDeviceManager.scala:98-112)
    jax.config.update("jax_default_device", jax.local_devices()[0])
    from jax.sharding import Mesh

    from .mesh import DATA_AXIS

    devs = np.array(sorted(jax.devices(), key=lambda d: d.id))
    return Mesh(devs, (DATA_AXIS,))


class MultiProcessRunner(DistributedRunner):
    """DistributedRunner over a mesh that spans OS processes/hosts.

    Differences from the single-controller base:
      * leaf placement constructs global arrays shard-by-shard so each
        process only touches devices it owns;
      * inter-stage retiling reads row counts through a replicated
        reduction (a sharded array is not host-readable on every
        controller);
      * the final collect gathers every process's shards
        (``multihost_utils.process_allgather`` — the read side of the
        reference's fetch protocol, RapidsShuffleIterator.scala:45)."""

    def _owned_shards(self) -> List[int]:
        import jax

        pidx = jax.process_index()
        return [s for s, d in enumerate(np.asarray(
            self.mesh.devices).flat) if d.process_index == pidx]

    # ---------------- per-process split ownership ---------------------
    def _run_leaf(self, node, ctx) -> DeviceBatch:
        """Decode ONLY this process's splits (see module docstring).
        Split -> shard assignment is the same deterministic
        ``pid % n_shards`` the base runner uses, restricted to the
        shards on this process's devices."""
        from ..exec.base import TpuExec
        from ..plan.physical import _empty_batch

        is_dev = isinstance(node, TpuExec)
        data = node.execute_columnar(ctx) if is_dev else node.execute(ctx)
        n_parts = data.n_partitions
        if n_parts < self.n:
            # small source: replicated identical execution on every
            # controller (the pre-ownership behavior)
            return super()._run_leaf(node, ctx, data=data)

        owned = self._owned_shards()
        ownset = set(owned)
        my_pids = [p for p in range(n_parts) if p % self.n in ownset]

        sem = None
        if ctx is not None and getattr(ctx, "session", None) is not None \
                and ctx.session.device_manager is not None:
            sem = ctx.session.device_manager.semaphore

        def drain(pid: int) -> List[HostBatch]:
            from ..fault.injector import maybe_inject_fault

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
        deadline_ms = 0
        if ctx is not None and len(my_pids) > 1:
            from ..config import TASK_THREADS

            threads = min(ctx.conf.get(TASK_THREADS), len(my_pids))
        if ctx is not None:
            from ..config import FAULT_STAGE_TIMEOUT_MS

            deadline_ms = ctx.conf.get(FAULT_STAGE_TIMEOUT_MS)
        spec = None
        if ctx is not None:
            from .elastic import SpeculationMonitor

            spec = SpeculationMonitor.from_conf(ctx.conf)
        if threads > 1 or spec is not None:
            # the multi-controller drain loop honors ONE aggregate
            # stage deadline: a wedged decode surfaces TpuStageTimeout
            # (and the leaf re-executes from lineage) instead of
            # blocking this controller's collectives forever while its
            # peers wait.  The shared collector (elastic.py) adds
            # straggler speculation on top: a shard whose drain
            # outlives the rolling latency baseline gets a duplicate
            # attempt, first result wins, the loser is cancelled.
            from .elastic import drain_with_speculation

            got = drain_with_speculation(
                my_pids, drain, max_threads=threads,
                deadline_ms=deadline_ms, site="leaf.drain",
                monitor=spec,
                timeout_msg=lambda done, total: (
                    f"multiprocess leaf drain exceeded "
                    f"fault.stageTimeoutMs={deadline_ms}ms "
                    f"({done}/{total} splits done)"))
            per_pid = [got[p] for p in my_pids]
        else:
            per_pid = [drain(p) for p in my_pids]

        shard_lists = {s: [] for s in owned}
        for pid, bs in zip(my_pids, per_pid):
            shard_lists[pid % self.n].extend(
                b for b in bs if b.num_rows)
        shards = {s: (HostBatch.concat(bs) if bs
                      else _empty_batch(node.schema))
                  for s, bs in shard_lists.items()}
        # host round-trip integrity over the owned shards (same CRC32C
        # stamp/verify contract as the single-controller staging path)
        order = sorted(shards)
        staged = self._verify_host_roundtrip(
            [shards[s] for s in order], ctx)
        shards = dict(zip(order, staged))
        return self._place_owned(shards, node.schema)

    def _place_owned(self, shards, schema) -> DeviceBatch:
        """Build the global stacked mesh arrays from OWNED shards only.
        Shapes must be identical on every controller, so the bucket and
        string widths come from an allgather of local maxima — the only
        cross-process traffic the leaf costs."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from .. import types as T
        from ..data import strings as dstrings
        from ..data.column import DeviceColumn, bucket_rows

        mesh = self.mesh
        n = self.n
        str_cols = [ci for ci, f in enumerate(schema)
                    if f.dtype.id is T.TypeId.STRING]
        # encode owned strings once; agree on (rows, width) maxima
        encs = {}  # (shard, ci) -> (bm, ln)
        local_rows = max((b.num_rows for b in shards.values()),
                         default=0)
        local_w = {ci: 1 for ci in str_cols}
        for s, b in shards.items():
            for ci in str_cols:
                bm, ln = dstrings.encode(b.columns[ci].data,
                                         b.columns[ci].validity)
                encs[(s, ci)] = (bm, ln)
                local_w[ci] = max(local_w[ci], bm.shape[1])
        stats = np.asarray([local_rows]
                           + [local_w[ci] for ci in str_cols],
                           dtype=np.int64)
        # cross-controller collective through the elastic funnel: it
        # polls cancellation BEFORE joining (a cancelled controller
        # entering an allgather wedges every peer), bills the wall to
        # shuffle.collectiveTime, and aborts with TpuPeerLost on a
        # dead peer / tripped fault.peer.collectiveTimeoutMs
        from .elastic import guarded_allgather

        agreed = guarded_allgather(stats).max(axis=0)
        bucket = bucket_rows(max(int(agreed[0]), 1), self.min_bucket)
        widths = {ci: int(w) for ci, w in zip(str_cols, agreed[1:])}

        def garr(shape, dtype, fill):
            """Global [n, ...] array whose addressable shards come from
            ``fill`` (shard idx -> local array without the lead axis)."""
            sh = NamedSharding(mesh, P(*([self.axis]
                                         + [None] * (len(shape) - 1))))

            def cb(idx):
                s = idx[0].start or 0
                return fill(s)[None, ...].astype(dtype, copy=False)

            return jax.make_array_from_callback(shape, sh, cb)

        cols = []
        for ci, f in enumerate(schema):
            if ci in widths:
                w = widths[ci]

                def fill_data(s, ci=ci, w=w):
                    bm, _ln = encs[(s, ci)]
                    out = np.zeros((bucket, w), dtype=np.uint8)
                    out[:bm.shape[0], :bm.shape[1]] = bm
                    return out

                def fill_len(s, ci=ci):
                    _bm, ln = encs[(s, ci)]
                    out = np.zeros(bucket, dtype=np.int32)
                    out[:ln.shape[0]] = ln
                    return out

                def fill_valid(s, ci=ci):
                    b = shards[s]
                    out = np.zeros(bucket, dtype=np.bool_)
                    out[:b.num_rows] = b.columns[ci].is_valid()
                    return out

                cols.append(DeviceColumn(
                    f.dtype,
                    garr((n, bucket, w), np.uint8, fill_data),
                    garr((n, bucket), np.bool_, fill_valid),
                    garr((n, bucket), np.int32, fill_len)))
            else:
                def fill_data(s, ci=ci, dt=f.dtype.np_dtype):
                    b = shards[s]
                    c = b.columns[ci]
                    out = np.zeros(bucket, dtype=dt)
                    valid = c.is_valid()
                    src = np.where(valid, c.data,
                                   np.zeros_like(c.data)) \
                        if c.validity is not None else c.data
                    out[:b.num_rows] = src
                    return out

                def fill_valid(s, ci=ci):
                    b = shards[s]
                    out = np.zeros(bucket, dtype=np.bool_)
                    out[:b.num_rows] = b.columns[ci].is_valid()
                    return out

                cols.append(DeviceColumn(
                    f.dtype,
                    garr((n, bucket), f.dtype.np_dtype, fill_data),
                    garr((n, bucket), np.bool_, fill_valid)))

        sh = NamedSharding(mesh, P(self.axis))
        rows = jax.make_array_from_callback(
            (n,), sh,
            lambda idx: np.asarray(
                [shards[idx[0].start or 0].num_rows], dtype=np.int32))
        return DeviceBatch(schema, cols, rows)

    def _place(self, stacked: DeviceBatch) -> DeviceBatch:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.mesh

        def put(arr):
            arr = np.asarray(arr)
            sh = NamedSharding(mesh, P(*([self.axis]
                                         + [None] * (arr.ndim - 1))))
            return jax.make_array_from_callback(
                arr.shape, sh, lambda idx, a=arr: a[idx])

        cols = []
        from ..data.column import DeviceColumn

        for c in stacked.columns:
            cols.append(DeviceColumn(
                c.dtype, put(c.data), put(c.validity),
                put(c.lengths) if c.lengths is not None else None))
        return DeviceBatch(stacked.schema, cols, put(stacked.num_rows))

    def _retile(self, stacked: DeviceBatch) -> DeviceBatch:
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..data.column import bucket_rows as _bucket

        mx = jax.jit(lambda r: r.max(),
                     out_shardings=NamedSharding(self.mesh, P()))(
            stacked.num_rows)
        need = _bucket(max(int(np.asarray(mx)), 1), self.min_bucket)
        if need >= stacked.padded_rows:
            return stacked
        from ..data.column import DeviceColumn

        sharding = NamedSharding(self.mesh, P(self.axis))

        @jax.jit
        def trim(b):
            cols = [DeviceColumn(
                c.dtype, c.data[:, :need], c.validity[:, :need],
                c.lengths[:, :need] if c.lengths is not None else None)
                for c in b.columns]
            return DeviceBatch(b.schema, cols, b.num_rows)

        out = trim(stacked)
        return jax.device_put(out, sharding)

    def _try_resume_stage(self, ctx, stage, stages):
        """Multi-controller runs never resume mid-query: every
        controller must take the same resume-vs-execute branch or the
        mesh deadlocks in the next collective, and the per-process
        recovery stores give no such guarantee.  The elastic shrink
        path resumes on the surviving single-controller mesh instead
        (runner.py:_try_resume_stage)."""
        return None

    def _stage_host_parts(self, out: DeviceBatch):
        """Stage checkpoints must cover EVERY partition (the surviving
        process resumes the dead peer's shards from its own store), so
        gather the non-addressable shards before serializing."""
        from ..data.column import device_to_host as _d2h
        from .elastic import guarded_allgather

        gathered = guarded_allgather(out, tiled=True)
        return [_d2h(p, trim=True)
                for p in X.unstack_partitions(gathered)]

    def _collect_output(self, out: DeviceBatch, stages) -> HostBatch:
        from .elastic import guarded_allgather

        gathered = guarded_allgather(out, tiled=True)
        # gathered leaves are full global numpy arrays [n, ...]
        parts = X.unstack_partitions(gathered)
        host = [device_to_host(p) for p in parts]
        host = [h for h in host if h.num_rows]
        if not host:
            from ..plan.physical import _empty_batch

            return _empty_batch(self._schema_of(stages[-1].root))
        return HostBatch.concat(host)


def _ship_back_events(ctx) -> None:
    """Telemetry event ship-back: merge every peer controller's events
    into the local query log (alongside the result gather — the same
    collective discipline as the stage programs).  Runs ONLY on the
    success path: after a failed run, peer control flow is not
    guaranteed to reach the collective."""
    tele = getattr(ctx, "telemetry", None)
    if tele is None:
        return
    from ..telemetry.events import gather_multiprocess_events

    try:
        tele.events.extend_shipped(
            gather_multiprocess_events(tele.events.snapshot()))
    except Exception:  # noqa: BLE001 — observability must never fail
        pass          # the query that produced the data


def run_distributed_mp(session, df, mesh) -> HostBatch:
    """Execute ``df`` SPMD across every controller process of ``mesh``.
    Must be called by ALL processes with an identically-built plan;
    returns the full result on every process.

    This is the elastic entry point of the multi-controller path: the
    per-query collective deadline and heartbeat ledger are installed
    here, the unified attempt budget is armed, and a ``TpuPeerLost``
    escaping the runner re-executes on the shrunken mesh (surviving
    devices + recovery checkpoints) instead of failing the query."""
    from ..config import (FAULT_DEGRADE_ENABLED, FAULT_MAX_TOTAL_ATTEMPTS,
                          FAULT_PEER_COLLECTIVE_TIMEOUT_MS,
                          RECOVERY_ENABLED)
    from ..fault.budget import GLOBAL as _budget
    from ..fault.errors import TpuPeerLost
    from ..plan.physical import ExecContext
    from . import elastic
    from .collective import make_transport
    from .mesh import DATA_AXIS as _AX

    phys = session.physical_plan(df.plan)
    ctx = ExecContext(session.conf, session)
    axis = mesh.axis_names[0] if mesh.axis_names else _AX
    recovery = None
    if session.conf.get(RECOVERY_ENABLED):
        from ..recovery import RecoveryManager

        recovery = RecoveryManager(session.conf)
        recovery.attach_query(df.plan)
        recovery.stamp_plan(phys)
        ctx.recovery = recovery
    owned = _budget.begin(session.conf.get(FAULT_MAX_TOTAL_ATTEMPTS))
    ledger = elastic.HeartbeatLedger.from_conf(session.conf)
    prev_ledger = None
    if ledger is not None:
        prev_ledger = elastic.install_heartbeat_ledger(ledger.start())
    prev_deadline = elastic.install_collective_deadline(
        session.conf.get(FAULT_PEER_COLLECTIVE_TIMEOUT_MS))
    shrunk = False
    try:
        try:
            out = MultiProcessRunner(
                mesh, transport=make_transport(session.conf, axis)).run(
                    phys, ctx)
            _ship_back_events(ctx)
            return out
        except TpuPeerLost as e:
            if not session.conf.get(FAULT_DEGRADE_ENABLED):
                raise
            # close the failed attempt's profile BEFORE the rung so
            # session.last_profile ends up as the completed run's
            from ..telemetry import finish_query as _finish

            _finish(session, ctx, phys=phys)
            # the peers are gone (or unreachable): no ship-back, no
            # further collectives against the old mesh — re-form on
            # the surviving devices and resume from checkpoints
            out = elastic.reexecute_on_shrunken_mesh(
                session, df, mesh, f"{type(e).__name__}: {e}",
                recovery=recovery)
            shrunk = True
            return out
    finally:
        elastic.install_collective_deadline(prev_deadline)
        if ledger is not None:
            elastic.install_heartbeat_ledger(prev_ledger)
            ledger.stop()
        budget_snap = _budget.snapshot()  # before end() clears it
        _budget.end(owned)
        from ..fault.stats import GLOBAL as _fault_stats

        from ..shuffle.device_shuffle import GLOBAL as _shuffle_stats

        session.last_metrics = dict(
            getattr(session, "last_metrics", None) or {})
        if not shrunk:
            # the shrunken-mesh rung already merged the failed
            # attempt's counters on top of its own snapshot — a raw
            # re-snapshot here would clobber the carry
            session.last_metrics.update(_fault_stats.snapshot())
        # per-run collective wall/bytes (the dispatch wrappers above
        # accrue into the process-global stats; the ExecContext mark
        # scopes the delta to THIS run, including any shrunken rerun)
        session.last_metrics.update(_shuffle_stats.metrics_since(
            getattr(ctx, "shuffle_stats_mark", None)))
        session.last_metrics.update(budget_snap)
        if recovery is not None:
            session.last_metrics.update(recovery.metrics())
        from ..telemetry import finish_query

        finish_query(session, ctx, phys=phys)
