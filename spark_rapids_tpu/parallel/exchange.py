"""Collective repartition: the TPU-native shuffle data path.

Reference analogue: the entire L7/L8 stack — GpuShuffleExchangeExec's
`prepareBatchShuffleDependency` (GpuShuffleExchangeExec.scala:123, GPU
hash-partition + contiguousSplit) plus the UCX transport's tagged
bounce-buffer transfers (RapidsShuffleClient.scala:452-555,
RapidsShuffleServer.scala:380-661).  On TPU the whole client/server/
bounce-buffer/tag machinery collapses into ONE compiled collective:

    per device:  bucket rows by destination into fixed [P, C] tiles
    all devices: `lax.all_to_all` over the mesh axis  (ICI data path)
    per device:  compact received rows to the front

because the XLA runtime owns transfer scheduling (SURVEY §2.9 UCX row,
§5 "Distributed communication backend").  Fixed tile capacity C keeps
shapes static — the inflight-bytes throttle of the reference
(maxReceiveInflightBytes, RapidsConf.scala:512) becomes a compile-time
capacity instead.

All functions here are shard_map-compatible: they take/return plain jax
arrays (or DeviceBatch pytrees) and are traced per-shard.
"""
from __future__ import annotations

from typing import List, Tuple

from ..data.column import DeviceBatch, DeviceColumn
from ..ops.kernels.gather import compact, take_rows
from ..utils import hashing
from ..utils.tracing import device_phase


@device_phase("shuffle.hashPids")
def device_partition_ids(batch: DeviceBatch, key_indices, num_parts: int):
    """Spark-compatible murmur3 pmod partition ids on device; rows past
    ``num_rows`` get id ``num_parts`` (a sentinel the bucketer drops).

    Reference analogue: GpuHashPartitioning.scala (cudf spark-murmur3
    hash-partition kernel) — bit-identical row placement to the host
    oracle via the same hash (utils/hashing.py).
    """
    import jax.numpy as jnp

    cols = [batch.columns[i] for i in key_indices]
    h = hashing.hash_device_batch(cols)
    pid = hashing.pmod(h, num_parts).astype(jnp.int32)
    return jnp.where(batch.row_mask(), pid, num_parts)


@device_phase("shuffle.packedBuild")
def bucket_rows(pids, num_parts: int, capacity: int):
    """Pack row indices into per-destination tiles.

    pids: int32[N] in [0, num_parts]; ``num_parts`` = dropped sentinel.
    Returns (rows int32[num_parts, capacity], valid bool[num_parts,
    capacity]): for each destination d, ``rows[d, :k]`` are the source
    rows headed to d (k = count), remaining lanes masked invalid.

    This is the contiguousSplit analogue (Plugin.scala:54-83): one
    stable sort by destination yields every split at once.
    """
    import jax.numpy as jnp

    n = pids.shape[0]
    order = jnp.argsort(pids, stable=True).astype(jnp.int32)
    sorted_pids = pids[order]
    bounds = jnp.searchsorted(
        sorted_pids, jnp.arange(num_parts + 1, dtype=pids.dtype))
    starts = bounds[:-1].astype(jnp.int32)
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    lane = jnp.arange(capacity, dtype=jnp.int32)
    gidx = starts[:, None] + lane[None, :]
    valid = lane[None, :] < counts[:, None]
    rows = order[jnp.clip(gidx, 0, n - 1)]
    return rows, valid


def gather_replicate(batch: DeviceBatch, axis_name: str) -> DeviceBatch:
    """Replicate every shard's rows onto every device — the mesh form of
    the broadcast exchange (GpuBroadcastExchangeExec.scala:215: build
    once, ship everywhere; here one `all_gather` over ICI)."""
    import jax

    present = jax.lax.all_gather(batch.row_mask(), axis_name, tiled=True)
    cols = []
    for c in batch.columns:
        data = jax.lax.all_gather(c.data, axis_name, tiled=True)
        validity = jax.lax.all_gather(c.validity, axis_name, tiled=True)
        lengths = (jax.lax.all_gather(c.lengths, axis_name, tiled=True)
                   if c.lengths is not None else None)
        cols.append(DeviceColumn(c.dtype, data, validity, lengths))
    return compact(DeviceBatch(batch.schema, cols, present.shape[0]),
                   present)


def collective_exchange(batch: DeviceBatch, pids, num_parts: int,
                        axis_name: str, capacity: int = 0) -> DeviceBatch:
    """Repartition ``batch`` across the mesh axis inside shard_map.

    Every device contributes a [P, C] tile per column; one
    ``lax.all_to_all`` swaps tile rows so device d ends with the rows
    every peer destined for d.  Output padded size = P * C.
    """
    import jax
    import jax.numpy as jnp

    cap = capacity or batch.padded_rows
    rows, valid = bucket_rows(pids, num_parts, cap)
    with device_phase("shuffle.packedBuild"):
        tiles = take_rows(batch.columns, rows, valid)

    recv_cols = []
    for c in tiles:
        data = jax.lax.all_to_all(c.data, axis_name, 0, 0, tiled=True)
        validity = jax.lax.all_to_all(c.validity, axis_name, 0, 0,
                                      tiled=True)
        lengths = (jax.lax.all_to_all(c.lengths, axis_name, 0, 0,
                                      tiled=True)
                   if c.lengths is not None else None)
        recv_cols.append(DeviceColumn(
            c.dtype,
            data.reshape((num_parts * cap,) + data.shape[2:]),
            validity.reshape(num_parts * cap),
            lengths.reshape(num_parts * cap)
            if lengths is not None else None))

    lane_present = jax.lax.all_to_all(valid, axis_name, 0, 0, tiled=True)
    present = lane_present.reshape(num_parts * cap)
    return compact(DeviceBatch(batch.schema, recv_cols, num_parts * cap),
                   present)


def squeeze_leading(b: DeviceBatch) -> DeviceBatch:
    """Drop the per-shard leading axis inside shard_map: the stacked
    [1, padded, ...] shard view -> a plain [padded, ...] DeviceBatch."""
    cols = [DeviceColumn(c.dtype, c.data[0], c.validity[0],
                         c.lengths[0] if c.lengths is not None else None)
            for c in b.columns]
    return DeviceBatch(b.schema, cols, b.num_rows.reshape(()))


def unsqueeze_leading(b: DeviceBatch) -> DeviceBatch:
    cols = [DeviceColumn(c.dtype, c.data[None], c.validity[None],
                         c.lengths[None] if c.lengths is not None
                         else None)
            for c in b.columns]
    return DeviceBatch(b.schema, cols, b.num_rows.reshape((1,)))


def exchange_step(mesh, fn):
    """Wrap ``fn(local_batch) -> local_batch`` (which may call
    collective_exchange) in shard_map over the mesh's data axis,
    operating on stacked [n_parts, ...] DeviceBatch pytrees.

    The returned callable is a Python-level dispatcher (not the raw
    shard_map program): every collective dispatch goes through the
    elastic layer's ``guarded_call`` — the query's cancellation token
    is polled first (a cancelled query must stop at the next exchange
    instead of joining a mesh-wide collective its peers will wait on),
    a dead peer or a tripped ``fault.peer.collectiveTimeoutMs`` aborts
    with ``TpuPeerLost`` instead of hanging — and its wall clock
    accrues to ``shuffle.collectiveTime``."""
    import jax
    from jax.sharding import PartitionSpec as P

    from ..shuffle.device_shuffle import collective_timer
    from .elastic import guarded_call

    axis = mesh.axis_names[0]

    def per_shard(stacked: DeviceBatch) -> DeviceBatch:
        return unsqueeze_leading(fn(squeeze_leading(stacked)))

    step = jax.shard_map(per_shard, mesh=mesh, in_specs=P(axis),
                         out_specs=P(axis))

    def dispatch(stacked: DeviceBatch) -> DeviceBatch:
        def timed(stacked=stacked):
            with collective_timer():
                return step(stacked)

        return guarded_call(timed)

    return dispatch


def stack_partitions(batches: List[DeviceBatch]) -> DeviceBatch:
    """Stack per-partition DeviceBatches (equal schema + padded rows)
    into one [n_parts, padded, ...] global batch for mesh placement."""
    import jax.numpy as jnp

    b0 = batches[0]
    cols = []
    for i, c0 in enumerate(b0.columns):
        data = jnp.stack([b.columns[i].data for b in batches])
        validity = jnp.stack([b.columns[i].validity for b in batches])
        lengths = (jnp.stack([b.columns[i].lengths for b in batches])
                   if c0.lengths is not None else None)
        cols.append(DeviceColumn(c0.dtype, data, validity, lengths))
    num_rows = jnp.asarray(
        [jnp.asarray(b.num_rows, dtype=jnp.int32) for b in batches],
        dtype=jnp.int32)
    return DeviceBatch(b0.schema, cols, num_rows)


def stack_to_mesh(mesh, stacked: DeviceBatch) -> DeviceBatch:
    """Place a stacked [n_parts, ...] batch on the mesh, leading axis
    split over the data axis."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(mesh.axis_names[0]))
    return jax.device_put(stacked, sharding)


def unstack_partitions(stacked: DeviceBatch) -> List[DeviceBatch]:
    import numpy as np

    n_parts = stacked.columns[0].data.shape[0]
    nrows = np.asarray(stacked.num_rows)
    out = []
    for p in range(n_parts):
        cols = [DeviceColumn(c.dtype, c.data[p], c.validity[p],
                             c.lengths[p] if c.lengths is not None else None)
                for c in stacked.columns]
        out.append(DeviceBatch(stacked.schema, cols, int(nrows[p])))
    return out
