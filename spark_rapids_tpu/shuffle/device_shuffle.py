"""Device-resident shuffle: packed partition blocks + shuffle stats.

Reference analogue: the UCX shuffle plugin's device-to-device data path
(RapidsShuffleClient/Server) — map output never round-trips through
host memory on the happy path.  The TPU form: a shuffle write runs ONE
jitted partition-build kernel per input batch that groups rows by
destination partition inside a single flat HBM block (stable sort by
partition id), and records per-partition ``counts``/``starts`` vectors.
The write counts before it packs: a batch whose live rows fit a
smaller power-of-two bucket than it arrived in (a partial aggregate's
four groups in a 2^22-row bucket) is first cut to that bucket by the
trim kernel, so hash, build and every reader's slice run at the size
the data has.
Readers slice their partition out of the resident block with a shared
gather kernel — no d2h, no host CRC, no h2d.  CRC32C stamping moves to
the spill/host boundary: it happens exactly when a block is demoted off
the device tier (``SpillableBuffer.to_host``), which is also where the
``shuffle.hostBytes`` metric accrues.

Layout note: the LOCAL block is the sorted-flat ragged form (block
padded size == the bucket of the input's live rows, never more than
the input's padded size).  The padded ``[n_parts, max_rows]``
tile form lives in ``parallel/exchange.py`` (``bucket_rows`` /
``collective_exchange``) where the fused ``lax.all_to_all`` collective
needs equal-capacity lanes per destination; a local exchange with
``n_out`` readers over one process would pay ``n_out×`` HBM for the
same information the flat block carries in ``1×``.

The kernels register in the process-wide kernel cache keyed by schema
signature, so every exchange of the same layout shares one compiled
trim, build and slice program per row bucket.
"""
from __future__ import annotations

import threading
import time
from contextlib import contextmanager
from typing import Dict

from ..data.column import DeviceBatch, DeviceColumn
from ..ops.kernels.gather import gather_batch
from ..utils.tracing import device_phase


# ==========================================================================
# shuffle counters (process-wide, delta-reported per query like the
# kernel cache: ExecContext marks at query start, the session merges
# ``metrics_since(mark)`` into last_metrics under ``shuffle.*``)
# ==========================================================================
class ShuffleStats:
    #: trimmedBlocks: blocks packed at a smaller bucket than their
    #: input arrived in; trimmedRows: padded rows in - padded rows packed
    _KEYS = ("deviceBytes", "hostBytes", "collectiveTimeNs",
             "numFallbacks", "checkpointBytes", "trimmedBlocks",
             "trimmedRows")

    def __init__(self):
        self._lock = threading.Lock()
        self._values: Dict[str, int] = {k: 0 for k in self._KEYS}

    def reset(self) -> None:
        with self._lock:
            for k in self._KEYS:
                self._values[k] = 0

    def add(self, name: str, v: int = 1) -> None:
        with self._lock:
            self._values[name] = self._values.get(name, 0) + v

    def counters(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._values)

    def metrics_since(self, mark) -> Dict[str, int]:
        """Per-query ``shuffle.*`` metric section: counter deltas since
        ``mark`` (a :meth:`counters` snapshot from ExecContext)."""
        cur = self.counters()
        out = {}
        for k, v in cur.items():
            base = mark.get(k, 0) if mark else 0
            out[f"shuffle.{k}"] = v - base
        return out


#: THE process-wide instance (like kernel_cache.GLOBAL)
GLOBAL = ShuffleStats()


@contextmanager
def collective_timer():
    """Wall-clock a Python-level collective dispatch into
    ``shuffle.collectiveTime`` (trace-time collective calls inside
    shard_map cost nothing per se — the dispatch that launches them is
    what this measures)."""
    t0 = time.perf_counter_ns()
    try:
        yield
    finally:
        GLOBAL.add("collectiveTimeNs", time.perf_counter_ns() - t0)


# ==========================================================================
# packed partition block: build + slice kernel bodies (module level so
# the kernel-cache key — not a per-exec closure — owns the compilation)
# ==========================================================================
@device_phase("shuffle.packedBuild")
def packed_build(batch: DeviceBatch, pids, n_out: int):
    """Group rows by destination partition inside ONE flat device block.

    Stable sort by partition id (padding rows get the sentinel id
    ``n_out`` so every real row lands in front — the spill serializer
    trims to ``num_rows`` and must lose only padding); returns
    ``(block, counts, starts)`` where ``counts[p]``/``starts[p]``
    delimit partition ``p``'s contiguous row range in the block.  The
    contiguousSplit analogue of the reference (Plugin.scala:54-83):
    one sort yields every split at once."""
    import jax.numpy as jnp

    pids = jnp.where(batch.row_mask(), pids, n_out)
    order = jnp.argsort(pids, stable=True).astype(jnp.int32)
    sorted_pids = pids[order]
    bounds = jnp.searchsorted(
        sorted_pids, jnp.arange(n_out + 1, dtype=sorted_pids.dtype))
    counts = (bounds[1:] - bounds[:-1]).astype(jnp.int32)
    starts = bounds[:-1].astype(jnp.int32)
    return gather_batch(batch, order, batch.num_rows), counts, starts


@device_phase("shuffle.packedSlice")
def packed_slice(block: DeviceBatch, start, count) -> DeviceBatch:
    """Slice one partition's contiguous row range out of a packed
    block: a clipped-index gather to the front plus a lane mask.
    ``start``/``count`` are traced scalars, so ONE compiled program
    serves every (partition, block) pair of the same layout."""
    import jax.numpy as jnp

    padded = block.padded_rows
    lane = jnp.arange(padded, dtype=jnp.int32)
    idx = jnp.clip(start + lane, 0, max(padded - 1, 0))
    return gather_batch(block, idx, jnp.asarray(count, dtype=jnp.int32),
                        lane < count)


@device_phase("shuffle.trim")
def trim(batch: DeviceBatch, out_rows: int) -> DeviceBatch:
    """The leading ``out_rows`` rows of every column: data, validity
    and lengths.  A batch's live rows are at the front
    (``DeviceBatch.row_mask``), so with ``out_rows >= num_rows`` only
    padding is cut.  ``out_rows`` is static — a bucket, so the number
    of programs is bounded by the powers of two."""
    cols = [DeviceColumn(
        c.dtype, c.data[:out_rows], c.validity[:out_rows],
        None if c.lengths is None else c.lengths[:out_rows])
        for c in batch.columns]
    return DeviceBatch(batch.schema, cols, batch.num_rows)


def trim_kernel(schema):
    """The jitted trim, named ``jit_shuffle_trim``: one entry per
    schema layout, one program per output bucket (static), found by
    the persistent compile cache — not an eager ``x[:n]`` whose shape
    follows the data."""
    from ..exec.kernel_cache import jit_kernel, schema_signature

    return jit_kernel(
        trim, key=("shuffle.trim", schema_signature(schema)),
        static_argnums=(1,))


def packed_build_kernel(schema, n_out: int):
    """The jitted build kernel, shared across execs via the kernel
    cache (key: schema layout + fan-out; ``n_out`` is static — it
    shapes the counts/starts vectors)."""
    from ..exec.kernel_cache import jit_kernel, schema_signature

    return jit_kernel(
        packed_build,
        key=("shuffle.packedBuild", int(n_out), schema_signature(schema)),
        static_argnums=(2,))


def packed_slice_kernel(schema):
    from ..exec.kernel_cache import jit_kernel, schema_signature

    return jit_kernel(
        packed_slice,
        key=("shuffle.packedSlice", schema_signature(schema)))


def fetch_counts(handles):
    """The ONE gated host readback of the device exchange write path:
    a single batched ``jax.device_get`` of tiny handles — per-block
    syncs would be a device RTT each.  A flush calls it twice: with
    the chunk's input row counts (scalars; one that is already a
    Python int passes through untouched), which decide each block's
    bucket before anything is packed, and then with the packed
    blocks' counts/starts vectors (int32[n_out] pairs).  Named so the
    host-sync analysis rule can gate exactly this function as the
    device path's host materialization point."""
    import jax

    return jax.device_get(list(handles))


def resolve_mode(conf_mode: str, *, force_host: bool = False,
                 headroom: int = 1) -> str:
    """Effective exchange data path for one shuffle write.

    ``device``/``host`` obey the conf; ``auto`` picks device while the
    HBM arena has headroom; a ladder-forced re-execution
    (``force_host``) always stages.  An unknown conf value raises at
    the write, not mid-drain.  Note range partitioning never takes the
    PACKED path even under ``device`` (its placement needs sampled
    bounds that only exist after the full write drain) — it keeps the
    legacy device-resident path, staging only when this returns
    ``host``."""
    mode = (conf_mode or "auto").lower()
    if mode not in ("device", "host", "auto"):
        raise ValueError(
            f"shuffle.mode must be device|host|auto, got {conf_mode!r}")
    if force_host:
        return "host"
    if mode == "auto":
        return "device" if headroom > 0 else "host"
    return mode
