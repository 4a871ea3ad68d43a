"""Runtime stage statistics — the AQE input side.

``StageStats`` lives on the ``ExecContext`` and aggregates what the
exchange write drain ALREADY knows once a stage materializes:

* device path — the per-partition count vectors of every packed block,
  pulled to the host by the second of a flush's two gated
  ``fetch_counts`` batch readbacks (``exec/exchange.py:flush``; the
  first reads the inputs' row counts, which size the blocks).
  Summing them gives the exact per-partition row histogram of the
  exchange, per-item so a skewed partition can later be cut into
  contiguous sub-slices.
* host path — per-batch row counts from the same gated readback
  (round-robin placement has no per-partition vector; totals only,
  except the trivial single-partition case).
* bytes — the arena-accounting byte sizes the write path tracks per
  block for spill bookkeeping (metadata math, no device touch): what
  was packed, not the padding the input arrived in.

Everything in here is host-side numpy on numbers that were already
host-resident: this module MUST NOT import jax or call any host-sync
primitive — the ``jax-import`` and ``host-sync`` analysis rules
enforce both, which is
how "zero added device syncs on the shuffle write path" stays true as
the code evolves.
"""
from __future__ import annotations

import itertools
import threading
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: a contiguous chunk of one write-item's rows belonging to one
#: partition: (item_index, row_lo, row_hi) — half-open, item-local
Segment = Tuple[int, int, int]


class ExchangeObservation:
    """What one drained exchange looked like, exactly."""

    __slots__ = ("exchange_id", "n_out", "device_path", "partitioning",
                 "name", "total_bytes", "total_rows", "part_rows",
                 "item_counts")

    def __init__(self, exchange_id: int, *, n_out: int, device_path: bool,
                 partitioning: str, name: str, total_bytes: int,
                 total_rows: int,
                 part_rows: Optional[np.ndarray],
                 item_counts: Optional[List[np.ndarray]]):
        self.exchange_id = exchange_id
        self.n_out = n_out
        self.device_path = device_path
        self.partitioning = partitioning
        self.name = name
        self.total_bytes = int(total_bytes)
        self.total_rows = int(total_rows)
        self.part_rows = part_rows
        self.item_counts = item_counts

    # ------------------------------------------------------------------
    @property
    def has_partition_rows(self) -> bool:
        return self.part_rows is not None and len(self.part_rows) > 0

    def rows_for(self, p: int) -> int:
        assert self.part_rows is not None
        return int(self.part_rows[p])

    def bytes_for(self, p: int) -> int:
        """Per-partition byte estimate: total bytes prorated by rows
        (columns are fixed-width on device, so this is near-exact)."""
        if not self.has_partition_rows or self.total_rows <= 0:
            return 0
        return int(round(self.total_bytes
                         * (int(self.part_rows[p]) / self.total_rows)))

    def histogram(self) -> Optional[Dict[str, int]]:
        """min/p50/max/skew of the partition row counts, all ints so
        they can ride the metrics registry and the Prometheus export."""
        if not self.has_partition_rows:
            return None
        rows = self.part_rows
        med = int(np.median(rows))
        mx = int(rows.max())
        return {
            "partitions": int(len(rows)),
            "min": int(rows.min()),
            "p50": med,
            "max": mx,
            # skew factor as an integer percentage of the median
            "skewPct": int(round(100.0 * mx / max(med, 1))),
        }


class StageStats:
    """Per-query accumulator of :class:`ExchangeObservation`.

    Re-recording an exchange id OVERWRITES the previous observation:
    a stage re-executed from lineage (task retry, corruption recovery)
    re-plans from the fresh drain's numbers, never stale ones.
    """

    def __init__(self):
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._obs: Dict[int, ExchangeObservation] = {}

    # ------------------------------------------------------------------
    def allocate_id(self) -> int:
        return next(self._ids)

    def record_exchange(self, exchange_id: int, *, items: Sequence,
                        n_out: int, device_path: bool, total_bytes: int,
                        partitioning: str,
                        name: str = "TpuShuffleExchangeExec"
                        ) -> ExchangeObservation:
        """Aggregate one drained exchange's write items.

        ``items`` is the drain's host-resident store: device path
        ``(buf_id, counts, starts)`` per packed block, host path
        ``(buf_id, rr_start, num_rows)`` per staged batch.  All numbers
        were materialized by the drain's gated readback already — this
        is pure host arithmetic.
        """
        part_rows: Optional[np.ndarray] = None
        item_counts: Optional[List[np.ndarray]] = None
        if device_path:
            item_counts = [np.asarray(it[1], dtype=np.int64)[:n_out]
                           for it in items]
            part_rows = np.zeros(n_out, dtype=np.int64)
            for c in item_counts:
                part_rows += c
            total_rows = int(part_rows.sum())
        else:
            total_rows = int(sum(int(it[2]) for it in items
                                 if len(it) > 2))
            if n_out == 1:
                # single-partition host exchange: the histogram is
                # trivially exact even without per-partition vectors
                part_rows = np.asarray([total_rows], dtype=np.int64)
        obs = ExchangeObservation(
            exchange_id, n_out=n_out, device_path=device_path,
            partitioning=partitioning, name=name,
            total_bytes=int(total_bytes), total_rows=total_rows,
            part_rows=part_rows, item_counts=item_counts)
        with self._lock:
            self._obs[exchange_id] = obs
        return obs

    def record_resumed(self, exchange_id: int, *,
                       n_out: int, part_rows: Sequence[int],
                       total_bytes: int, partitioning: str,
                       name: str) -> ExchangeObservation:
        """A checkpoint-RESUMED exchange (recovery/): per-partition rows
        come exactly from the checkpoint manifest, not a drain.  There
        are no live packed blocks, so ``device_path`` is False and
        ``item_counts`` is None — the skew-split rewrite (which needs
        segment reads over resident device blocks) correctly sees this
        stage as unsplittable, while coalescing, broadcast conversion
        and reservation re-basing get real sizes."""
        rows = np.asarray([int(r) for r in part_rows], dtype=np.int64)
        obs = ExchangeObservation(
            exchange_id, n_out=n_out, device_path=False,
            partitioning=partitioning, name=name,
            total_bytes=int(total_bytes),
            total_rows=int(rows.sum()) if rows.size else 0,
            part_rows=rows if rows.size else None,
            item_counts=None)
        with self._lock:
            self._obs[exchange_id] = obs
        return obs

    # ------------------------------------------------------------------
    def get(self, exchange_id: int) -> Optional[ExchangeObservation]:
        with self._lock:
            return self._obs.get(exchange_id)

    def exchanges(self) -> List[ExchangeObservation]:
        with self._lock:
            return [self._obs[k] for k in sorted(self._obs)]

    def observed_peak_bytes(self) -> int:
        """Largest materialized stage output seen so far — the basis
        for re-basing the scheduler's per-query HBM reservation."""
        with self._lock:
            return max((o.total_bytes for o in self._obs.values()),
                       default=0)

    def metrics(self) -> Dict[str, int]:
        """Flat int metrics merged into ``Session.last_metrics`` (and
        thereby the Prometheus export) — surfaced even with
        ``adaptive.enabled=false`` so skew is always visible."""
        out: Dict[str, int] = {}
        for obs in self.exchanges():
            pfx = f"shuffle.exchange{obs.exchange_id}."
            out[pfx + "partitions"] = obs.n_out
            out[pfx + "rowsTotal"] = obs.total_rows
            out[pfx + "bytesTotal"] = obs.total_bytes
            h = obs.histogram()
            if h is not None:
                out[pfx + "partRowsMin"] = h["min"]
                out[pfx + "partRowsP50"] = h["p50"]
                out[pfx + "partRowsMax"] = h["max"]
                out[pfx + "skewPct"] = h["skewPct"]
        return out


# --------------------------------------------------------------------------
# Pure helpers the AdaptivePlanner computes its rewrites with
# --------------------------------------------------------------------------
def coalesce_groups(part_bytes: Sequence[int],
                    target_bytes: int) -> List[Tuple[int, ...]]:
    """Greedily merge ADJACENT partitions up to ``target_bytes`` —
    Spark's ShufflePartitionsUtil rule.  Adjacency preserves the
    partition order, so downstream concatenation order is exactly the
    non-adaptive order.  A partition already over target stays alone."""
    groups: List[Tuple[int, ...]] = []
    cur: List[int] = []
    cur_b = 0
    for p, b in enumerate(part_bytes):
        if cur and cur_b + int(b) > target_bytes:
            groups.append(tuple(cur))
            cur, cur_b = [], 0
        cur.append(p)
        cur_b += int(b)
    if cur:
        groups.append(tuple(cur))
    return groups


def split_partition_segments(item_counts: Sequence[np.ndarray], p: int,
                             n_slices: int) -> List[List[Segment]]:
    """Cut partition ``p``'s (item, row) sequence into ``n_slices``
    contiguous row-balanced slices.

    Each slice is a list of ``(item_idx, row_lo, row_hi)`` segments;
    concatenating the slices in order reproduces the partition's exact
    row sequence, which is what keeps a skew split bit-identical to
    reading the whole partition.
    """
    per_item = [int(c[p]) for c in item_counts]
    total = sum(per_item)
    if total <= 0 or n_slices <= 1:
        segs = [(i, 0, n) for i, n in enumerate(per_item) if n > 0]
        return [segs] if segs else []
    cuts = [int(round(j * total / n_slices))
            for j in range(1, n_slices)]
    bounds = [0] + cuts + [total]
    slices: List[List[Segment]] = []
    for j in range(n_slices):
        lo_g, hi_g = bounds[j], bounds[j + 1]
        if hi_g <= lo_g:
            continue  # degenerate cut (tiny partition, many slices)
        segs: List[Segment] = []
        base = 0
        for i, n in enumerate(per_item):
            a, b = max(lo_g, base), min(hi_g, base + n)
            if b > a:
                segs.append((i, a - base, b - base))
            base += n
        if segs:
            slices.append(segs)
    return slices
