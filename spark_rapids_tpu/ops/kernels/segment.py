"""Segment-reduction kernels — the TPU group-by engine.

The reference lowers group-by to cudf's hash-based groupBy.aggregate
(aggregate.scala:360-388).  Hash tables scatter randomly, which is hostile
to the TPU memory model, so the device implementation here is sort-based:
sort rows by key, flag the key-change boundaries, then reduce each run of
sorted rows — exactly the "sort + segment-reduce" design called out in
SURVEY §7 Hard parts.

Both engines share the same structure: the host (numpy) versions use
argsort + scatters by segment id (``np.add.at``); the device versions use
a stable sort, then scans that restart at the boundaries and one gather
at the segments' last rows (``reduce_sorted``: a scatter-add into as
many bins as rows costs the TPU 0.3 s per column of 2^22 rows), with a
static output size (the row bucket), so shapes stay static.
"""
from __future__ import annotations

from typing import List, Tuple

import numpy as np

from ... import types as T
from ...data.column import DeviceColumn, HostColumn
from ...utils.tracing import READ_TIER, device_phase
from .gather import _SCAN_BLOCK, prefix_sum

# ---------------------------------------------------------------------------
# Host (numpy) engine
# ---------------------------------------------------------------------------


def _null_key_np(col: HostColumn):
    """Sortable key array where nulls order first and floats canonicalize."""
    if col.dtype.is_string:
        data = np.asarray([x if isinstance(x, str) else "" for x in col.data],
                          dtype=object)
    else:
        data = col.data
        if col.dtype.is_floating:
            data = np.where(data == 0.0, data.dtype.type(0.0), data)
    return data, ~col.is_valid()


def _uint64_key_np(col: HostColumn) -> np.ndarray:
    """Order-preserving uint64 encoding of a non-string column
    (floats via sign-magnitude bit flip; NaN > +inf, Spark order)."""
    tid = col.dtype.id
    data = col.data
    if tid is T.TypeId.BOOL:
        return data.astype(np.uint64)
    if col.dtype.is_floating:
        d = data.astype(np.float64)
        d = np.where(d == 0.0, 0.0, d)
        bits = d.view(np.int64)
        flipped = np.where(bits < 0, ~bits, bits ^ np.int64(-2 ** 63))
        u = flipped.view(np.uint64)
        return np.where(np.isnan(d), np.uint64(0xFFFFFFFFFFFFFFFE), u)
    return (data.astype(np.int64) ^ np.int64(-2 ** 63)).view(np.uint64)


def lexsort_np(key_cols: List[HostColumn],
               descending: List[bool] = None,
               nulls_first: List[bool] = None) -> np.ndarray:
    """Stable multi-key argsort; nulls first by default (Spark ASC).
    Same pass structure as the device lexsort so orderings agree."""
    n = key_cols[0].num_rows if key_cols else 0
    if descending is None:
        descending = [False] * len(key_cols)
    if nulls_first is None:
        nulls_first = [True] * len(key_cols)
    passes = []  # passes[0] dominates
    for col, desc, nf in zip(key_cols, descending, nulls_first):
        is_null = ~col.is_valid()
        null_rank = 0 if nf else 1
        passes.append(np.where(is_null, np.uint64(null_rank),
                               np.uint64(1 - null_rank)))
        if col.dtype.is_string:
            s = np.asarray([x if isinstance(x, str) else ""
                            for x in col.data], dtype=object)
            # rank-encode via unique (binary collation of python str
            # matches UTF-8 byte order for the BMP subset we support)
            uniq, inv = np.unique(s.astype(str), return_inverse=True)
            k = inv.astype(np.uint64)
        else:
            k = _uint64_key_np(col)
        if desc:
            k = ~k
        passes.append(np.where(is_null, np.uint64(0), k))
    order = np.arange(n)
    for k in reversed(passes):
        order = order[np.argsort(k[order], kind="stable")]
    return order


def group_segments_np(key_cols: List[HostColumn]
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sort by keys; return (sorted_order, segment_id_per_sorted_row,
    segment_start_indices)."""
    n = key_cols[0].num_rows
    order = lexsort_np(key_cols)
    change = np.zeros(n, dtype=np.bool_)
    if n:
        change[0] = True
    for col in key_cols:
        data, is_null = _null_key_np(col)
        d = data[order]
        nl = is_null[order]
        if n > 1:
            neq = np.zeros(n, dtype=np.bool_)
            # a value difference only matters when BOTH rows are valid —
            # invalid lanes hold arbitrary data
            both_valid = ~nl[1:] & ~nl[:-1]
            if col.dtype.is_string:
                for i in range(1, n):
                    neq[i] = (both_valid[i - 1] and d[i] != d[i - 1]) \
                        or (nl[i] != nl[i - 1])
            else:
                data_neq = (d[1:] != d[:-1]) & both_valid
                if col.dtype.is_floating:
                    both_nan = np.isnan(d[1:].astype(np.float64)) & \
                        np.isnan(d[:-1].astype(np.float64))
                    data_neq &= ~both_nan
                neq[1:] = data_neq | (nl[1:] != nl[:-1])
            change |= neq
    seg_ids = np.cumsum(change) - 1 if n else np.zeros(0, dtype=np.int64)
    seg_starts = np.nonzero(change)[0]
    return order, seg_ids.astype(np.int64), seg_starts


_NP_REDUCE = {
    "sum": np.add,
    "min": np.minimum,
    "max": np.maximum,
}


def segment_pick_np(eligible: np.ndarray, seg_ids: np.ndarray,
                    n_segments: int, op: str):
    """Pick the first/last eligible row index per segment.
    Returns (safe_row_indices, segment_has_eligible_row)."""
    n = len(eligible)
    if n == 0:
        return (np.zeros(n_segments, dtype=np.int64),
                np.zeros(n_segments, dtype=np.bool_))
    idx = np.arange(n)
    big = n + 1
    first = op.startswith("first")
    key = np.where(eligible, idx, big if first else -1)
    pick = np.full(n_segments, big if first else -1, dtype=np.int64)
    red = np.minimum if first else np.maximum
    red.at(pick, seg_ids, key)
    counts = np.zeros(n_segments, dtype=np.int64)
    np.add.at(counts, seg_ids, eligible.astype(np.int64))
    safe = np.clip(pick, 0, max(n - 1, 0)).astype(np.int64)
    return safe, counts > 0


def segment_reduce_np(values: np.ndarray, valid: np.ndarray,
                      seg_ids: np.ndarray, n_segments: int, op: str):
    """Reduce ``values`` per segment, ignoring invalid rows (the *_any
    picks instead consider every row — Spark's ignoreNulls=false first/
    last).  Returns (out_values, out_valid)."""
    counts = np.zeros(n_segments, dtype=np.int64)
    np.add.at(counts, seg_ids, valid.astype(np.int64))
    if op == "count":
        return counts, np.ones(n_segments, dtype=np.bool_)
    if op in ("first", "last", "first_any", "last_any"):
        if len(values) == 0:
            out = np.empty(n_segments, dtype=object) \
                if values.dtype == object \
                else np.zeros(n_segments, dtype=values.dtype)
            return out, np.zeros(n_segments, dtype=np.bool_)
        if op in ("first", "last"):
            safe, ok = segment_pick_np(valid, seg_ids, n_segments, op)
            return values[safe], ok
        present = np.ones(len(values), dtype=np.bool_)
        safe, ok = segment_pick_np(present, seg_ids, n_segments, op)
        return values[safe], ok & valid[safe]
    if op == "sum":
        if values.dtype == object:
            raise TypeError("sum of strings")
        acc_t = np.float64 if np.issubdtype(values.dtype, np.floating) \
            else np.int64
        acc = np.zeros(n_segments, dtype=acc_t)
        # long sums wrap on overflow (Spark semantics) and float sums may
        # hit inf-inf: both are intended, not numeric accidents
        with np.errstate(over="ignore", invalid="ignore"):
            np.add.at(acc, seg_ids,
                      np.where(valid, values, 0).astype(acc_t))
        return acc, counts > 0
    if op in ("min", "max"):
        if values.dtype == object:  # strings: python reduce per segment
            out = np.empty(n_segments, dtype=object)
            ok = counts > 0
            fn = min if op == "min" else max
            for s in range(n_segments):
                vals = [v for v, vl in zip(values[seg_ids == s],
                                           valid[seg_ids == s]) if vl]
                out[s] = fn(vals) if vals else None
            return out, ok
        if np.issubdtype(values.dtype, np.floating):
            init = np.inf if op == "min" else -np.inf
            acc = np.full(n_segments, init, dtype=values.dtype)
            fill = init
        else:
            info = np.iinfo(values.dtype)
            fill = info.max if op == "min" else info.min
            acc = np.full(n_segments, fill, dtype=values.dtype)
        red = _NP_REDUCE[op]
        with np.errstate(invalid="ignore"):
            red.at(acc, seg_ids, np.where(valid, values,
                                          values.dtype.type(fill)))
        return acc, counts > 0
    raise ValueError(op)


# ---------------------------------------------------------------------------
# Device (jnp) engine
# ---------------------------------------------------------------------------
def _ordered_u32(x):
    """Order-preserving uint32 image of an int32 array (sign flip)."""
    import jax.numpy as jnp

    return (x ^ jnp.int32(-2 ** 31)).view(jnp.uint32)


def _float32_image(d):
    """Order-preserving uint32 image of float32 values (sign-magnitude
    bit flip; -0.0 and 0.0 share one image)."""
    import jax.numpy as jnp

    d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
    bits = d.view(jnp.int32)
    return jnp.where(bits < 0, ~bits,
                     bits ^ jnp.int32(-2 ** 31)).view(jnp.uint32)


def float64_words_ieee(d):
    """(hi, lo) uint32 words of the order-preserving image of IEEE
    doubles — the device twin of ``_uint64_key_np``, for backends that
    hold a float64 as its 64 IEEE bits."""
    import jax.numpy as jnp

    bits = d.view(jnp.int64)
    flipped = jnp.where(bits < 0, ~bits, bits ^ jnp.int64(-2 ** 63))
    return ((flipped >> 32).astype(jnp.uint32),
            flipped.astype(jnp.uint32))


def float64_words_pair(d):
    """(hi, lo) uint32 words ordering float64 values on a backend that
    holds them as an unevaluated sum of two float32 (the TPU: its
    compiler refuses every bitcast OUT of f64).  ``hi`` is the value
    rounded to float32 — monotone in the value — and ``lo`` the exact
    remainder, so comparing (hi, lo) lexicographically compares the
    values."""
    import jax.numpy as jnp

    hi = d.astype(jnp.float32)
    lo = jnp.where(jnp.isfinite(hi), d - hi.astype(jnp.float64),
                   0.0).astype(jnp.float32)
    return _float32_image(hi), _float32_image(lo)


def _float64_words(d):
    import jax

    if jax.default_backend() == "tpu":
        return float64_words_pair(d)
    return float64_words_ieee(d)


def _sort_key_device(col: DeviceColumn, desc: bool):
    """Orderable key fields of one non-string device column:
    ``[(uint32 array, nbits)]``, most significant first — comparing
    the fields lexicographically as unsigned integers compares the
    values in Spark's order (NaN above every double, -0.0 == 0.0).
    ``desc`` inverts every field; null rows get zeros (their placement
    is the dominating null-rank field ``key_passes_device`` adds)."""
    import jax.numpy as jnp

    tid = col.dtype.id
    data = col.data
    if tid is T.TypeId.BOOL:
        fields = [(data.astype(jnp.uint32), 1)]
    elif tid is T.TypeId.FLOAT64:
        d = data.astype(jnp.float64)
        d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
        hi, lo = _float64_words(d)
        # NaN sorts last among valids (Spark: NaN > all doubles)
        nan = jnp.isnan(d)
        fields = [(jnp.where(nan, jnp.uint32(0xFFFFFFFF), hi), 32),
                  (jnp.where(nan, jnp.uint32(0xFFFFFFFE), lo), 32)]
    elif col.dtype.is_floating:
        d = data.astype(jnp.float32)
        fields = [(jnp.where(jnp.isnan(d), jnp.uint32(0xFFFFFFFE),
                             _float32_image(d)), 32)]
    elif data.dtype.itemsize == 8:
        x = data.astype(jnp.int64)
        fields = [(_ordered_u32((x >> 32).astype(jnp.int32)), 32),
                  (x.astype(jnp.uint32), 32)]
    else:
        nbits = 8 * data.dtype.itemsize
        x = _ordered_u32(data.astype(jnp.int32))
        if nbits < 32:  # int8/int16: the image fits the narrow field
            x = x - jnp.uint32(2 ** 31 - 2 ** (nbits - 1))
        fields = [(x, nbits)]
    out = []
    for f, nbits in fields:
        if desc:
            f = ~f if nbits == 32 else f ^ jnp.uint32(2 ** nbits - 1)
        out.append((jnp.where(col.validity, f, jnp.uint32(0)), nbits))
    return out


def _pack_fields(fields):
    """Greedily pack ``[(uint32 array, nbits)]`` key fields, most
    significant first, into as few uint32 words as hold them — the
    order over the words equals the order over the fields, and every
    word less is a sort pass less."""
    import jax.numpy as jnp

    words = []
    cur, used = None, 0
    for f, nbits in fields:
        if cur is not None and used + nbits <= 32:
            cur = (cur << jnp.uint32(nbits)) | f
            used += nbits
        else:
            if cur is not None:
                words.append(cur)
            cur, used = f, nbits
    if cur is not None:
        words.append(cur)
    return words


def key_passes_device(key_cols: List[DeviceColumn],
                      descending: List[bool] = None,
                      nulls_first: List[bool] = None,
                      pad_valid=None):
    """Order-preserving uint32 word encoding of multi-column sort keys:
    comparing rows lexicographically over the words (words[0]
    dominates) == comparing them under the sort order, with desc /
    null-placement baked into the encoding and rows where ``pad_valid``
    is False after every other row.  Shared by the lexsort and the
    device range partitioner (sampled bounds compare)."""
    import jax.numpy as jnp

    if descending is None:
        descending = [False] * len(key_cols)
    if nulls_first is None:
        nulls_first = [True] * len(key_cols)
    fields = []  # (uint32 array, nbits); fields[0] dominates
    if pad_valid is not None:
        fields.append((jnp.where(pad_valid, jnp.uint32(0),
                                 jnp.uint32(1)), 1))
    for col, desc, nf in zip(key_cols, descending, nulls_first):
        # null placement dominates this column's value fields
        null_rank = jnp.uint32(0 if nf else 1)
        fields.append((jnp.where(col.validity, jnp.uint32(1) - null_rank,
                                 null_rank), 1))
        if col.dtype.is_string:
            flip = jnp.uint32(0xFF if desc else 0)
            for b in range(col.data.shape[1]):  # one byte per field
                k = col.data[:, b].astype(jnp.uint32) ^ flip
                fields.append((jnp.where(col.validity, k,
                                         jnp.uint32(0)), 8))
        else:
            fields.extend(_sort_key_device(col, desc))
    return _pack_fields(fields)


def lexsort_device(key_cols: List[DeviceColumn],
                   descending: List[bool] = None,
                   nulls_first: List[bool] = None,
                   pad_valid=None):
    """Stable multi-key argsort on device.  Padding rows (pad_valid False)
    always sort last.  Returns int32 permutation."""
    n = key_cols[0].data.shape[0] if key_cols else pad_valid.shape[0]
    return sort_permutation(
        key_passes_device(key_cols, descending, nulls_first, pad_valid), n)


def sort_permutation(words, n: int):
    """int32 permutation ordering rows lexicographically by the uint32
    ``words`` (words[0] dominates), stable.

    A least-significant-word-first chain of stable single-key sorts,
    written as a loop over the stacked words so the program holds the
    same one-key sort however many words the key has.  The TPU
    compiler's time for a sort grows steeply with the comparator's key
    count (2^20 rows for v5e: one 32-bit key 21 s, two 47 s, the six
    that three 64-bit passes come to 243 s; this loop over six words
    30 s), and a cold query holds dozens of sorts."""
    import jax.numpy as jnp
    from jax import lax

    iota = jnp.arange(n, dtype=jnp.int32)
    if not words:
        return iota

    def sort_by(key, perm):
        return lax.sort((key, perm), dimension=0, is_stable=True,
                        num_keys=1)[1]

    with device_phase("lexsort"):
        # the least significant word sorts outside the loop: under
        # shard_map the carry must start as shard-varying as it ends
        order = sort_by(words[-1], iota)
        if len(words) == 1:
            return order
        stacked = jnp.stack(words[:-1])
        last = len(words) - 2
        return lax.fori_loop(
            0, len(words) - 1,
            lambda i, perm: sort_by(stacked[last - i][perm], perm), order)


@device_phase("segments")
def segment_change_device(sorted_keys: List[DeviceColumn], pad_valid=None):
    """Given key columns already in sorted order, the bool flags of the
    rows that start a segment: row 0, every key change, and every
    padding row (each its own trailing segment beyond the real ones)."""
    import jax.numpy as jnp

    n = sorted_keys[0].data.shape[0] if sorted_keys else (
        pad_valid.shape[0] if pad_valid is not None else 0)
    change = jnp.zeros((n,), dtype=jnp.bool_).at[0].set(True)
    for col in sorted_keys:
        v = col.validity
        # a value difference only matters when BOTH rows are valid —
        # computed key columns carry arbitrary data in invalid lanes
        bv = jnp.zeros((n,), dtype=jnp.bool_).at[1:].set(v[1:] & v[:-1])
        if col.dtype.is_string:
            d = col.data
            neq = jnp.zeros((n,), dtype=jnp.bool_)
            neq = neq.at[1:].set(
                (((d[1:] != d[:-1]).any(axis=1)
                  | (col.lengths[1:] != col.lengths[:-1])) & bv[1:])
                | (v[1:] != v[:-1]))
        else:
            d = col.data
            if col.dtype.is_floating:
                d = jnp.where(d == 0.0, jnp.zeros_like(d), d)
                both_nan = jnp.zeros((n,), dtype=jnp.bool_)
                both_nan = both_nan.at[1:].set(jnp.isnan(d[1:])
                                               & jnp.isnan(d[:-1]))
                neq = jnp.zeros((n,), dtype=jnp.bool_)
                neq = neq.at[1:].set(
                    ((d[1:] != d[:-1]) & ~both_nan[1:] & bv[1:])
                    | (v[1:] != v[:-1]))
            else:
                neq = jnp.zeros((n,), dtype=jnp.bool_)
                neq = neq.at[1:].set(((d[1:] != d[:-1]) & bv[1:])
                                     | (v[1:] != v[:-1]))
        change = change | neq
    if pad_valid is not None:
        # every padding row becomes its own segment so it never merges
        change = change | ~pad_valid
    return change


def segment_ids_device(sorted_keys: List[DeviceColumn], pad_valid=None):
    """int32 segment ids of rows in sorted key order: the running count
    of ``segment_change_device``'s flags."""
    import jax.numpy as jnp

    change = segment_change_device(sorted_keys, pad_valid)
    with device_phase("segments"):
        return prefix_sum(change.astype(jnp.int32)) - 1


@device_phase("segments")
def segmented_scan(x, change, op):
    """Inclusive scan of ``x`` ([k, n]) along its rows by the
    associative ``op``, restarting at every row ``change`` (bool[n])
    flags and at row 0: a segment's total stands at its last row.

    Two levels like ``prefix_sum``, and its block: blocks of
    ``_SCAN_BLOCK`` rows lie side by side and one loop walks down all of
    them at once, a row a step; then the blocks' last values are scanned
    the same way and carried into the rows their block's first restart
    has not cut off.  So a segment that crosses a block edge adds up
    block by block: a float sum equals the row-by-row one to rounding,
    not to the bit.  The ``k`` operands go through the program as one.
    (2^22 rows, 7 float64 on a v5e: 11.3 ms, where a log-step
    ``associative_scan`` took 38 and the scatter-add of one float64
    column 308.)  ``prefix_sum`` stays a ``cumsum``: it has no restarts
    to honour, and the counts need none (``reduce_sorted``)."""
    return scan_restarting(x, change, op)


def scan_restarting(x, change, op):
    """``segmented_scan`` under no scope of its own: for a caller whose
    phase the scan is part of (``join.py:pair_rows`` carries a left
    row's values to its pairs with it)."""
    import jax.numpy as jnp
    from jax import lax

    k, n = x.shape
    block = min(n, _SCAN_BLOCK)
    pad = -n % block
    if pad:                 # rows past the end, each a segment of its own
        x = jnp.pad(x, ((0, 0), (0, pad)))
        change = jnp.pad(change, (0, pad), constant_values=True)
    nb = (n + pad) // block
    rows = x.reshape(k, nb, block).transpose(2, 0, 1)
    flags = change.at[0].set(True).reshape(1, nb, block).transpose(2, 0, 1)

    def step(carry, row):
        flag, top, value = row      # top: a block's first row adds to nothing
        cut = carry[0] | flag
        acc = jnp.where(flag | top, value, op(carry[1], value))
        return (cut, acc), (cut, acc)

    # the carry starts from the inputs: under shard_map it must start as
    # shard-varying as it ends
    top = (jnp.arange(block) == 0)[:, None, None]
    (cut_last, last), (cut, inner) = lax.scan(
        step, (flags[0] & False, rows[0]), (flags, top, rows))
    if nb > 1:
        carry = scan_restarting(last, cut_last[0], op)
        carry = jnp.concatenate([carry[:, :1], carry[:, :-1]], axis=1)
        inner = jnp.where(cut, inner, op(carry, inner))
    return inner.transpose(1, 2, 0).reshape(k, n + pad)[:, :n]


_SCAN_OPS = {"sum": "add", "min": "minimum", "max": "maximum",
             "first": "minimum", "last": "maximum"}

#: below this many rows ``reduce_sorted`` reads every slot whatever the
#: segment count: a gather of 2^13 indices is a quarter of a millisecond
#: (30 ns an index from HBM) and the call its fixed latencies (q1's specs
#: on a v5e, plain against tiered: 1.84 / 1.66 ms at 2^13 rows, 2.43 /
#: 1.70 at 2^14, 6.84 / 2.01 at 2^16), so a branch has nothing to save
#: there, and the small buckets (a trimmed exchange's 128 rows, a test's
#: batch) keep one read to compile where the tiers are four
_TIER_FLOOR = 1 << 14


def read_tiers(n: int):
    """The read widths a ``reduce_sorted`` over ``n`` rows chooses from
    when its segment count is known on the device alone: ``n/64``,
    ``n/16``, ``n/4`` and ``n`` (the plain read, the fallback that is
    always right), a function of the static ``n``; ``(n,)`` below
    ``_TIER_FLOOR``."""
    if n < _TIER_FLOOR:
        return (n,)
    return (n // 64, n // 16, n // 4, n)


@device_phase("segments")
def reduce_sorted(change, order, specs, segments=None):
    """Per-segment reductions of a batch whose rows ``order`` (an int32
    permutation from a STABLE sort; None: as they stand) brings into
    contiguous segments, ``change`` (bool[n]) flagging each segment's
    first sorted row as ``segment_change_device`` gives it.  ``specs``
    lists (column, op), the column over the batch's rows as they stand;
    the answer lists (data, valid, lengths) of ``n`` rows each, segment
    ``j``'s in row ``j`` (rows past the last segment hold nothing of
    meaning): the device analogue of ``segment_reduce_np`` /
    ``segment_pick_np``.

    ``segments`` says how many leading segments the caller reads; the
    rows at and past it are the caller's to mask, and come back as zeros
    wherever they were not read.  None: all ``n`` slots are read (a
    gather of ``n`` indices a stack; 2^22 rows on a v5e: 30-124 ms
    each).  A static int (a keyless aggregate: 1): only that many ends
    are read.  A traced int32 scalar (a keyed aggregate's group count,
    known on the device alone): the reads stand in a ``lax.switch`` over
    ``read_tiers(n)`` and the smallest width that holds ``segments`` is
    the one that runs, so q1's 4 groups in a 2^22-row bucket cost 2^16
    indices a gather and not 2^22; the rows below ``segments`` are read
    by the same indices from the same arrays as the plain read's, equal
    to the bit.  The sort of the ends, the scans and the prefix sum are
    outside the switch: every branch reads their whole arrays.

    Nothing scatters.  The segments' first and last rows come from one
    sort; sums, minima and maxima from a segmented scan read at the last
    rows; counts from a prefix sum differenced there (whole numbers:
    exact); ``first`` / ``last`` and a string's extreme are the batch row
    a minimum or maximum names.  The operands of one (op, dtype) are
    stacked before they are sorted, scanned and read, because a gather
    of a stack costs a tenth of a gather a column (16 float32 of 2^22
    rows on a v5e: 138 ms against 1429)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    if not specs:
        return []
    n = change.shape[0]
    # one width where the count is static or absent, or the batch small
    tiers = (n,) if segments is None or isinstance(segments, int) \
        else read_tiers(n)
    idx = jnp.arange(n, dtype=jnp.int32)
    last = jnp.concatenate([change[1:], jnp.ones((1,), jnp.bool_)])
    # the sorted rows that end a segment, in order, then n's: a one-key
    # sort runs in a quarter of a compaction's scatter
    ends = jnp.minimum(lax.sort(jnp.where(last, idx, n)), n - 1)

    def starts_of(ends):    # of the leading segments whose ends these are
        return jnp.minimum(jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), ends[:-1] + 1]), n - 1)

    if len(tiers) == 1:
        starts = starts_of(ends)
        if isinstance(segments, int):
            ends, starts = ends[:segments], starts[:segments]

    def in_order(stack):
        if order is None:
            return stack
        with device_phase("reorder"):
            return stack[:, order]

    # ----- what each spec has the scans and the counts do --------------
    stacks, flags, slots = {}, [], []
    for col, op in specs:
        x = by_value = None
        if col.dtype.is_string and op in ("min", "max"):
            # rank encoding: the extreme rank names the winning row
            by_value = lexsort_device([col], pad_valid=col.validity)
            x = sort_permutation([by_value.astype(jnp.uint32)], n)
        elif op in ("first", "last"):
            x = idx         # the sort is stable: first is lowest
        elif op in ("sum", "min", "max"):
            x = col.data
            if op == "sum":
                x = x.astype(jnp.float64 if jnp.issubdtype(
                    x.dtype, jnp.floating) else jnp.int64)
        elif op not in ("count", "first_any", "last_any"):
            raise ValueError(op)
        slot = None
        if x is not None:       # invalid rows hold the scan's identity
            key = _SCAN_OPS[op], x.dtype
            if op == "sum":
                fill = 0
            elif jnp.issubdtype(x.dtype, jnp.floating):
                fill = jnp.inf if key[0] == "minimum" else -jnp.inf
            else:
                info = jnp.iinfo(x.dtype)
                fill = info.max if key[0] == "minimum" else info.min
            rows = stacks.setdefault(key, [])
            rows.append(jnp.where(col.validity, x,
                                  jnp.asarray(fill, x.dtype)))
            slot = key, len(rows) - 1
        # one count serves every spec over the same validity
        at = None if op.endswith("_any") else next(
            (i for i, f in enumerate(flags) if f is col.validity),
            len(flags))
        if at == len(flags):
            flags.append(col.validity)
        slots.append((slot, at, by_value))

    # ----- one scan a stack, running to every row -----------------------
    def scans(read):
        totals = {key: read(segmented_scan(in_order(jnp.stack(rows)),
                                           change, getattr(jnp, key[0])))
                  for key, rows in stacks.items()}
        upto = read(prefix_sum(in_order(jnp.stack(flags)).astype(
            jnp.int32))) if flags else None
        return totals, upto

    # ----- one gather a stack, and each spec's rows ----------------------
    def read_rows(ends, starts, totals, upto):
        """The ``m = len(ends)`` leading segments' rows, then zeros up to
        ``n``; ``totals`` and ``upto`` as read at ``ends``."""
        m = ends.shape[0]
        if upto is not None:
            counts = upto - jnp.concatenate(
                [jnp.zeros_like(upto[:, :1]), upto[:, :-1]], axis=1)
        out = []
        for (col, op), (slot, at, by_value) in zip(specs, slots):
            if op == "count":
                out.append((counts[at].astype(jnp.int64),
                            jnp.ones((m,), jnp.bool_), None))
                continue
            if op.endswith("_any"):     # every segment has a first row
                row = starts if op == "first_any" else ends
                row = row if order is None else order[row]
                has = col.validity[row]
            else:
                has = counts[at] > 0
                total = totals[slot[0]][slot[1]]
                if by_value is None and op in ("sum", "min", "max"):
                    out.append((total, has, None))
                    continue
                row = jnp.clip(total, 0, n - 1)
                row = row if by_value is None else by_value[row]
            out.append((col.data[row], has,
                        None if col.lengths is None else col.lengths[row]))
        if m < n:

            def whole(x):
                return None if x is None else jnp.pad(
                    x, [(0, n - m)] + [(0, 0)] * (x.ndim - 1))

            out = [tuple(whole(x) for x in triple) for triple in out]
        return out

    if len(tiers) == 1:
        return read_rows(ends, starts, *scans(lambda x: x[:, ends]))

    # ----- the segment count picks the read's width on the device --------
    scanned, upto = scans(lambda x: x)

    def tier(m):
        def read():
            with jax.named_scope(f"{READ_TIER}{m}"):
                e = ends[:m]
                return read_rows(
                    e, starts_of(e), {k: x[:, e] for k, x in scanned.items()},
                    None if upto is None else upto[:, e])

        return read

    segments = jnp.asarray(segments, jnp.int32)
    # the smallest tier that holds them (the last holds any count)
    width = sum((segments > m).astype(jnp.int32) for m in tiers[:-1])
    return lax.switch(width, [tier(m) for m in tiers])
