"""Device equi-join kernels: sort-merge with static-shape expansion.

Reference analogue: GpuHashJoin.scala:71-140 (cudf hash-join calls) —
but where cudf scatters into hash tables, the TPU-friendly frontier is
sort-based (SURVEY §7 "Hard parts": hash join on TPU → sort + merge;
the reference replaces SortMergeJoin with hash join, here the
replacement is reversed).  Three stages, all static shapes:

  1. group ids: concat both sides' key columns, one lexsort, segment
     ids at key-change boundaries → per-row int32 ids where equal keys
     (with Spark null/NaN/-0.0 semantics) share an id across sides.
  2. probe: the same sort, split by side, is the right rows in id
     order; per left row, searchsorted gives the contiguous run
     [lo, lo+cnt) of its matches.  Match counts are
     exact before any expansion — the same "size before materialize"
     contract cudf's join APIs give the reference.
  3. expand: with an output capacity chosen from the exact count, a
     searchsorted over the emit-prefix-sum turns slot t into its
     (left row, k-th match) pair; gathers materialize the output.

The only host sync is reading the match count to pick the output's
power-of-two bucket (the same sync point the reference has when cudf
returns the join output size).
"""
from __future__ import annotations

from typing import List, NamedTuple

from ...data.column import DeviceColumn
from . import segment as seg
from .gather import partition_order, prefix_sum


def _concat_key_cols(lc: DeviceColumn, rc: DeviceColumn) -> DeviceColumn:
    """Row-concat one key column from each side (strings pad to the
    wider byte matrix)."""
    import jax.numpy as jnp

    if lc.dtype.is_string:
        w = max(lc.data.shape[1], rc.data.shape[1])

        def widen(d):
            return jnp.pad(d, ((0, 0), (0, w - d.shape[1]))) \
                if d.shape[1] < w else d

        data = jnp.concatenate([widen(lc.data), widen(rc.data)], axis=0)
        lengths = jnp.concatenate([lc.lengths, rc.lengths])
    else:
        data = jnp.concatenate([lc.data, rc.data])
        lengths = None
    validity = jnp.concatenate([lc.validity, rc.validity])
    return DeviceColumn(lc.dtype, data, validity, lengths)


#: id of a row that never joins (null key / padding) in the id-sorted
#: views — above every real id, so the views stay ascending
_NEVER = 2 ** 31 - 1


class Probe(NamedTuple):
    gl: object       # int32[Nl] left group ids (-1 = never matches)
    gr: object       # int32[Nr]
    order_r: object  # int32[Nr] right rows sorted by group id
    lo: object       # int32[Nl] first match position in order_r
    cnt: object      # int32[Nl] number of right matches per left row
    has_r: object    # bool[Nr] right row has a left match


def probe(l_keys: List[DeviceColumn], r_keys: List[DeviceColumn],
          l_ok, r_ok) -> Probe:
    """Group ids and match runs from ONE sort of both sides' keys.

    Rows (on either side) with equal, fully-non-null keys share a group
    id; left rows with null keys/padding get -1, right ones -2 —
    sentinels that never match anything.  The combined sort already
    holds each side in id order, so the right rows sorted by id (and
    the sorted ids of each side, for the run searches) are a stable
    split of it by side — a prefix sum and a scatter — not two more
    sorts (each sort in a program costs the TPU compiler ~30 s)."""
    import jax.numpy as jnp

    nl, nr = l_ok.shape[0], r_ok.shape[0]
    combined = [_concat_key_cols(a, b) for a, b in zip(l_keys, r_keys)]
    ok = jnp.concatenate([l_ok, r_ok])
    # null keys never join: fold key validity into row eligibility
    for c in combined:
        ok = ok & c.validity
    order = seg.lexsort_device(combined, pad_valid=ok)
    ok_s = ok[order]
    sorted_cols = [DeviceColumn(c.dtype, c.data[order],
                                c.validity[order] & ok_s,
                                c.lengths[order]
                                if c.lengths is not None else None)
                   for c in combined]
    ids_s = seg.segment_ids_device(sorted_cols, pad_valid=ok_s)
    ids = jnp.zeros((nl + nr,), dtype=jnp.int32).at[order].set(ids_s)
    gl = jnp.where(ok[:nl], ids[:nl], -1)
    gr = jnp.where(ok[nl:], ids[nl:], -2)

    # sorted positions of the right rows, then of the left rows, each
    # still in id order (never-joining rows last)
    by_side = partition_order(order >= nl)
    pos_r, pos_l = by_side[:nr], by_side[nr:]
    ids_s = jnp.where(ok_s, ids_s, _NEVER)
    order_r = order[pos_r] - nl
    sorted_gr, sorted_gl = ids_s[pos_r], ids_s[pos_l]

    lo = jnp.searchsorted(sorted_gr, gl, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(sorted_gr, gl, side="right").astype(jnp.int32)
    rlo = jnp.searchsorted(sorted_gl, gr, side="left")
    rhi = jnp.searchsorted(sorted_gl, gr, side="right")
    has_r = (rhi > rlo) & (gr >= 0)
    return Probe(gl, gr, order_r, lo, hi - lo, has_r)


def emit_counts(p: Probe, how: str, l_rm, r_rm):
    """Per-left-row emit counts + unmatched-right mask + total rows.

    l_rm/r_rm: logical-row masks (padding excluded).  Emit semantics
    match the host oracle: inner = cnt; left/full = max(cnt, 1);
    right/full additionally emit each unmatched right row once."""
    import jax.numpy as jnp

    cnt = jnp.where(l_rm, p.cnt, 0)
    if how in ("left", "full"):
        emit = jnp.where(l_rm, jnp.maximum(cnt, 1), 0)
    else:
        emit = cnt
    if how in ("right", "full"):
        r_extra = r_rm & ~p.has_r
    else:
        r_extra = jnp.zeros_like(r_rm)
    total = emit.sum(dtype=jnp.int64) + r_extra.sum(dtype=jnp.int64)
    return emit, r_extra, total


def expand_pairs(p: Probe, emit, r_extra, c_out: int):
    """Turn slot t in [0, c_out) into its (lidx, ridx) pair; -1 marks
    the null-extended side.  Returns (lidx, ridx, slot_valid)."""
    import jax.numpy as jnp

    nl = emit.shape[0]
    nr = p.gr.shape[0]
    offs = prefix_sum(emit)                      # inclusive
    m_left = offs[-1]
    t = jnp.arange(c_out, dtype=jnp.int64)
    li = jnp.searchsorted(offs, t, side="right").astype(jnp.int32)
    li_safe = jnp.clip(li, 0, nl - 1)
    prev = offs[li_safe] - emit[li_safe]         # exclusive prefix
    k = (t - prev).astype(jnp.int32)
    in_left = t < m_left
    matched = p.cnt[li_safe] > 0
    ri_pos = jnp.clip(p.lo[li_safe] + k, 0, nr - 1)
    ridx = jnp.where(matched, p.order_r[ri_pos], -1)
    lidx = jnp.where(in_left, li_safe, -1)
    ridx = jnp.where(in_left, ridx, -1)

    # unmatched right rows fill slots [m_left, m_left + n_extra)
    n_extra = r_extra.sum(dtype=jnp.int64)
    unmatched_order = partition_order(r_extra)
    s = jnp.clip(t - m_left, 0, nr - 1)
    ridx = jnp.where(~in_left, unmatched_order[s], ridx)
    slot_valid = t < (m_left + n_extra)
    ridx = jnp.where(slot_valid, ridx, -1)
    lidx = jnp.where(slot_valid, lidx, -1)
    return lidx, ridx, slot_valid


def gather_side(columns: List[DeviceColumn], idx, slot_valid
                ) -> List[DeviceColumn]:
    """Gather one side's columns by row index; idx -1 → null."""
    import jax.numpy as jnp

    out = []
    for c in columns:
        safe = jnp.clip(idx, 0, c.data.shape[0] - 1)
        data = c.data[safe]
        validity = c.validity[safe] & (idx >= 0) & slot_valid
        lengths = c.lengths[safe] if c.lengths is not None else None
        out.append(DeviceColumn(c.dtype, data, validity, lengths))
    return out
