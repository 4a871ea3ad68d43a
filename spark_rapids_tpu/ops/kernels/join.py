"""Device equi-join kernels: sort-merge with static-shape expansion.

Reference analogue: GpuHashJoin.scala:71-140 (cudf hash-join calls) —
but where cudf scatters into hash tables, the TPU-friendly frontier is
sort-based (SURVEY §7 "Hard parts": hash join on TPU → sort + merge;
the reference replaces SortMergeJoin with hash join, here the
replacement is reversed).  Three stages, all static shapes:

  1. merge: concat both sides' key columns (left first), one stable
     lexsort.  Equal keys (with Spark null/NaN/-0.0 semantics) now lie
     together, a key's left rows before its right rows; a key changes
     where the words the sort compared change.
  2. probe: per left row the contiguous run [lo, lo+cnt) of its
     matches among the right rows in key order, read off that order by
     scans (a prefix sum of the side flag, segmented sums from the
     front and from the back) — no search, no second lookup.  Match
     counts are exact before any expansion — the same "size before
     materialize" contract cudf's join APIs give the reference.
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
from ...utils.tracing import device_phase
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


class Probe(NamedTuple):
    """What one sort of both sides' keys says about every row, in the
    rows' own order (not the sorted one)."""
    order_r: object  # int32[Nr] right rows in key order, never-joining last
    lo: object       # int32[Nl] a left row's first match, a place in order_r
    cnt: object      # int32[Nl] its number of right matches (0: none)
    has_r: object    # bool[Nr] right row has a left match


@device_phase("join.probe")
def probe(l_keys: List[DeviceColumn], r_keys: List[DeviceColumn],
          l_ok, r_ok) -> Probe:
    """Every left row's run of matches, read off ONE sort of both sides'
    keys.

    The sort is stable and the left side is concatenated first, so in
    sorted order the rows of one key lie together, its left rows before
    its right rows, and the rows that never join (null key, padding:
    ``ok`` False) after every key, each a segment of its own.  A left
    row's matches are then the right rows from it to its segment's end
    (``cnt``: a segmented sum run from the back), the first of them as
    far into the right rows' order as there are right rows before it
    (``lo``: a prefix sum), and a right row is matched when a left row
    stands before it in its segment (``has_r``: a segmented sum run
    from the front).  Whole numbers, so exact.  Nothing is searched for
    and nothing scatters: the key changes come from one stacked gather
    of the words the sort compared, the answers go back to row order in
    one sort by ``order`` that carries them, and ``order_r`` is
    ``order`` with its right rows sorted to the front.  (2^22 + 2^17
    rows on a v5e: 147 ms where the four searches alone took 1125; the
    way back 13 ms by that sort, 33 by the inverse permutation's gather,
    342 as a stacked scatter; ``order_r`` 7 ms, 31 by
    ``partition_order``; the words 27 ms stacked, 162 a gather a word;
    PERF.md, PR 31.)"""
    import jax.numpy as jnp
    from jax import lax

    nl, nr = l_ok.shape[0], r_ok.shape[0]
    n = nl + nr
    combined = [_concat_key_cols(a, b) for a, b in zip(l_keys, r_keys)]
    ok = jnp.concatenate([l_ok, r_ok])
    # null keys never join: fold key validity into row eligibility
    for c in combined:
        ok = ok & c.validity
    words = seg.key_passes_device(combined, pad_valid=ok)
    order = seg.sort_permutation(words, n)

    # equal keys have equal words (-0.0 and 0.0, NaN and NaN share
    # theirs); a string's length rides along, as its bytes are padded
    rows = words + [c.lengths.astype(jnp.uint32) for c in combined
                    if c.lengths is not None]
    with device_phase("reorder"):
        keys_s = jnp.stack(rows)[:, order]
    pos = jnp.arange(n, dtype=jnp.int32)
    ok_s = pos < ok.sum(dtype=jnp.int32)
    change = ~ok_s | jnp.concatenate(
        [jnp.ones((1,), jnp.bool_),
         (keys_s[:, 1:] != keys_s[:, :-1]).any(axis=0)])
    seg_end = jnp.concatenate([change[1:], jnp.ones((1,), jnp.bool_)])

    is_right = order >= nl
    rights_before = prefix_sum(is_right.astype(jnp.int32))
    lefts_before = seg.segmented_scan(
        (~is_right).astype(jnp.int32)[None], change, jnp.add)[0]
    rights_after = jnp.flip(seg.segmented_scan(
        jnp.flip(is_right).astype(jnp.int32)[None], jnp.flip(seg_end),
        jnp.add)[0])

    # back to row order: a sort by a permutation is its inverse's gather
    mine = jnp.where(is_right, lefts_before,
                     jnp.where(ok_s, rights_before, 0))
    _, mine, rights_after = lax.sort((order, mine, rights_after),
                                     num_keys=1, is_stable=False)
    _, right_first = lax.sort((jnp.where(is_right, pos, n + pos), order),
                              num_keys=1, is_stable=False)
    return Probe(right_first[:nr] - nl, mine[:nl], rights_after[:nl],
                 mine[nl:] > 0)


@device_phase("join.emitCounts")
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


@device_phase("join.expandGather")
def expand_pairs(p: Probe, emit, r_extra, c_out: int):
    """Turn slot t in [0, c_out) into its (lidx, ridx) pair; -1 marks
    the null-extended side.  Returns (lidx, ridx, slot_valid)."""
    import jax.numpy as jnp

    nl = emit.shape[0]
    nr = p.order_r.shape[0]
    offs = prefix_sum(emit)                      # inclusive
    m_left = offs[-1]
    t = jnp.arange(c_out, dtype=jnp.int64)
    with device_phase("join.expandSearch"):
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


@device_phase("reorder")
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
