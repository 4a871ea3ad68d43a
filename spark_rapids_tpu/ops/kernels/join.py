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
  3. expand: with an output capacity chosen from the exact count, slot
     t becomes its (left row, k-th match) pair: by ``pair_rows``' sort
     and carry scan and a second sort that moves the slots to the front,
     or, where few slots stand over a wide left side, by a searchsorted
     over the emit prefix sum (``expand_by_sort`` decides from the
     shapes); gathers materialize the output.

The only host sync is reading the match count to pick the output's
power-of-two bucket (the same sync point the reference has when cudf
returns the join output size).

A semi or anti join with a condition (Spark's LeftSemi/LeftAnti with a
residual, what ``EXISTS`` / ``NOT EXISTS`` with a non-equi correlation
plan to) asks only whether SOME pair of a left row makes it TRUE.  Where
the condition is one comparison of a left value with a right one
(``<``, ``<=``, ``>``, ``>=``, ``<>``) over integers, dates or
timestamps, that depends on the key's least and greatest non-null right
value alone: ``some_holds`` reads both off the probe's sort by one scan
and compares, with no pair laid out.  Any other condition
needs its pairs but never their order: ``pair_rows`` lays them out by
one sort and scans, with no search (section 5 of PERF.md prices a
``searchsorted`` step of 2^25 slots at seconds), and ``any_pair`` reads
one bit a left row back off a prefix sum of the condition's hits.
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


class _Merged(NamedTuple):
    """Both sides' rows in the order of one sort of their keys."""
    order: object    # int32[n]: the row at each place, first side first
    pos: object      # int32[n]: the places
    ok_s: object     # bool[n]: the place holds a row that can join
    change: object   # bool[n]: a key's segment starts at the place
    seg_end: object  # bool[n]: ... or ends there
    ride: object     # uint32[k, n]: the rows asked to ride, in key order


def _merge(l_keys: List[DeviceColumn], r_keys: List[DeviceColumn],
           l_ok, r_ok, ride=()) -> _Merged:
    """The one sort ``probe`` and ``some_holds`` read their answers off
    (``probe`` says how); within a key the first side's rows stand before
    the second's.  Each uint32[nl + nr] row of ``ride`` goes into key
    order in the stacked gather of the key words."""
    import jax.numpy as jnp

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
        stacked = jnp.stack(rows + list(ride))[:, order]
    keys_s = stacked[:len(rows)] if ride else stacked
    pos = jnp.arange(n, dtype=jnp.int32)
    ok_s = pos < ok.sum(dtype=jnp.int32)
    change = ~ok_s | jnp.concatenate(
        [jnp.ones((1,), jnp.bool_),
         (keys_s[:, 1:] != keys_s[:, :-1]).any(axis=0)])
    seg_end = jnp.concatenate([change[1:], jnp.ones((1,), jnp.bool_)])
    return _Merged(order, pos, ok_s, change, seg_end,
                   stacked[len(rows):] if ride else None)


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
    m = _merge(l_keys, r_keys, l_ok, r_ok)
    order, pos = m.order, m.pos

    is_right = order >= nl
    rights_before = prefix_sum(is_right.astype(jnp.int32))
    lefts_before = seg.segmented_scan(
        (~is_right).astype(jnp.int32)[None], m.change, jnp.add)[0]
    rights_after = jnp.flip(seg.segmented_scan(
        jnp.flip(is_right).astype(jnp.int32)[None], jnp.flip(m.seg_end),
        jnp.add)[0])

    # back to row order: a sort by a permutation is its inverse's gather
    mine = jnp.where(is_right, lefts_before,
                     jnp.where(m.ok_s, rights_before, 0))
    _, mine, rights_after = lax.sort((order, mine, rights_after),
                                     num_keys=1, is_stable=False)
    _, right_first = lax.sort((jnp.where(is_right, pos, n + pos), order),
                              num_keys=1, is_stable=False)
    return Probe(right_first[:nr] - nl, mine[:nl], rights_after[:nl],
                 mine[nl:] > 0)


def _value_words(v):
    """uint32 words, most significant first, whose unsigned
    lexicographic order is the order of the int32 or int64 ``v``."""
    import jax.numpy as jnp
    from jax import lax

    sign = jnp.uint32(1 << 31)
    if v.dtype == jnp.int64:
        return [lax.bitcast_convert_type((v >> 32).astype(jnp.int32),
                                         jnp.uint32) ^ sign,
                lax.bitcast_convert_type(v.astype(jnp.int32), jnp.uint32)]
    return [lax.bitcast_convert_type(v, jnp.uint32) ^ sign]


def _less(a, b, or_equal: bool):
    """``a < b`` (``a <= b``) over lists of words, most significant
    first, broadcast over whatever leading axes they share."""
    out = (a[-1] <= b[-1]) if or_equal else (a[-1] < b[-1])
    for x, y in zip(reversed(a[:-1]), reversed(b[:-1])):
        out = (x < y) | ((x == y) & out)
    return out


def _max_of_pairs(a, b):
    """The row-wise max of ``[hi; lo]`` word pairs stacked on axis -2
    (each ``[..., 2g, m]``: the g high words, then the g low words): the
    combining op of the scan in ``some_holds`` for 64-bit values."""
    import jax.numpy as jnp

    a, b = jnp.broadcast_arrays(a, b)
    g = a.shape[-2] // 2
    take = ~_less([a[..., :g, :], a[..., g:, :]],
                  [b[..., :g, :], b[..., g:, :]], or_equal=False)
    return jnp.where(jnp.concatenate([take, take], axis=-2), a, b)


#: ``x op r`` holds for SOME right value ``r`` of a key, in terms of the
#: key's least and greatest (word lists; ``low <= high`` known)
_SOME = {
    "<": lambda x, low, high: _less(x, high, False),
    "<=": lambda x, low, high: _less(x, high, True),
    ">": lambda x, low, high: _less(low, x, False),
    ">=": lambda x, low, high: _less(low, x, True),
    "!=": lambda x, low, high: ~_less(x, low, True) | _less(x, high, False),
}


@device_phase("join.probe")
def some_holds(op: str, l_keys: List[DeviceColumn],
               r_keys: List[DeviceColumn], l_ok, r_ok, x, x_ok, value,
               value_ok):
    """bool[nl]: ``x op value`` (``op`` a key of ``_SOME``; ``x`` [nl]
    and ``value`` [nr], both int32 or both int64, valid where ``x_ok``,
    ``value_ok``) is TRUE for some right row of the left row's key (a
    NULL on either side, or no key match, is no match), without laying
    out a pair: it depends only on the least and the greatest non-null
    ``value`` of the key.

    Read off ``probe``'s sort with the right side first, so that in each
    key's segment the right rows stand before the left ones, and with a
    NULL ``x`` or ``value`` as a row that never joins (sorted last, a
    segment of its own), as a NULL key is.  The values' words
    (order-preserving uint32) and ``x``'s ride into key order in the
    gather of the key words; one max-scan from the front over each
    segment, of the right values and of their complements (``~w`` orders
    them the other way, so its max is the complement of the min; the
    left rows are 0, the identity), then stands at every left row of the
    key, where ``x`` is compared in place; one sort by ``order`` takes
    the answer to row order.  Exact: whole words compared, no rounding."""
    import jax.numpy as jnp
    from jax import lax

    nr = r_ok.shape[0]
    ride = [jnp.concatenate([a, b])
            for a, b in zip(_value_words(value), _value_words(x))]
    m = _merge(r_keys, l_keys, r_ok & value_ok, l_ok & x_ok, ride)
    with device_phase("join.condition"):
        is_left = m.order >= nr
        words = list(m.ride)
        # each word of the values, then its complement: [w, ~w] or, a
        # pair per group, [hi, ~hi, lo, ~lo]
        top = seg.scan_restarting(
            jnp.stack([jnp.where(is_left, jnp.uint32(0), v)
                       for w in words for v in (w, ~w)]),
            m.change, _max_of_pairs if len(words) == 2 else jnp.maximum)
        high, low = list(top[0::2]), list(~top[1::2])
        hit = (is_left & _less(low, high, True)
               & _SOME[op](words, low, high))
        _, hit = lax.sort((m.order, hit), num_keys=1, is_stable=False)
    return hit[nr:]


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


#: what one ``searchsorted`` step of one output slot costs against one
#: element of the sort path (``_slots_by_sort``: two sorts of ``nl +
#: c_out`` words, one carry scan).  A whole expand on a v5e: the search
#: 18-46 ns a slot and step, the sort 7-13 ns an element (2^21 slots over
#: 2^21 left rows: 1524 ms against 54; over 2^23: 1693 against 102; 2^15
#: slots over 2^23 rows: 17 against 60; PERF.md, section 6)
_SEARCH_STEP_PER_SORTED_ROW = 3


def expand_by_sort(nl: int, c_out: int) -> bool:
    """Whether ``expand_pairs`` maps its ``c_out`` slots to their left
    rows by sorting (``_slots_by_sort``, about ``nl + c_out`` elements
    sorted twice) rather than by searching (``searchsorted``: every slot
    gathered once a step, ``log2(nl)`` steps).  A function of the static
    shapes alone: a few thousand slots over a large left side search, an
    expansion as wide as its side sorts."""
    steps = max(nl, 1).bit_length()
    return nl + c_out < _SEARCH_STEP_PER_SORTED_ROW * c_out * steps


@device_phase("join.expandSearch")
def _slots_by_sort(p: Probe, emit, c_out: int):
    """Every slot's left row and the place of its right row in
    ``order_r`` (negative: the row has no match), without a search, from
    ``pair_rows``' layout: one sort puts each row's slots after its
    marker and a carry scan gives them the row's index and the offset to
    its right rows; a second sort by place moves the slots, in order, to
    the front (a slot past the emitted ones holds any row: the caller
    masks it).  A row without a match carries a ``lo`` so far below zero
    that every place it gives stays negative."""
    import jax.numpy as jnp
    from jax import lax

    nl = emit.shape[0]
    n = nl + c_out
    lo = jnp.where(p.cnt > 0, p.lo, -n - 1)
    lay = _pair_layout(emit, lo, c_out, [jnp.arange(nl, dtype=jnp.int32)])
    place = jnp.arange(n, dtype=jnp.int32)
    _, li, right_pos = lax.sort(
        (jnp.where(lay.valid, place, n), lay.carried[0], lay.right_pos),
        num_keys=1, is_stable=False)
    return li[:c_out], right_pos[:c_out]


@device_phase("join.expandGather")
def expand_pairs(p: Probe, emit, r_extra, c_out: int):
    """Turn slot t in [0, c_out) into its (lidx, ridx) pair; -1 marks
    the null-extended side.  Returns (lidx, ridx, slot_valid).  The
    slot's left row comes by sort or by search as ``expand_by_sort``
    says of the shapes; both give the same arrays."""
    return _expand_pairs(p, emit, r_extra, c_out,
                         expand_by_sort(emit.shape[0], c_out))


def _expand_pairs(p: Probe, emit, r_extra, c_out: int, by_sort: bool):
    import jax.numpy as jnp

    nl = emit.shape[0]
    nr = p.order_r.shape[0]
    offs = prefix_sum(emit)                      # inclusive
    m_left = offs[-1]
    t = jnp.arange(c_out, dtype=jnp.int64)
    if by_sort:
        li_safe, right_pos = _slots_by_sort(p, emit, c_out)
        in_left = t < m_left
        matched = right_pos >= 0
        ri_pos = jnp.clip(right_pos, 0, nr - 1)
    else:
        with device_phase("join.expandSearch"):
            li = jnp.searchsorted(offs, t, side="right").astype(jnp.int32)
        li_safe = jnp.clip(li, 0, nl - 1)
        prev = offs[li_safe] - emit[li_safe]     # exclusive prefix
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


class PairRows(NamedTuple):
    """Every left row's key-matched pairs laid out in one array of
    ``nl + c_out`` places: a row's marker, then one place for each of its
    pairs, rows in order (a row without pairs has no place)."""
    valid: object     # bool[nl + c_out]: the place holds a pair
    right_pos: object  # int32[...]: its right row, a place in order_r
    carried: list     # the left values asked for, at their pairs' places
    first: object     # int32[nl]: a left row's marker, its pairs after it


@device_phase("join.pairRows")
def pair_rows(emit, lo, c_out: int, carried=()) -> PairRows:
    """Slot t of a row's run, without a search.  Row i's ``emit[i]``
    pairs are the right rows ``order_r[lo[i] + k]``, k < emit[i].  A
    place in the layout is a row's marker (sort word 2 x its first slot)
    or a slot (2t + 1), and one sort of both puts each row's slots right
    after its marker: the marker of row i lands at ``first[i]`` = its
    first slot + the rows with pairs before it.  Each 1-D array of
    ``carried`` ([nl]) and the offset to the right row ride along in the
    sort on the markers and are carried to the slots after them by one
    restarting scan (``segment.scan_restarting`` keeping a segment's
    first value): no gather by row, no scatter.  Unique words, so the
    sort need not be stable; the rows without pairs sort last."""
    return _pair_layout(emit, lo, c_out, carried)


def _pair_layout(emit, lo, c_out: int, carried) -> PairRows:
    """``pair_rows`` under its caller's scope (``expand_pairs`` maps its
    slots with it inside ``join.expandSearch``)."""
    import jax.numpy as jnp
    from jax import lax

    nl = emit.shape[0]
    some = emit > 0
    offs = prefix_sum(emit) - emit
    first = offs + prefix_sum(some.astype(jnp.int32)) - some
    total = offs[-1] + emit[-1]
    words = jnp.concatenate([
        jnp.where(some, 2 * offs, 2 * c_out),
        2 * jnp.arange(c_out, dtype=jnp.int32) + 1])
    ride = [lo.astype(jnp.int32) - first - 1] + list(carried)
    ride = [jnp.concatenate([x, jnp.zeros((c_out,), x.dtype)])
            for x in ride]
    words, *ride = lax.sort((words, *ride), num_keys=1, is_stable=False)
    marker = (words & 1) == 0
    by_dtype = {}
    for i, x in enumerate(ride):
        by_dtype.setdefault(x.dtype, []).append(i)
    for group in by_dtype.values():
        held = seg.scan_restarting(jnp.stack([ride[i] for i in group]),
                                   marker, lambda a, b: a)
        for row, i in enumerate(group):
            ride[i] = held[row]
    place = jnp.arange(nl + c_out, dtype=jnp.int32)
    valid = ~marker & ((words >> 1) < total)
    return PairRows(valid, place + ride[0], ride[1:], first)


@device_phase("join.condition")
def any_pair(hit, first, emit):
    """bool[nl]: a left row has a pair where ``hit`` (bool over the
    layout of ``pair_rows``) holds: its hits are the prefix sum read at
    its marker and at its last slot, two gathers of ``nl`` rows."""
    import jax.numpy as jnp

    nl = first.shape[0]
    hits = prefix_sum(hit.astype(jnp.int32))
    ends = jnp.clip(jnp.concatenate([first, first + emit]), 0,
                    hit.shape[0] - 1)
    at = hits[ends]
    return at[nl:] > at[:nl]
