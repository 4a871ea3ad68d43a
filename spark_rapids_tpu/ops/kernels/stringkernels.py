"""Device string kernels over the fixed-width byte-matrix encoding.

These are the TPU answers to cudf's string kernels (reference:
stringFunctions.scala lowers to cudf string ops).  All operate on
(bytes uint8[n, w], lengths int32[n]) and are branch-free/static-shape so
they fuse on the VPU.  Ops with data-dependent width (regexp etc.) are NOT
here — they host-fallback, mirroring the reference's regex bail-outs.
"""
from __future__ import annotations


def _jnp():
    import jax.numpy as jnp

    return jnp


def _pad_to(bm, w):
    jnp = _jnp()
    cur = bm.shape[1]
    if cur == w:
        return bm
    if cur < w:
        return jnp.pad(bm, ((0, 0), (0, w - cur)))
    return bm[:, :w]


def _masked(bm, lengths):
    """Zero out bytes at positions >= length (defensive canonicalization)."""
    jnp = _jnp()
    w = bm.shape[1]
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    return jnp.where(pos < lengths[:, None], bm, 0)


def compare(lbm, llen, rbm, rlen):
    """Lexicographic byte-wise compare -> int32 in {-1, 0, 1}.

    Matches UTF-8 binary collation (Spark's default string ordering)."""
    jnp = _jnp()
    w = max(lbm.shape[1], rbm.shape[1])
    l = _masked(_pad_to(lbm, w), llen).astype(jnp.int32)
    r = _masked(_pad_to(rbm, w), rlen).astype(jnp.int32)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    both = (pos < llen[:, None]) & (pos < rlen[:, None])
    diff = jnp.where(both, l - r, 0)
    nz = diff != 0
    # index of first nonzero difference, w if none
    first = jnp.where(nz.any(axis=1), jnp.argmax(nz, axis=1), w)
    d = jnp.take_along_axis(diff, jnp.clip(first, 0, w - 1)[:, None],
                            axis=1)[:, 0]
    byte_cmp = jnp.sign(d)
    len_cmp = jnp.sign(llen - rlen)
    return jnp.where(first < jnp.minimum(llen, rlen), byte_cmp,
                     len_cmp).astype(jnp.int32)


def equals(lbm, llen, rbm, rlen):
    jnp = _jnp()
    w = max(lbm.shape[1], rbm.shape[1])
    l = _masked(_pad_to(lbm, w), llen)
    r = _masked(_pad_to(rbm, w), rlen)
    return (llen == rlen) & (l == r).all(axis=1)


def _case_map(bm, lengths, lo, hi, delta):
    jnp = _jnp()
    m = _masked(bm, lengths)
    in_range = (m >= lo) & (m <= hi)
    return jnp.where(in_range, m + delta, m).astype(jnp.uint8)


def upper(bm, lengths):
    """ASCII upper (documented incompat vs full Unicode, like the
    reference's cudf upper gated by incompatibleOps)."""
    return _case_map(bm, lengths, ord("a"), ord("z"), -32), lengths


def lower(bm, lengths):
    return _case_map(bm, lengths, ord("A"), ord("Z"), 32), lengths


def length(bm, lengths):
    """Character length.  UTF-8: count non-continuation bytes."""
    jnp = _jnp()
    m = _masked(bm, lengths)
    cont = (m & jnp.uint8(0xC0)) == jnp.uint8(0x80)
    pos = jnp.arange(bm.shape[1], dtype=jnp.int32)[None, :]
    valid_byte = pos < lengths[:, None]
    return (valid_byte & ~cont).sum(axis=1).astype(jnp.int32)


def substring(bm, lengths, start: int, sub_len: int, out_w: int):
    """Byte-position substring (ASCII-accurate; Spark substring is
    character based — multibyte handled by charpos below).
    ``start`` is 0-based here; negative means from the end."""
    jnp = _jnp()
    n, w = bm.shape
    if start < 0:
        s = jnp.maximum(lengths + start, 0)
    else:
        s = jnp.minimum(jnp.full_like(lengths, start), lengths)
    e = jnp.minimum(s + max(sub_len, 0), lengths)
    new_len = (e - s).astype(jnp.int32)
    pos = jnp.arange(out_w, dtype=jnp.int32)[None, :]
    src = s[:, None] + pos
    src_c = jnp.clip(src, 0, w - 1)
    gathered = jnp.take_along_axis(bm, src_c, axis=1)
    out = jnp.where(pos < new_len[:, None], gathered, 0).astype(jnp.uint8)
    return out, new_len


def concat(parts):
    """Concatenate [(bm, len), ...] row-wise."""
    jnp = _jnp()
    total_w = sum(p[0].shape[1] for p in parts)
    n = parts[0][0].shape[0]
    out = jnp.zeros((n, total_w), dtype=jnp.uint8)
    out_len = jnp.zeros((n,), dtype=jnp.int32)
    pos = jnp.arange(total_w, dtype=jnp.int32)[None, :]
    for bm, ln in parts:
        w = bm.shape[1]
        src = pos - out_len[:, None]
        src_c = jnp.clip(src, 0, w - 1)
        g = jnp.take_along_axis(_pad_to(bm, max(total_w, w))[:, :total_w]
                                if w < total_w else bm[:, :total_w],
                                src_c, axis=1)
        write = (src >= 0) & (src < ln[:, None])
        out = jnp.where(write, g, out)
        out_len = out_len + ln
    return out, out_len


def _find(bm, lengths, needle: bytes):
    """Positions where needle matches (bool[n, w]).  The byte ``j``
    places on is read by a static slice of the matrix, zero-padded at
    the row's end.  A ``take_along_axis`` by ``pos + j`` compiles to
    gathers on a TPU (256 for ``%special%requests%`` over 2^20 rows): on
    a v5e, 2^21 rows of 64 bytes, the matcher took 14.6 ms that way and
    8.0 by slices, and compiled in 24.5 s against 2.8 (PERF.md,
    section 6)."""
    jnp = _jnp()
    n, w = bm.shape
    k = len(needle)
    if k == 0:
        return jnp.ones((n, w), dtype=bool)
    if k > w:
        return jnp.zeros((n, w), dtype=bool)
    m = _masked(bm, lengths)
    match = jnp.ones((n, w), dtype=bool)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    for j, byte in enumerate(needle):
        shifted = jnp.pad(m[:, j:], ((0, 0), (0, j))) if j else m
        match = match & (shifted == byte)
    match = match & (pos + k <= lengths[:, None])
    return match


def contains(bm, lengths, needle: bytes):
    return _find(bm, lengths, needle).any(axis=1)


def startswith(bm, lengths, needle: bytes):
    jnp = _jnp()
    k = len(needle)
    if k == 0:
        return jnp.ones((bm.shape[0],), dtype=bool)
    if k > bm.shape[1]:
        return jnp.zeros((bm.shape[0],), dtype=bool)
    m = _masked(bm, lengths)
    ok = lengths >= k
    for j, byte in enumerate(needle):
        ok = ok & (m[:, j] == byte)
    return ok


def endswith(bm, lengths, needle: bytes):
    jnp = _jnp()
    n, w = bm.shape
    k = len(needle)
    if k == 0:
        return jnp.ones((n,), dtype=bool)
    if k > w:
        return jnp.zeros((n,), dtype=bool)
    m = _masked(bm, lengths)
    ok = lengths >= k
    for j, byte in enumerate(needle):
        idx = jnp.clip(lengths - k + j, 0, w - 1)
        ok = ok & (jnp.take_along_axis(m, idx[:, None], axis=1)[:, 0] == byte)
    return ok


def locate_from(bm, lengths, needle: bytes, start):
    """1-based byte position of the first match at offset >= ``start``
    (a traced per-row int32 vector); 0 if absent.  The greedy-leftmost
    building block of the device LIKE matcher."""
    jnp = _jnp()
    w = bm.shape[1]
    match = _find(bm, lengths, needle)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    match = match & (pos >= start[:, None])
    any_ = match.any(axis=1)
    first = jnp.argmax(match, axis=1).astype(jnp.int32)
    return jnp.where(any_, first + 1, 0)


def locate(bm, lengths, needle: bytes, start_pos: int = 1):
    """1-based position of first match at/after start_pos; 0 if absent."""
    jnp = _jnp()
    n, w = bm.shape
    match = _find(bm, lengths, needle)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    match = match & (pos >= (start_pos - 1))
    any_ = match.any(axis=1)
    first = jnp.argmax(match, axis=1).astype(jnp.int32)
    return jnp.where(any_, first + 1, 0)


def substring_index(bm, lengths, delim: bytes, count: int):
    """Spark ``substring_index`` for a SINGLE-BYTE delimiter (cannot
    self-overlap, so every match is a split point — exact vs
    str.split).  count>0: prefix before the count-th delimiter;
    count<0: suffix after the |count|-th-from-the-right; too few
    delimiters -> the whole string."""
    jnp = _jnp()
    n, w = bm.shape
    if count == 0:
        return jnp.zeros_like(bm), jnp.zeros_like(lengths)
    match = _find(bm, lengths, delim)
    cum = jnp.cumsum(match.astype(jnp.int32), axis=1)
    total = cum[:, -1] if w else jnp.zeros((n,), jnp.int32)
    if count > 0:
        has = total >= count
        hit = (cum == count) & match
        cut = jnp.argmax(hit, axis=1).astype(jnp.int32)
        new_len = jnp.where(has, cut, lengths)
        return _masked(bm, new_len), new_len
    k = -count
    has = total >= k
    target = total - k + 1
    hit = (cum == target[:, None]) & match
    start = jnp.where(has,
                      jnp.argmax(hit, axis=1).astype(jnp.int32)
                      + len(delim), 0)
    new_len = (lengths - start).astype(jnp.int32)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    src = jnp.clip(start[:, None] + pos, 0, max(w - 1, 0))
    g = jnp.take_along_axis(bm, src, axis=1)
    keep = pos < new_len[:, None]
    return jnp.where(keep, g, 0).astype(jnp.uint8), new_len


def replace_single(bm, lengths, search: bytes, replace: bytes):
    """Replace every occurrence of a SINGLE search byte with ``replace``
    (any length, including empty = delete).  A single byte cannot
    self-overlap, so match positions are exactly str.replace's
    non-overlapping scan.  Output width grows to w*len(replace) worst
    case; built by scatter with a dump slot for masked writes."""
    jnp = _jnp()
    n, w = bm.shape
    k = len(replace)
    m = _masked(bm, lengths)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    in_str = pos < lengths[:, None]
    match = (m == search[0]) & in_str
    mi = match.astype(jnp.int32)
    excl = jnp.cumsum(mi, axis=1) - mi      # matches strictly before j
    o = pos + (k - 1) * excl                # output offset of byte j
    out_w = max(w * max(k, 1), 1)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]
    out = jnp.zeros((n, out_w + 1), dtype=jnp.uint8)  # +1 dump slot
    copy_idx = jnp.where(in_str & ~match, o, out_w)
    out = out.at[rows, copy_idx].set(
        jnp.where(in_str & ~match, m, 0).astype(jnp.uint8))
    for t in range(k):
        idx_t = jnp.where(match, o + t, out_w)
        out = out.at[rows, idx_t].set(jnp.uint8(replace[t]))
    new_len = (lengths + (k - 1) * mi.sum(axis=1)).astype(jnp.int32)
    return out[:, :out_w], new_len


def trim_ws(bm, lengths, out_w: int, left: bool = True, right: bool = True):
    """Trim spaces (0x20) from either end."""
    jnp = _jnp()
    n, w = bm.shape
    m = _masked(bm, lengths)
    pos = jnp.arange(w, dtype=jnp.int32)[None, :]
    is_sp = (m == 0x20) | (pos >= lengths[:, None])
    if left:
        lead = jnp.where((~is_sp).any(axis=1),
                         jnp.argmax(~is_sp, axis=1), lengths)
    else:
        lead = jnp.zeros((n,), dtype=jnp.int32)
    if right:
        rev = ~is_sp[:, ::-1]
        from_end = jnp.where(rev.any(axis=1),
                             jnp.argmax(rev, axis=1).astype(jnp.int32),
                             jnp.full((n,), w, dtype=jnp.int32))
        # positions past the logical length counted as spaces; subtract
        trail = jnp.maximum(from_end - (w - lengths), 0)
    else:
        trail = jnp.zeros((n,), dtype=jnp.int32)
    new_len = jnp.maximum(lengths - lead - trail, 0).astype(jnp.int32)
    src = jnp.clip(lead[:, None] + jnp.arange(out_w, dtype=jnp.int32)[None, :],
                   0, w - 1)
    out = jnp.take_along_axis(m, src, axis=1)
    keep = jnp.arange(out_w, dtype=jnp.int32)[None, :] < new_len[:, None]
    return jnp.where(keep, out, 0).astype(jnp.uint8), new_len
