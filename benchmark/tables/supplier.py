"""TPC-H ``supplier``.  ``s_comment`` follows the specification
(clause 4.2.3) where Q16 reads it: text of 25 to 100 bytes, cut from a
pool of comment words as dbgen cuts its own; ``Customer`` ...
``Complaints`` in 5 rows of every 10,000 and ``Customer`` ...
``Recommends`` in another 5, with text between the two words, so that
only the two-wildcard pattern finds them.  The other columns keep the
in-repo generator's shapes (NOT dbgen)."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g
from benchmark.harness import load_module

STREAM = 6
COMMENT_MIN, COMMENT_MAX = 25, 100
#: every WIDEST_EVERY-th row is COMMENT_MAX bytes long: a byte matrix
#: is as wide as its batch's longest string, so every batch of that
#: many rows has the column's greatest width, whatever the seed
WIDEST_EVERY = 16
#: of every 10,000 suppliers, as the specification has it
MARKED_PER_10000 = 5
POOL_BYTES = 1 << 16
FIRST, BAD, GOOD = b"Customer", b"Complaints", b"Recommends"
#: at least two bytes between the words: never "Customer Complaints"
GAP_MIN, GAP_MAX = 2, 7


def marked_rows(n, seed):
    """The first draw of this table's stream: (the stream, rows that
    complain, rows that recommend), the two disjoint, in the
    specification's ratio and at least one of each."""
    rng = g.rng_for(seed, STREAM)
    k = max(1, n * MARKED_PER_10000 // 10_000)
    rows = rng.permutation(n)[:2 * k]
    return rng, np.sort(rows[:k]), np.sort(rows[k:])


def comment_text(rng, n, bad, good):
    """(uint8[n, COMMENT_MAX], lengths): each row a cut of the pool,
    the marked rows with their two words written over it."""
    words = np.array(g.COMMENT_WORDS)[
        rng.integers(0, len(g.COMMENT_WORDS), POOL_BYTES // 4)]
    pool = np.frombuffer(" ".join(words).encode(), dtype=np.uint8)
    lengths = rng.integers(COMMENT_MIN, COMMENT_MAX + 1, n).astype(np.int32)
    lengths[::WIDEST_EVERY] = COMMENT_MAX
    start = rng.integers(0, len(pool) - COMMENT_MAX, n)
    text = pool[start[:, None] + np.arange(COMMENT_MAX)]
    for rows_, second in ((bad, BAD), (good, GOOD)):
        for r in rows_:
            gap = int(rng.integers(GAP_MIN, GAP_MAX + 1))
            at = int(rng.integers(
                0, lengths[r] - len(FIRST) - gap - len(second) + 1))
            text[r, at:at + len(FIRST)] = np.frombuffer(FIRST, np.uint8)
            at += len(FIRST) + gap
            text[r, at:at + len(second)] = np.frombuffer(second, np.uint8)
    return text, lengths


def strings(text, lengths):
    """The byte matrix's rows, each cut to its length, as Arrow strings."""
    offsets = np.zeros(len(lengths) + 1, dtype=np.int32)
    np.cumsum(lengths, out=offsets[1:])
    flat = text[np.arange(text.shape[1]) < lengths[:, None]]
    return pa.StringArray.from_buffers(
        len(lengths), pa.py_buffer(offsets), pa.py_buffer(flat))


def generate(rows, seed):
    n = rows["supplier"]
    customer = load_module("tables", "customer")
    rng, bad, good = marked_rows(n, seed)
    key = np.arange(1, n + 1, dtype=np.int64)
    return pa.table({
        "s_suppkey": key,
        "s_name": g.numbered("Supplier#", key),
        "s_address": g.comments(rng, n, 2),
        "s_nationkey": customer.nation_keys(rng, n),
        "s_phone": customer.phones(rng, n),
        "s_acctbal": g.money(rng, -999.99, 9999.99, n),
        "s_comment": strings(*comment_text(rng, n, bad, good)),
    })
