"""The device LIKE matcher (``ops/stringexprs.py:Like``: ``startswith``,
``locate_from``, ``endswith`` over a string column's byte matrix)
against Python's ``re``, on seeded strings and on the edges of the
matrix: adjacent and overlapping segments, a needle at a row's first and
last byte, rows as wide as the matrix, empty, null and padding rows; and
the scope ``strings.match`` that names the matcher inside a program."""
import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.data import strings
from spark_rapids_tpu.data.column import DeviceBatch, DeviceColumn
from spark_rapids_tpu.ops.expression import BoundReference
from spark_rapids_tpu.ops.stringexprs import Like

SEED = 2**31 + 4040


def _regex(pattern):
    """LIKE with ``%`` alone as the anchored regular expression it is,
    built here and not by ``Like``: ``%`` any run of characters
    (newlines too), every other character itself."""
    return re.compile("".join(".*" if ch == "%" else re.escape(ch)
                              for ch in pattern), re.DOTALL)


def _device(pattern, values, width=None, pad_rows=0):
    """The matcher's bool per row over the byte matrix ``strings.encode``
    makes of ``values`` (widened to ``width``), with ``pad_rows`` rows of
    non-zero bytes and length 0 below them, as a padded bucket's rows."""
    bm, ln = strings.encode(np.asarray(values, dtype=object), None, width)
    if pad_rows:
        bm = np.concatenate([bm, np.full((pad_rows, bm.shape[1]), ord("s"),
                                         np.uint8)])
        ln = np.concatenate([ln, np.zeros(pad_rows, ln.dtype)])
    like = Like(BoundReference(0, T.STRING, True), pattern)
    return np.asarray(jax.jit(like._match_device)(jnp.asarray(bm),
                                                  jnp.asarray(ln)))


EDGES = [
    # (pattern, value, matches)
    ("%special%requests%", "specialrequests", True),        # adjacent
    ("%special%requests%", "requests special", False),      # wrong order
    ("%special%requests%", "special requests", True),       # at byte 0
    ("%special%requests%", "x special y requests", True),   # at the end
    ("%special%requests%", "special", False),
    ("%special%requests%", "requests", False),
    ("%special%requests%", "specia lrequests", False),
    ("%special%requests%", "requests special requests", True),
    ("%aba%aba%", "ababa", False),                           # overlap
    ("%aba%aba%", "abaaba", True),
    ("%aba%aba%", "abababa", True),
    ("%aa%aa%", "aaa", False),
    ("%aa%aa%", "aaaa", True),
    ("a%b", "ab", True),
    ("a%b", "a", False),
    ("a%a", "a", False),                                     # overlap
    ("%ab", "abab", True),
    ("ab%", "b", False),
    ("%%", "", True),
    ("%", "", True),
    ("", "", True),
    ("", "x", False),
    ("%x%", "", False),
]


@pytest.mark.parametrize("pattern,value,matches", EDGES,
                         ids=[f"{p}|{v}" for p, v, _ in EDGES])
def test_edge_rows_equal_re(pattern, value, matches):
    assert bool(_regex(pattern).fullmatch(value)) is matches
    # alone (the matrix as wide as the row), and in a wider matrix among
    # rows as wide as it with the needles at their last byte
    alone = _device(pattern, [value])
    assert alone.tolist() == [matches]
    others = ["z" * 40 + "special requests", "q" * 42 + "ababa",
              "requests special" + "w" * 40]
    got = _device(pattern, [value] + others, width=64, pad_rows=3)
    want = [bool(_regex(pattern).fullmatch(v)) for v in [value] + others]
    assert got[:4].tolist() == want
    # padding rows hold bytes past their length 0: an empty string's
    # answer, whatever the bytes
    assert got[4:].tolist() == [bool(_regex(pattern).fullmatch(""))] * 3


WORDS = ["special", "requests", "spec", "ial", "req", "uests", "aba", "ab",
         "a", "b", "x", " ", "%", "é"]


@pytest.mark.parametrize("pattern", [
    "%special%requests%", "special%requests%", "%special%requests",
    "%aba%ab%a%", "%a%", "ab%x", "%req%uests%req%", "%é%a%"])
def test_seeded_strings_equal_re(pattern):
    rng = np.random.default_rng(SEED)
    values = ["".join(rng.choice(WORDS, size=rng.integers(0, 9)))
              for _ in range(600)]
    got = _device(pattern, values)
    want = np.array([bool(_regex(pattern).fullmatch(v)) for v in values])
    assert (got == want).all(), [v for v, g, w in zip(values, got, want)
                                 if g != w][:5]
    assert 0 < want.sum() < len(values)


def test_rows_at_the_matrix_full_width():
    """Every row as wide as the matrix: the needle's last byte on the
    matrix's last column, and one byte short of it."""
    values = ["x" * 49 + "special requests" + "!",
              "x" * 50 + "special requests",
              "x" * 51 + "special request",
              "special" + "y" * 51 + "requests"[:8]]
    assert {len(v) for v in values} == {66}
    got = _device("%special%requests%", values)
    assert got.tolist() == [True, True, False, True]


def test_null_and_empty_rows_through_the_engine():
    """Through ``Session`` in strict mode (no host fallback): a null
    comment is neither LIKE nor NOT LIKE, an empty one is NOT LIKE."""
    values = ["special requests", None, "", "requests special",
              "a special b requests", None, "specialrequests"]
    sess = srt.Session({"spark.rapids.tpu.sql.test.enabled": True})
    df = sess.create_dataframe({"k": list(range(len(values))),
                                "s": values})
    got = df.select("k", df["s"].like("%special%requests%").alias("m")) \
        .collect()
    assert [r[1] for r in sorted(got)] == \
        [True, None, False, False, True, None, True]
    kept = df.filter(~df["s"].like("%special%requests%")).select("k")
    assert sorted(r[0] for r in kept.collect()) == [2, 3]


def _scopes(text):
    return {part for path in re.findall(r'loc\("([^"]+)"\(', text)
            for part in path.split("/")[:-1]}


def test_strings_match_names_the_matcher_in_the_lowered_program(
        monkeypatch):
    """``strings.match`` stands in the op metadata of the program that
    evaluates a LIKE, around the matcher and not its input; switched
    off, the lowered program without debug info is the same: a scope
    changes no program and no compile-cache key."""
    schema = T.Schema([T.Field("s", T.STRING, True)])
    like = Like(BoundReference(0, T.STRING, True), "%special%requests%")

    def lowered():
        # a new function each time: nothing JAX traced before is reused
        def program(bm, ln, valid):
            batch = DeviceBatch(
                schema, [DeviceColumn(T.STRING, bm, valid, ln)],
                bm.shape[0])
            return like.eval_tpu(batch).data

        return jax.jit(program).lower(
            jax.ShapeDtypeStruct((256, 32), jnp.uint8),
            jax.ShapeDtypeStruct((256,), jnp.int32),
            jax.ShapeDtypeStruct((256,), jnp.bool_))

    assert "strings.match" in _scopes(lowered().as_text(debug_info=True))
    keyed = lowered().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert "strings.match" not in _scopes(
        lowered().as_text(debug_info=True))
    assert lowered().as_text() == keyed
