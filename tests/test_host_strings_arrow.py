"""Strings cross the host as Arrow's buffers (ISSUE 33).

A STRING column that a scan decoded keeps Arrow's array
(``data/column.py:ArrowStringColumn``) and the upload builds its byte
matrix from the offsets and bytes (``data/strings.py:encode_buffers``).
Held here: the buffer path gives the arrays ``encode`` gives over the
python objects, to the byte; nothing between a scan and an upload makes
an object; whoever reads ``data`` gets what every other STRING column
holds, once; and an Arrow-backed column cuts, joins, picks, sizes,
pickles and converts like an object-backed one."""
import os
import pickle
import threading
import time
from contextlib import contextmanager

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import spark_rapids_tpu as srt
from spark_rapids_tpu import types as T
from spark_rapids_tpu.data import column as column_mod
from spark_rapids_tpu.data import strings as dstrings
from spark_rapids_tpu.data.column import (ArrowStringColumn, HostBatch,
                                          HostColumn, host_to_device)
from spark_rapids_tpu.io.arrow_convert import (arrow_to_host_batch,
                                               host_batch_to_arrow)
from spark_rapids_tpu.plan import functions as F
from test_tracing_spans import TRACED, recorder  # noqa: F401 (a fixture)

ASCII = ["Brand#45", "MEDIUM POLISHED TIN", "A", "N", "ab cd", "zzzz"]
UTF8 = ["naïve", "日本語のテキスト", "a", "😀 smile", "ß", "Ωmega"]


def _nulls_over_bytes():
    """Arrow allows bytes under a null slot: 'xx' | null('dead') | 'y'
    | null('') | 'tail'."""
    offsets = np.array([0, 2, 6, 7, 7, 11], dtype=np.int32)
    data = np.frombuffer(b"xxdeadytail", dtype=np.uint8)
    bitmap = np.packbits(np.array([1, 0, 1, 0, 1], dtype=np.uint8),
                         bitorder="little")
    return pa.StringArray.from_buffers(
        5, pa.py_buffer(offsets.tobytes()), pa.py_buffer(data.tobytes()),
        pa.py_buffer(bitmap.tobytes()), null_count=2)


#: name -> (Arrow array as a scan could hand it on, max_len)
ARRAYS = {
    "ascii": lambda: (pa.array(ASCII * 50), None),
    "one_byte_flags": lambda: (pa.array(["A", "N", "R"] * 100), None),
    "fixed_width_under_a_wider_matrix":
        lambda: (pa.array(["Brand#12", "Brand#45"] * 64), 25),
    "utf8_multi_byte": lambda: (pa.array(UTF8 * 20), None),
    "empty_strings": lambda: (pa.array(["", "a", "", "", "bc", ""]), None),
    "only_empty_strings": lambda: (pa.array([""] * 7), None),
    "nulls": lambda: (pa.array(["a", None, "ccc", None, "", "dd"]), None),
    "nulls_with_bytes_under_them": lambda: (_nulls_over_bytes(), None),
    "all_null": lambda: (pa.array([None] * 9, type=pa.string()), None),
    "zero_rows": lambda: (pa.array([], type=pa.string()), None),
    "zero_rows_given_width": lambda: (pa.array([], type=pa.string()), 6),
    "sliced_non_zero_offset":
        lambda: (pa.array(ASCII * 10 + [None] + UTF8).slice(7, 55), None),
    "sliced_to_nothing": lambda: (pa.array(ASCII).slice(3, 0), None),
    "large_string":
        lambda: (pa.array(ASCII + [None] + UTF8, type=pa.large_string()),
                 None),
    "large_string_sliced":
        lambda: (pa.array(UTF8 * 9, type=pa.large_string()).slice(5, 30),
                 None),
    "max_len_wider_than_needed": lambda: (pa.array(ASCII * 3), 64),
    "max_len_exactly_the_longest":
        lambda: (pa.array(ASCII), max(len(s) for s in ASCII)),
}


def _objects(arr):
    validity = np.asarray(arr.is_valid()) if arr.null_count else None
    return np.asarray(arr.to_pylist(), dtype=object), validity


def _same(got, want):
    for g, w in zip(got, want):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    assert len(got) == len(want)


# -- (a) the buffer path against encode over the objects --------------
@pytest.mark.parametrize("case", sorted(ARRAYS))
def test_buffers_encode_to_the_bytes_the_objects_encode_to(case):
    arr, max_len = ARRAYS[case]()
    objs, validity = _objects(arr)
    want = dstrings.encode(objs, validity, max_len)
    got = dstrings.encode_buffers(*dstrings.arrow_buffers(arr), validity,
                                  max_len)
    _same(got, want)
    assert got[0].dtype == np.uint8 and got[1].dtype == np.int32
    assert got[0].flags.c_contiguous and got[0].flags.writeable
    # padding and null rows are zero, a row's bytes are its string's
    for i, v in enumerate(arr.to_pylist()):
        raw = (v or "").encode("utf-8")
        assert got[1][i] == len(raw)
        assert bytes(got[0][i, :len(raw)]) == raw
        assert not got[0][i, len(raw):].any()


@pytest.mark.parametrize("case", ["ascii", "utf8_multi_byte", "nulls",
                                  "nulls_with_bytes_under_them",
                                  "sliced_non_zero_offset", "large_string"])
def test_too_narrow_a_matrix_raises_as_encode_does(case):
    arr, _ = ARRAYS[case]()
    objs, validity = _objects(arr)
    longest = max(len((v or "").encode("utf-8")) for v in arr.to_pylist())
    with pytest.raises(ValueError, match="exceeds max_len") as want:
        dstrings.encode(objs, validity, longest - 1)
    with pytest.raises(ValueError, match="exceeds max_len") as got:
        dstrings.encode_buffers(*dstrings.arrow_buffers(arr), validity,
                                longest - 1)
    assert str(got.value) == str(want.value)


def test_bytes_under_a_null_do_not_make_a_matrix_too_narrow():
    arr = _nulls_over_bytes()           # 'dead' lies under a null
    validity = np.asarray(arr.is_valid())
    bm, ln = dstrings.encode_buffers(*dstrings.arrow_buffers(arr),
                                     validity, 4)   # 'tail' is 4 bytes
    assert ln.tolist() == [2, 0, 1, 0, 4]
    assert bytes(bm[4]) == b"tail" and not bm[1].any()


def test_a_dictionary_encoded_column_is_decoded_once_on_arrows_side():
    values = ASCII + [None] + UTF8
    tbl = pa.table({"s": pa.array(values * 5).dictionary_encode()})
    col = arrow_to_host_batch(tbl).columns[0]
    arr = col.arrow_strings()
    assert isinstance(col, ArrowStringColumn) and arr.type == pa.string()
    objs = np.asarray((values * 5), dtype=object)
    _same(dstrings.encode_buffers(*dstrings.arrow_buffers(arr),
                                  col.validity),
          dstrings.encode(objs, col.validity))
    assert col.data.tolist() == values * 5


@pytest.mark.parametrize("case", ["ascii", "utf8_multi_byte", "nulls",
                                  "nulls_with_bytes_under_them",
                                  "all_null", "zero_rows",
                                  "sliced_non_zero_offset", "large_string"])
def test_the_device_sees_no_difference(case):
    """``host_to_device`` of the Arrow-backed column and of the objects:
    the same shapes, dtypes and bytes, so no program recompiles."""
    arr, _ = ARRAYS[case]()
    objs, validity = _objects(arr)
    schema = T.Schema([T.Field("s", T.STRING), T.Field("i", T.INT32)])
    ints = np.arange(len(arr), dtype=np.int32)
    from_arrow = HostBatch(schema, [ArrowStringColumn(T.STRING, arr, validity),
                                    HostColumn(T.INT32, ints)])
    from_objects = HostBatch(schema, [HostColumn(T.STRING, objs, validity),
                                      HostColumn(T.INT32, ints)])
    for widths in (None, {0: 40}):
        a = host_to_device(from_arrow, string_widths=widths)
        b = host_to_device(from_objects, string_widths=widths)
        assert a.num_rows == b.num_rows == len(arr)
        for ca, cb in zip(a.columns, b.columns):
            for xa, xb in ((ca.data, cb.data), (ca.validity, cb.validity),
                           (ca.lengths, cb.lengths)):
                if xa is None:
                    assert xb is None
                    continue
                assert xa.dtype == xb.dtype and xa.shape == xb.shape
                assert np.array_equal(np.asarray(xa), np.asarray(xb))
    assert from_arrow.columns[0]._objects is None   # no object was made


# -- (c) an Arrow-backed column against an object-backed one ----------
def _pair(values=None):
    values = values if values is not None \
        else (ASCII + [None] + UTF8 + ["", None]) * 12
    arr = pa.array(values, type=pa.string())
    objs, validity = _objects(arr)
    return (ArrowStringColumn(T.STRING, arr, validity),
            HostColumn(T.STRING, objs, validity))


def _equal_columns(got, want):
    assert got.dtype == want.dtype and got.num_rows == want.num_rows
    assert got.null_count == want.null_count
    assert (got.validity is None) == (want.validity is None)
    assert np.array_equal(got.is_valid(), want.is_valid())
    assert got.to_pylist() == want.to_pylist()
    assert got.data.dtype == object and got.data.shape == want.data.shape
    assert got.data.tolist() == want.data.tolist()


CUTS = {"whole": (0, None), "head": (0, 5), "middle": (7, 100),
        "tail_past_the_end": (200, 10**6), "nothing": (4, 4),
        "negative_start": (-9, None), "no_null_left": (0, 6)}


@pytest.mark.parametrize("cut", sorted(CUTS))
def test_slice_cuts_arrow_where_it_cuts_objects(cut):
    lazy, eager = _pair()
    start, stop = CUTS[cut]
    stop = lazy.num_rows if stop is None else stop
    got = lazy.slice(start, stop)
    assert got.arrow_strings() is not None and lazy._objects is None
    _equal_columns(got, eager.slice(start, stop))
    # and again, a slice of a slice
    _equal_columns(lazy.slice(start, stop).slice(1, 3),
                   eager.slice(start, stop).slice(1, 3))


PICKS = {
    "ints": np.array([5, 0, 0, 17, 3], dtype=np.int64),
    "int32": np.array([1, 2, 3], dtype=np.int32),
    "negative": np.array([-1, 0, -5], dtype=np.int64),
    "none": np.array([], dtype=np.int64),
    "mask": np.arange(15 * 12) % 3 == 0,
    "list": [4, 4, 2],
}


@pytest.mark.parametrize("pick", sorted(PICKS))
def test_take_picks_from_arrow_what_it_picks_from_objects(pick):
    lazy, eager = _pair()
    got = lazy.take(PICKS[pick])
    assert got.arrow_strings() is not None and lazy._objects is None
    _equal_columns(got, eager.take(PICKS[pick]))


def test_concat_takes_an_empty_part():
    lazy, eager = _pair()
    none = ArrowStringColumn(T.STRING, pa.array([], type=pa.string()))
    got = HostColumn.concat([none, lazy.slice(4, 4), lazy, none])
    assert got.arrow_strings() is not None
    _equal_columns(got, eager)
    _equal_columns(HostColumn.concat([none, none]), none)


@pytest.mark.parametrize("parts", ["all_arrow", "mixed", "large_and_small",
                                   "one_materialised"])
def test_concat_stays_on_arrows_side_when_every_part_is_there(parts):
    lazy, eager = _pair()
    other_lazy, other_eager = _pair(UTF8 * 3)
    if parts == "all_arrow":
        got = HostColumn.concat([lazy, other_lazy, lazy.slice(3, 9)])
        assert got.arrow_strings() is not None
    elif parts == "large_and_small":
        large = ArrowStringColumn(
            T.STRING, other_lazy.arrow_strings().cast(pa.large_string()))
        got = HostColumn.concat([lazy, large, lazy.slice(3, 9)])
        assert got.arrow_strings().type == pa.large_string()
    elif parts == "mixed":
        got = HostColumn.concat([lazy, other_eager, lazy.slice(3, 9)])
        assert got.arrow_strings() is None      # objects: materialised
    else:
        assert lazy.data is lazy.data
        got = HostColumn.concat([lazy, other_lazy, lazy.slice(3, 9)])
        assert got.arrow_strings() is None
    _equal_columns(got, HostColumn.concat([eager, other_eager,
                                           eager.slice(3, 9)]))


def test_rows_and_bytes_are_read_off_the_offsets():
    lazy, eager = _pair()
    schema = T.Schema([T.Field("s", T.STRING)])
    assert lazy.num_rows == eager.num_rows == 15 * 12
    # under 2048 rows the object column's sample is every row: equal
    assert lazy.string_bytes() == eager.string_bytes() == sum(
        len(v.encode("utf-8")) for v in eager.data if v is not None)
    assert HostBatch(schema, [lazy]).estimate_bytes() \
        == HostBatch(schema, [eager]).estimate_bytes()
    cut = lazy.slice(20, 90)
    assert cut.string_bytes() == eager.slice(20, 90).string_bytes()
    over_bytes = _nulls_over_bytes()
    col = ArrowStringColumn(T.STRING, over_bytes,
                            np.asarray(over_bytes.is_valid()))
    assert col.string_bytes() == len("xx") + len("y") + len("tail")
    assert ArrowStringColumn(
        T.STRING, pa.array([], type=pa.string())).string_bytes() == 0
    assert lazy._objects is None        # none of it made an object


def test_a_pickle_carries_the_objects_and_loads_as_a_plain_column():
    lazy, eager = _pair()
    back = pickle.loads(pickle.dumps(lazy))
    assert type(back) is HostColumn
    _equal_columns(back, pickle.loads(pickle.dumps(eager)))
    batch = HostBatch(T.Schema([T.Field("s", T.STRING)]), [lazy.slice(2, 40)])
    assert pickle.loads(pickle.dumps(batch)).to_pydict() \
        == {"s": eager.slice(2, 40).to_pylist()}


@pytest.mark.parametrize("kind", ["string", "large_string", "sliced"])
def test_back_to_arrow_equals_the_object_columns_table(kind):
    lazy, eager = _pair()
    if kind == "large_string":
        lazy = ArrowStringColumn(
            T.STRING, lazy.arrow_strings().cast(pa.large_string()),
            lazy.validity)
    elif kind == "sliced":
        lazy, eager = lazy.slice(5, 77), eager.slice(5, 77)
    schema = T.Schema([T.Field("s", T.STRING), T.Field("i", T.INT64)])
    ints = HostColumn(T.INT64, np.arange(lazy.num_rows, dtype=np.int64))
    got = host_batch_to_arrow(HostBatch(schema, [lazy, ints]))
    want = host_batch_to_arrow(HostBatch(schema, [eager, ints]))
    assert got.schema == want.schema and got.equals(want)


def test_reading_data_gives_what_the_scan_made_before():
    arr = pa.array(ASCII + [None] + UTF8)
    lazy = ArrowStringColumn(T.STRING, arr, np.asarray(arr.is_valid()))
    want = np.asarray(arr.to_pylist(), dtype=object)
    assert lazy._objects is None
    first = lazy.data
    assert first.dtype == object and first.tolist() == want.tolist()
    assert first[len(ASCII)] is None and lazy[len(ASCII)] is None
    assert lazy.data is first and lazy.arrow_strings() is arr
    # once the objects exist they are what is cut: no second conversion
    assert type(lazy.slice(1, 4)) is HostColumn
    assert type(lazy.take(np.array([0, 2]))) is HostColumn
    assert lazy.slice(1, 4).data.tolist() == want[1:4].tolist()
    with pytest.raises(AttributeError):
        lazy.data = want


# -- (d) two readers, one array ---------------------------------------
def test_two_threads_reading_data_at_once_get_one_array(monkeypatch):
    opened = []

    @contextmanager
    def slow_range(name, *a, **k):
        opened.append(name)
        time.sleep(0.2)       # the other reader arrives meanwhile
        yield

    monkeypatch.setattr(column_mod, "trace_range", slow_range)
    arr = pa.array([f"row {i}" for i in range(20000)])
    lazy = ArrowStringColumn(T.STRING, arr)
    start = threading.Barrier(4, timeout=60)
    got = [None] * 4

    def read(i):
        start.wait()
        got[i] = lazy.data

    threads = [threading.Thread(target=read, args=(i,)) for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert opened == ["HostStrings.materialize"]
    assert all(g is got[0] for g in got) and got[0][19999] == "row 19999"


# -- (b) through a session --------------------------------------------
ROWS = 3000


def _strings_table(n=ROWS):
    i = np.arange(n)
    brand = [f"Brand#{1 + k % 5}{1 + k % 3}" for k in i]
    kind = [None if k % 11 == 0 else UTF8[k % 6] + " " + ASCII[k % 6]
            for k in i]
    return pa.table({"k": i.astype(np.int64), "brand": brand, "kind": kind})


def _write(tmp_path, fmt, files=2):
    tbl = _strings_table()
    d = os.path.join(str(tmp_path), fmt)
    os.makedirs(d)
    per = ROWS // files
    for j in range(files):
        part = tbl.slice(j * per, per)
        path = os.path.join(d, f"part-{j}.{fmt}")
        if fmt == "parquet":
            pq.write_table(part, path, row_group_size=700,
                           use_dictionary=(j == 0))
        elif fmt == "orc":
            import pyarrow.orc as orc

            orc.write_table(part, path)
        else:
            import pyarrow.csv as pacsv

            pacsv.write_csv(part.select(["k", "brand"]), path)
    return d


def _read(sess, d, fmt):
    if fmt == "csv":
        return sess.read_csv(d, header=True)
    return getattr(sess, f"read_{fmt}")(d)


@pytest.mark.parametrize("fmt", ["parquet", "orc", "csv"])
def test_a_scan_uploads_its_strings_without_making_an_object(
        fmt, tmp_path, recorder):  # noqa: F811
    d = _write(tmp_path, fmt)
    sess = srt.Session(dict(TRACED))
    cols = ["k", "brand"] if fmt == "csv" else ["k", "brand", "kind"]
    got = _read(sess, d, fmt).select(*cols).collect()
    want = _strings_table().select(cols).to_pylist()
    assert sorted(got) == sorted(tuple(r[c] for c in cols) for r in want)
    m = sess.last_metrics
    n_strings = len(cols) - 1
    uploads = sum(1 for s in recorder.spans if s[0] == "HostToDevice")
    assert uploads >= 2
    assert m["HostToDeviceExec.stringColumnsFromArrow"] \
        == n_strings * uploads
    assert m["HostToDeviceExec.stringColumnsFromObjects"] == 0
    assert "HostStrings.materialize" not in recorder.names()
    assert {"ScanDecode.strings", "HostToDevice.strings"} \
        <= recorder.names()
    assert m["FileScanExec.decodedStringBytes"] == sum(
        len(r[c].encode("utf-8")) for r in want for c in cols[1:]
        if r[c] is not None)
    assert recorder.misnested == [] and recorder.still_open() == {}


def test_a_host_operator_over_the_scan_pays_once_a_column(
        tmp_path, recorder):  # noqa: F811
    """``rlike`` has no device form: with strict mode off the filter
    runs on the host over the scan's batches and reads ``brand``'s
    objects; ``kind`` is only carried, and is made when the result's
    rows are."""
    d = _write(tmp_path, "parquet")
    conf = {"spark.rapids.tpu.sql.trace.enabled": True}

    def query(sess):
        df = sess.read_parquet(d)
        return df.filter(df["brand"].rlike("Brand#[12]3")) \
            .select("k", "brand", "kind")

    sess = srt.Session(conf)
    assert "!" in query(sess).explain()
    got = query(sess).collect()
    batches = sess.last_metrics["FileScanExec.decodedBatches"]
    made = [s for s in recorder.spans if s[0] == "HostStrings.materialize"]
    # brand once a batch under the filter; kind at most once a batch
    assert batches <= len(made) <= 2 * batches
    want = [tuple(r.values()) for r in _strings_table().to_pylist()
            if r["brand"] in ("Brand#13", "Brand#23")]
    assert sorted(got, key=lambda r: r[0]) == want and len(want) > 100
    oracle = srt.Session(tpu_enabled=False)
    assert sorted(query(oracle).collect(), key=lambda r: r[0]) == want
    assert recorder.misnested == [] and recorder.still_open() == {}


def test_the_host_engine_answers_from_arrow_backed_scans(tmp_path):
    d = _write(tmp_path, "parquet")
    oracle = srt.Session(tpu_enabled=False)
    df = oracle.read_parquet(d)
    got = df.filter(df["kind"].is_not_null()).group_by("brand") \
        .agg(F.count("k").alias("n"), F.max("kind").alias("top")).collect()
    tbl = _strings_table().to_pandas()
    tbl = tbl[tbl.kind.notna()]
    want = tbl.groupby("brand").agg(n=("k", "count"), top=("kind", "max"))
    assert sorted(got) == sorted(
        (b, int(r.n), r.top) for b, r in want.iterrows())


@pytest.mark.parametrize("query", ["q1", "q3"])
def test_a_tpch_request_uploads_no_string_from_objects(query, tmp_path):
    """The benchmark's own q1 and q3 under their configuration's conf,
    at toy size (q16: ``tests/test_benchmark_q16.py``)."""
    import json

    from benchmark.harness import datagen, load_module

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "tpch_sf1_chip1.json")) as f:
        config = json.load(f)
    mod = load_module("queries", query)
    rows = {t: max(4, n // 2000) for t, n in config["rows"].items()}
    datagen.write_tables(str(tmp_path), sorted(mod.TABLES), rows, 2**31 + 33,
                         config["parquet"])
    sess = srt.Session(dict(config["conf"]))
    df = mod.build({t: sess.read_parquet(os.path.join(str(tmp_path), t))
                    for t in mod.TABLES})
    assert df.collect()
    m = sess.last_metrics
    assert m["HostToDeviceExec.stringColumnsFromArrow"] > 0
    assert m["HostToDeviceExec.stringColumnsFromObjects"] == 0
    assert m["FileScanExec.decodedStringBytes"] > 0
