"""Foundation tests: types, columns, hashing, transfers, config."""
import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.config import TpuConf, dump_markdown
from spark_rapids_tpu.data.column import (
    HostBatch,
    HostColumn,
    bucket_rows,
    device_to_host,
    host_to_device,
)
from spark_rapids_tpu.utils import hashing


def test_type_gate():
    assert T.is_supported_type(T.INT32)
    assert T.is_supported_type(T.STRING)
    assert T.is_supported_type(T.TIMESTAMP, session_zone_utc=True)
    assert not T.is_supported_type(T.TIMESTAMP, session_zone_utc=False)


def test_promote():
    assert T.promote(T.INT32, T.INT64) == T.INT64
    assert T.promote(T.INT64, T.FLOAT32) == T.FLOAT64
    assert T.promote(T.INT8, T.FLOAT32) == T.FLOAT32


def test_host_column_roundtrip():
    c = HostColumn.from_pylist([1, None, 3], T.INT32)
    assert c.to_pylist() == [1, None, 3]
    assert c.null_count == 1
    s = HostColumn.from_pylist(["a", None, "xyz"], T.STRING)
    assert s.to_pylist() == ["a", None, "xyz"]


def test_bucket_rows():
    assert bucket_rows(0) == 128
    assert bucket_rows(128) == 128
    assert bucket_rows(129) == 256
    assert bucket_rows(5000) == 8192


def test_device_roundtrip():
    batch = HostBatch.from_pydict({
        "i": [1, None, 3, -5],
        "f": [1.5, float("nan"), None, -0.0],
        "s": ["abc", "", None, "Ünïcode"],
        "b": [True, False, None, True],
    }, T.Schema([
        T.Field("i", T.INT64), T.Field("f", T.FLOAT64),
        T.Field("s", T.STRING), T.Field("b", T.BOOL)]))
    db = host_to_device(batch)
    assert db.padded_rows == 128
    back = device_to_host(db)
    assert back.column("i").to_pylist() == [1, None, 3, -5]
    f = back.column("f").to_pylist()
    assert f[0] == 1.5 and np.isnan(f[1]) and f[2] is None and f[3] == 0.0
    assert back.column("s").to_pylist() == ["abc", "", None, "Ünïcode"]
    assert back.column("b").to_pylist() == [True, False, None, True]


def _ref_murmur3_long(v, seed=42):
    """Scalar reference implementation for cross-checking."""
    def mix_k1(k1):
        k1 = (k1 * 0xCC9E2D51) & 0xFFFFFFFF
        k1 = ((k1 << 15) | (k1 >> 17)) & 0xFFFFFFFF
        return (k1 * 0x1B873593) & 0xFFFFFFFF

    def mix_h1(h1, k1):
        h1 ^= k1
        h1 = ((h1 << 13) | (h1 >> 19)) & 0xFFFFFFFF
        return (h1 * 5 + 0xE6546B64) & 0xFFFFFFFF

    def fmix(h1, length):
        h1 ^= length
        h1 ^= h1 >> 16
        h1 = (h1 * 0x85EBCA6B) & 0xFFFFFFFF
        h1 ^= h1 >> 13
        h1 = (h1 * 0xC2B2AE35) & 0xFFFFFFFF
        h1 ^= h1 >> 16
        return h1

    u = v & 0xFFFFFFFFFFFFFFFF
    h = mix_h1(seed, mix_k1(u & 0xFFFFFFFF))
    h = mix_h1(h, mix_k1(u >> 32))
    return fmix(h, 8)


def test_murmur3_long():
    vals = np.asarray([0, 1, -1, 42, 2**40, -(2**40)], dtype=np.int64)
    c = HostColumn(T.INT64, vals)
    h = hashing.hash_batch_np([c]).view(np.uint32)
    for i, v in enumerate(vals):
        assert int(h[i]) == _ref_murmur3_long(int(v)), f"mismatch at {v}"


def test_murmur3_string_matches_known():
    # Spark: SELECT hash('abc') == murmur3(utf8 'abc', seed 42)
    c = HostColumn.from_pylist(["abc", "", "a", "abcd", "hello world"],
                               T.STRING)
    h = hashing.hash_batch_np([c])
    # cross-check against pure-python reference
    def ref_bytes(b, seed=42):
        h1 = seed
        n = len(b)
        def mix_k1(k1):
            k1 = (k1 * 0xCC9E2D51) & 0xFFFFFFFF
            k1 = ((k1 << 15) | (k1 >> 17)) & 0xFFFFFFFF
            return (k1 * 0x1B873593) & 0xFFFFFFFF
        def mix_h1(h1, k1):
            h1 ^= k1
            h1 = ((h1 << 13) | (h1 >> 19)) & 0xFFFFFFFF
            return (h1 * 5 + 0xE6546B64) & 0xFFFFFFFF
        for blk in range(n // 4):
            word = int.from_bytes(b[blk * 4:blk * 4 + 4], "little")
            h1 = mix_h1(h1, mix_k1(word))
        for i in range((n // 4) * 4, n):
            byte = b[i]
            if byte >= 128:
                byte -= 256
            h1 = mix_h1(h1, mix_k1(byte & 0xFFFFFFFF))
        h1 ^= n
        h1 ^= h1 >> 16
        h1 = (h1 * 0x85EBCA6B) & 0xFFFFFFFF
        h1 ^= h1 >> 13
        h1 = (h1 * 0xC2B2AE35) & 0xFFFFFFFF
        h1 ^= h1 >> 16
        return h1
    for i, s in enumerate(["abc", "", "a", "abcd", "hello world"]):
        assert int(h[i].view(np.uint32)) == ref_bytes(s.encode()), s


def test_murmur3_device_matches_host():
    import jax.numpy as jnp  # noqa: F401

    batch = HostBatch.from_pydict({
        "i": [1, None, 3, -5, 2**40],
        "s": ["abc", None, "", "hello world", "Ünïcode"],
        "d": [1.5, -0.0, None, 3.25, float("nan")],
    }, T.Schema([T.Field("i", T.INT64), T.Field("s", T.STRING),
                 T.Field("d", T.FLOAT64)]))
    host_h = hashing.hash_batch_np(batch.columns)
    db = host_to_device(batch)
    dev_h = np.asarray(hashing.hash_device_batch(db.columns))[:5]
    np.testing.assert_array_equal(host_h, dev_h)


def test_conf_registry_and_docs():
    conf = TpuConf({"spark.rapids.tpu.sql.batchSizeBytes": "1024"})
    assert conf.batch_size_bytes == 1024
    assert conf.is_sql_enabled
    md = dump_markdown()
    assert "spark.rapids.tpu.sql.enabled" in md


def test_upload_roundtrip_every_dtype_family():
    """host_to_device sends a batch's arrays up in one batched put; the
    round trip through the device must be exact for every dtype family
    (validity, string byte matrices and lengths included)."""
    import numpy as np

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.data.column import (HostBatch, HostColumn,
                                              device_to_host,
                                              host_to_device)

    cols = {
        "i64": np.asarray([-1, 0, 2**62], dtype=np.int64),
        "f64": np.asarray([1.5, -0.0, float("nan")], dtype=np.float64),
        "i32": np.asarray([7, -7, 0], dtype=np.int32),
        "i8": np.asarray([-1, -128, 127], dtype=np.int8),
        "b": np.asarray([True, False, True]),
    }
    hb = HostBatch(T.Schema([T.Field(n, T.from_numpy(a.dtype))
                             for n, a in cols.items()]),
                   [HostColumn.from_numpy(a) for a in cols.values()])
    rt = device_to_host(host_to_device(hb))
    for a, c in zip(cols.values(), rt.columns):
        np.testing.assert_array_equal(a, c.data)
        assert c.data.dtype == a.dtype

    hb = HostBatch.from_pydict({
        "i": [1, None, 3], "f": [0.5, 2.5, None],
        "s": ["ab", None, "xyz"], "b": [True, False, None],
    })
    rt = device_to_host(host_to_device(hb))
    assert rt.to_rows() == hb.to_rows()


def test_local_scan_upload_cache(monkeypatch):
    """Repeated collects of the same plan reuse the cached device
    upload of an immutable in-memory source; a partially-drained
    partition (limit) is never cached."""
    import spark_rapids_tpu.exec.transitions as tr
    from spark_rapids_tpu import Session, f
    from spark_rapids_tpu.data import column as dc

    calls = {"n": 0}
    orig = dc.host_to_device

    def counting(hb, *a, **k):
        calls["n"] += 1
        return orig(hb, *a, **k)

    monkeypatch.setattr(tr, "host_to_device", counting)
    sess = Session()
    df = sess.create_dataframe(
        {"k": list(range(100)), "v": [float(i) for i in range(100)]})
    # a limit abandons its read early -> partial partitions must NOT
    # be published to the cache
    lim = df.select("k").limit(1).collect()
    assert len(lim) == 1
    q = df.group_by("k").agg(f.sum("v").alias("s"))
    a = sorted(q.collect())
    first = calls["n"]
    assert first > 0
    b = sorted(q.collect())
    assert a == b
    assert calls["n"] == first, \
        "second collect must not re-upload the cached source"
