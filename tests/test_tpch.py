"""TPC-H-like q1..q22: CPU-oracle vs TPU-path equality.

Reference analogue: TpchLikeSparkSuite.scala — every query runs on the
small checked-in dataset and the plugin result must match CPU Spark.
Here each query is executed on a Session with tpu_enabled=False (host
numpy engine, the oracle) and tpu_enabled=True (rewrite engine + device
execs), and results are compared with the same sort/float tolerance
semantics as asserts.py.
"""
import json
import os

import pytest
from conftest import REPO

from spark_rapids_tpu.benchmarks import tpch, tpch_datagen
from spark_rapids_tpu.session import Session
from spark_rapids_tpu.testing.asserts import assert_rows_equal

SF = 0.0007
SEED = 7


def _run(qnum: int, tpu: bool):
    sess = Session(tpu_enabled=tpu)
    tables = tpch_datagen.dataframes(sess, sf=SF, seed=SEED)
    df = tpch.QUERIES[qnum](tables)
    return df.collect(), df.columns


# queries whose output has no total order (ties in sort keys / no sort)
_UNORDERED = {2, 5, 6, 10, 11, 13, 14, 16, 17, 18, 19, 21, 22}


@pytest.mark.parametrize("qnum", sorted(tpch.QUERIES))
def test_tpch_query_cpu_vs_tpu(qnum):
    cpu_rows, cols = _run(qnum, tpu=False)
    tpu_rows, _ = _run(qnum, tpu=True)
    assert_rows_equal(cpu_rows, tpu_rows,
                      ignore_order=qnum in _UNORDERED,
                      approximate_float=1e-6)


def test_tpch_q16_like_stays_on_device():
    """q16's `p_type NOT LIKE 'MEDIUM POLISHED%'` must lower onto the
    device byte-matrix kernels (reference keeps Like on GPU via regex
    translation, GpuOverrides.scala:326-371); strict test mode raises
    on any unexpected host fallback."""
    sess = Session({"spark.rapids.tpu.sql.test.enabled": True})
    tables = tpch_datagen.dataframes(sess, sf=SF, seed=SEED)
    rows = tpch.QUERIES[16](tables).collect()
    cpu_rows, _ = _run(16, tpu=False)
    assert_rows_equal(cpu_rows, rows, ignore_order=True,
                      approximate_float=1e-6)


def test_tpch_nonempty_coverage():
    """The generator must feed every query a non-trivial subset (guards
    against the suite silently comparing empty results everywhere)."""
    nonempty = 0
    for qnum in sorted(tpch.QUERIES):
        rows, _ = _run(qnum, tpu=False)
        if rows:
            nonempty += 1
    assert nonempty >= 18, f"only {nonempty}/22 queries returned rows"


# --------------------------------------------------------------------------
# the served path: Parquet on disk -> Session -> rows, as the benchmark's
# one-chip configuration runs it (strict mode, degrade ladder off)
# --------------------------------------------------------------------------
@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """The generator's tables written by its own Parquet writer, read
    back by a host session (the oracle) and by a device session under
    ``benchmark/configs/tpch_sf1_chip1.json``'s conf."""
    path = str(tmp_path_factory.mktemp("tpch_parquet"))
    host = Session(tpu_enabled=False)
    tpch_datagen.write_parquet(host, path, sf=SF, seed=SEED)
    with open(os.path.join(REPO, "benchmark", "configs",
                           "tpch_sf1_chip1.json")) as f:
        conf = json.load(f)["conf"]
    assert conf["spark.rapids.tpu.sql.test.enabled"] is True
    assert conf["spark.rapids.tpu.fault.degrade.enabled"] is False

    def tables(sess):
        return {name: sess.read_parquet(os.path.join(path, name))
                for name in sorted(os.listdir(path))}

    sess = Session(conf)
    yield sess, tables(sess), host, tables(host)
    sess.close()
    host.close()


def _served_query(qnum):
    def case(sess, tables, host, host_tables):
        df = tpch.QUERIES[qnum](tables)
        want = tpch.QUERIES[qnum](host_tables).collect()
        for warm in (False, True):
            got = sess.execute(df.plan).to_rows()
            assert_rows_equal(want, got, ignore_order=qnum in _UNORDERED,
                              approximate_float=1e-6)
            m = sess.last_metrics
            assert m["fault.degradeLevel"] == 0
            if warm:
                assert m["kernelCache.misses"] == 0, \
                    f"q{qnum}'s second execution compiled programs"
                assert m["kernelCache.hits"] > 0
    return case


def _served_submit(sess, tables, host, host_tables):
    want = tpch.QUERIES[6](host_tables).collect()
    got = sess.submit(tpch.QUERIES[6](tables)).result(timeout=300)
    assert_rows_equal(want, got.to_rows(), ignore_order=True,
                      approximate_float=1e-6)


def _served_prepared(sess, tables, host, host_tables):
    """q6's discount band as parameters: re-bound to other literals the
    statement answers as the host engine does with the same binding, and
    not as it did with its defaults."""
    stmt = sess.prepare(tpch.QUERIES[6](tables))
    assert 0.05 in stmt.defaults and 0.07 in stmt.defaults
    rebound = [0.03 if v == 0.05 else 0.05 if v == 0.07 else v
               for v in stmt.defaults]
    got = stmt.execute(rebound).to_rows()
    want = host.execute(host.prepare(tpch.QUERIES[6](host_tables))
                        .bind(rebound)).to_rows()
    assert_rows_equal(want, got, ignore_order=True,
                      approximate_float=1e-6)
    assert got != stmt.execute().to_rows()


@pytest.mark.parametrize("case", [
    pytest.param(_served_query(6), id="q6"),
    pytest.param(_served_query(1), id="q1"),
    pytest.param(_served_query(3), id="q3"),
    pytest.param(_served_query(16), id="q16"),
    pytest.param(_served_submit, id="submit"),
    pytest.param(_served_prepared, id="prepared"),
])
def test_served_path_from_parquet(served, case):
    case(*served)
