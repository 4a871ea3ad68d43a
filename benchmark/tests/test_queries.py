"""Each query file's plain pandas ``reference()`` against the repo's
host engine (``Session(tpu_enabled=False)``) on the same tiny tables,
on the CPU.  A query file added later is picked up by its name."""
import glob
import os

import pytest

from benchmark.harness import compare, datagen, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
QUERIES = sorted(os.path.basename(p)[:-3] for p in glob.glob(
    os.path.join(os.path.dirname(HERE), "queries", "*.py")))
ROWS = {"lineitem": 12000, "orders": 3000, "customer": 300, "part": 400,
        "partsupp": 1600, "supplier": 20, "nation": 25, "region": 5}
LAYOUT = {"files_per_table": 2, "rows_per_row_group": 1024,
          "compression": "snappy"}


@pytest.fixture(scope="module")
def tables_dir(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("tpch") / "tables")
    made = datagen.write_tables(path, ["customer", "lineitem", "orders"],
                                ROWS, 2**31 + 12345, LAYOUT)
    assert {t: m["rows"] for t, m in made.items()} == \
        {t: ROWS[t] for t in made}
    return path


@pytest.mark.parametrize("name", QUERIES)
def test_reference_equals_the_host_engine(name, tables_dir):
    import pyarrow.parquet as pq

    import spark_rapids_tpu as srt

    q = load_module("queries", name)
    frames = {t: pq.read_table(os.path.join(tables_dir, t),
                               columns=cols).to_pandas(date_as_object=False)
              for t, cols in q.TABLES.items()}
    want = q.reference(frames)
    assert want, "the tiny tables select nothing: the test shows nothing"
    host = srt.Session(tpu_enabled=False)
    got = q.build({t: host.read_parquet(os.path.join(tables_dir, t))
                   for t in q.TABLES}).collect()
    assert compare.difference(want, got, q.ORDERED, 1e-9) is None
    assert q.min_bytes(ROWS) > 0


def test_same_seed_same_tables_other_seed_other_tables(tmp_path):
    import pyarrow.parquet as pq

    def make(where, seed):
        datagen.write_tables(str(tmp_path / where), ["customer"], ROWS,
                             seed, LAYOUT)
        return pq.read_table(str(tmp_path / where / "customer"))

    assert make("a", 7).equals(make("b", 7))
    assert not make("c", 8).equals(make("a2", 7))
