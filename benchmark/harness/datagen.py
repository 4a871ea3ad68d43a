"""Tables from ``--seed``, written as Parquet: the benchmark's own copy.

The distributions are those of the repo's generator
(``spark_rapids_tpu/benchmarks/tpch_datagen.py``: "NOT dbgen", value
ranges and vocabularies shaped so every TPC-H query selects something),
rewritten so that a table is made in bulk: numbers by one numpy draw a
column, strings as dictionary arrays over a small vocabulary.  The
original builds 6M comment strings in a Python loop, a minute at SF 1;
every run of every cell pays for its data, so here it is seconds.

A table is a file of its own under ``benchmark/tables/<name>.py`` with
``generate(rows, seed) -> pyarrow.Table``; ``rows`` holds every table's
row count at the configuration's scale.  This module holds what they
share and the writer.  Nothing is kept between runs: the caller gives a
fresh directory and removes it.
"""
import datetime
import os
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from benchmark.harness import load_module

EPOCH = datetime.date(1970, 1, 1)

COMMENT_WORDS = ["carefully", "quickly", "furiously", "slyly", "blithely",
                 "express", "regular", "final", "ironic", "pending",
                 "bold", "even", "silent", "unusual", "special",
                 "requests", "deposits", "packages", "accounts", "ideas"]


def days(y, m, d):
    return (datetime.date(y, m, d) - EPOCH).days


def rng_for(seed, stream):
    """One independent stream for each (seed, stream): a table that
    needs another table's column draws it again from that stream and
    not the whole table."""
    return np.random.default_rng([int(seed), int(stream)])


def pick(rng, n, vocabulary):
    """``n`` strings drawn evenly from ``vocabulary``, as an Arrow
    dictionary array: no string is copied until the Parquet encoder
    writes the column (as a plain string column, see ``_write_files``)."""
    return from_vocabulary(
        rng.integers(0, len(vocabulary), n, dtype=np.int32), vocabulary)


def from_vocabulary(indices, vocabulary):
    return pa.DictionaryArray.from_arrays(
        pa.array(indices, pa.int32()), pa.array(vocabulary, pa.string()))


def comments(rng, n, words):
    """``words`` comment words a row.  Two-word phrases are taken from
    the 400 there are; longer ones are two-word halves joined."""
    pairs = [f"{a} {b}" for a in COMMENT_WORDS for b in COMMENT_WORDS]
    if words == 2:
        return pick(rng, n, pairs)
    if words != 4:
        raise ValueError("comments are 2 or 4 words")
    import pyarrow.compute as pc

    return pc.binary_join_element_wise(
        pick(rng, n, pairs).cast(pa.string()),
        pick(rng, n, pairs).cast(pa.string()), " ")


def numbered(prefix, keys, width=9):
    """``Customer#000000042`` for each key."""
    import pyarrow.compute as pc

    digits = pc.utf8_lpad(pc.cast(pa.array(keys), pa.string()), width, "0")
    return pc.binary_join_element_wise(prefix, digits, "")


def money(rng, lo, hi, n):
    return np.round(rng.uniform(lo, hi, n), 2)


def _write_files(table, directory, layout):
    os.makedirs(directory)
    files = int(layout["files_per_table"])
    per = -(-table.num_rows // files)
    for i in range(files):
        pq.write_table(table.slice(i * per, per),
                       os.path.join(directory, f"part-{i:05d}.parquet"),
                       compression=layout["compression"],
                       row_group_size=int(layout["rows_per_row_group"]),
                       # without the Arrow schema a dictionary array reads
                       # back as the plain string column it is in Parquet
                       store_schema=False)
    with open(os.path.join(directory, "_SUCCESS"), "w"):
        pass


def write_tables(directory, names, rows, seed, layout):
    """Generate the named tables and write each as
    ``<directory>/<table>/part-*.parquet``.  Returns what was made:
    rows, bytes on disk and seconds a table."""
    made = {}

    def one(name):
        t0 = time.perf_counter()
        table = load_module("tables", name).generate(rows, seed)
        if table.num_rows != rows[name]:
            raise ValueError(f"{name}: generated {table.num_rows} rows, "
                             f"the configuration says {rows[name]}")
        path = os.path.join(directory, name)
        _write_files(table, path, layout)
        nbytes = sum(os.path.getsize(os.path.join(path, f))
                     for f in os.listdir(path))
        made[name] = {"rows": table.num_rows, "parquet_bytes": nbytes,
                      "seconds": time.perf_counter() - t0}

    # numpy and Arrow release the interpreter lock in the bulk draws and
    # in the Parquet encoder, so tables overlap; few threads, since the
    # one-chip machine's cores are shared
    with ThreadPoolExecutor(max_workers=min(3, len(names))) as pool:
        for f in [pool.submit(one, n) for n in names]:
            f.result()
    return made
