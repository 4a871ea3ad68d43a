"""TPC-H ``nation``: the specification's 25 rows (clause 4.2.3 and its
appendix: each nation's key, name and region), ``n_comment`` drawn by
the benchmark's generator.  A rehearsal that asks for fewer rows gets
the first of them in key order."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g

STREAM = 7
#: (n_nationkey, n_name, n_regionkey), as the specification lists them
NATIONS = [
    (0, "ALGERIA", 0), (1, "ARGENTINA", 1), (2, "BRAZIL", 1),
    (3, "CANADA", 1), (4, "EGYPT", 4), (5, "ETHIOPIA", 0),
    (6, "FRANCE", 3), (7, "GERMANY", 3), (8, "INDIA", 2),
    (9, "INDONESIA", 2), (10, "IRAN", 4), (11, "IRAQ", 4),
    (12, "JAPAN", 2), (13, "JORDAN", 4), (14, "KENYA", 0),
    (15, "MOROCCO", 0), (16, "MOZAMBIQUE", 0), (17, "PERU", 1),
    (18, "CHINA", 2), (19, "ROMANIA", 3), (20, "SAUDI ARABIA", 4),
    (21, "VIETNAM", 2), (22, "RUSSIA", 3), (23, "UNITED KINGDOM", 3),
    (24, "UNITED STATES", 1),
]


def generate(rows, seed):
    kept = NATIONS[:rows["nation"]]
    rng = g.rng_for(seed, STREAM)
    return pa.table({
        "n_nationkey": np.array([k for k, _, _ in kept], dtype=np.int64),
        "n_name": pa.array([n for _, n, _ in kept], pa.string()),
        "n_regionkey": np.array([r for _, _, r in kept], dtype=np.int64),
        "n_comment": g.comments(rng, len(kept), 4),
    })
