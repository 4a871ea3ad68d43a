"""TPC-H ``part``.  Where Q16 reads a column it follows the
specification (clause 4.2.3): ``p_brand`` ``Brand#MN`` with M, N in
1..5, ``p_type`` three syllables of the specification's 6 x 5 x 5 lists,
``p_size`` 1..50, each drawn evenly.  The columns no query here reads
keep the in-repo generator's shorter text (NOT dbgen)."""
import numpy as np
import pyarrow as pa

from benchmark.harness import datagen as g

STREAM = 4
TYPE_S1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_S2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_S3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
TYPES = [f"{a} {b} {c}" for a in TYPE_S1 for b in TYPE_S2 for c in TYPE_S3]
BRANDS = [f"Brand#{m}{n}" for m in range(1, 6) for n in range(1, 6)]
MFGRS = [f"Manufacturer#{m}" for m in range(1, 6)]
CONTAINERS = [f"{a} {b}" for a in ["SM", "LG", "MED", "JUMBO", "WRAP"]
              for b in ["CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM"]]
COLORS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
          "black", "blanched", "blue", "blush", "brown", "burlywood",
          "chartreuse", "chiffon", "chocolate", "coral", "cornflower",
          "cream", "cyan", "dark", "deep", "dim", "dodger", "drab",
          "firebrick", "floral", "forest", "frosted", "gainsboro",
          "ghost", "goldenrod", "green", "grey", "honeydew", "hot",
          "indian", "ivory", "khaki", "lace", "lavender"]
#: the widest ``p_type``: a byte matrix is as wide as its batch's
#: longest string, so every batch has to hold one of these
TYPE_WIDTH = max(map(len, TYPES))


def generate(rows, seed):
    n = rows["part"]
    rng = g.rng_for(seed, STREAM)
    key = np.arange(1, n + 1, dtype=np.int64)
    brand = rng.integers(0, 25, n, dtype=np.int32)
    return pa.table({
        "p_partkey": key,
        "p_name": g.pick(rng, n, [f"{a} {b}" for a in COLORS
                                  for b in COLORS if a != b]),
        # the specification's Manufacturer#M is the brand's M
        "p_mfgr": g.from_vocabulary(brand // 5, MFGRS),
        "p_brand": g.from_vocabulary(brand, BRANDS),
        "p_type": g.pick(rng, n, TYPES),
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_container": g.pick(rng, n, CONTAINERS),
        "p_retailprice": np.round(
            900 + (key % 1000) * 0.1 + (key % 100), 2).astype(np.float64),
        "p_comment": g.comments(rng, n, 2),
    })
