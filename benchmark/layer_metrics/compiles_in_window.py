"""XLA compiles and persistent-cache reads inside the measured window:
0 where the warm-up reached every program the window runs; anything
above is a program it did not reach."""
UNIT, LAYER, MOVES = "count", "compile cache", "query_s_p50"


def reduce(trace, notes):
    w = notes["window_compiles"]
    return w["xla_compiles"] + w["persistent_cache_hits"] \
        + w["persistent_cache_misses"]
