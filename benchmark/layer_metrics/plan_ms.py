"""Host milliseconds to plan one query (optimise, physical plan, device
overrides, transitions), timed by the benchmark around
``Session.physical_plan`` before each traced request; the median."""
import statistics

UNIT, LAYER, MOVES = "ms", "plan / rewrite / fusion", "query_s_p50"


def reduce(trace, notes):
    return 1e3 * statistics.median(notes["plan_s"]) if notes["plan_s"] \
        else None
