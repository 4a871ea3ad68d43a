"""Device seconds a query in the phase ``segments`` (a group-by's or a
join's change flags and segmented scans, and ``reduce_sorted``'s sort of
the segments' ends and its one-row-a-segment reads: the reads stand in a
switch over read widths where the program has one, under ``readTier.<rows>``
scopes that take nothing out of the phase), in any program.  Leaf
seconds on the busiest device, read from the ops' metadata by the
program's own ``telemetry/device_trace.py`` (``harness/phases.py``).
0.0 where the program names no such scope or says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "phase", "segments")
