"""Device seconds a query in the phase ``lexsort``
(``ops/kernels/segment.py:sort_permutation``: the one-word sorts and the
in-loop gathers of the next word by the running permutation), in any
program: the aggregate's, the joins', the sort's, a mesh stage's alike.
Leaf seconds on the busiest device, read from the ops' metadata by the
program's own ``telemetry/device_trace.py`` (``harness/phases.py``).
0.0 where the program names no such scope or says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "phase", "lexsort")
