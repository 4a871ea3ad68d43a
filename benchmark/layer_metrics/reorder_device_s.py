"""Device seconds a query in the phase ``reorder`` (rows or words read
through a permutation: ``gather_column`` / ``gather_batch``,
``reduce_sorted``'s stacks brought into sorted order, a join side's
payload, the key words a probe reads at its order), in any program.
Leaf seconds on the busiest device, read from the ops' metadata by the
program's own ``telemetry/device_trace.py`` (``harness/phases.py``).
0.0 where the program names no such scope or says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "phase", "reorder")
