"""Device seconds a query in the phase ``strings.match``
(``ops/stringexprs.py:Like.eval_tpu``: the device LIKE matcher's
``startswith`` / ``locate_from`` / ``endswith`` chain over a string
column's byte matrix), in any program.  Leaf seconds on the busiest
device, read from the ops' metadata by the program's own
``telemetry/device_trace.py`` (``harness/phases.py``).  0.0 where the
program names no such scope or says nothing of its trace."""
from benchmark.harness import phases

UNIT, LAYER, MOVES = "s/query", "kernels", "query_s_p50"


def reduce(trace, notes):
    return phases.seconds(trace, "phase", "strings.match")
