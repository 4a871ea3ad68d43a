"""Records the small xplane ``test_device_phases.py`` reads (run once on
a chip: ``python tests/record_phases_xplane.py <out.xplane.pb.gz>``).

Two marked requests, each one call of ``phased`` over 2^20 rows:

* a ``fori_loop`` of three steps under the scope ``lexsort``, whose body
  gathers under a nested ``reorder`` (the innermost scope names an op's
  phase) and sorts under ``lexsort`` alone: on the device the loop is
  one ``while`` event that wraps its body's events;
* one gather under ``reorder`` after the loop;
* two sums under no scope, the ``(unscoped)`` remainder."""
import gzip
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from spark_rapids_tpu.utils.tracing import device_phase  # noqa: E402

MARKER = "bench.query"
ROWS = 1 << 20


@jax.jit
def phased(x, perm):
    with device_phase("lexsort"):
        def step(_, p):
            with device_phase("reorder"):
                key = x[p]
            return lax.sort((key, p), num_keys=1, is_stable=True)[1]

        perm = lax.fori_loop(0, 3, step, perm)
    with device_phase("reorder"):
        y = x[perm]
    return y[:128].sum() + (x * 2.0).sum()


def main(out):
    from benchmark.harness import trace

    x = jax.random.uniform(jax.random.PRNGKey(0), (ROWS,), jnp.float32)
    perm = jnp.arange(ROWS, dtype=jnp.int32)[::-1]
    phased(x, perm).block_until_ready()         # compiles
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(2):
            with jax.profiler.TraceAnnotation(MARKER):
                phased(x, perm).block_until_ready()
        jax.profiler.stop_trace()
        with open(trace.find_xplane(d), "rb") as src, \
                gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)


if __name__ == "__main__":
    main(sys.argv[1])
