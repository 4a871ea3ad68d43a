"""Records the small xplane the reducer's test reads (run once on a
chip: ``python benchmark/tests/record_small_xplane.py <out.xplane.pb.gz>``).

Two marked requests.  Each runs ``scale`` on two shapes (two
fingerprints of one module name) and ``shift`` once, inside nested
spans ``outer`` > ``inner``, and sleeps 20 ms inside ``inner`` and
10 ms inside ``outer`` alone, so that the device's idle time has known
owners."""
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, __file__.rsplit("/benchmark/", 1)[0])
from benchmark.harness import trace  # noqa: E402


@jax.jit
def scale(x):
    return (x * 2.0).sum()


@jax.jit
def shift(x):
    return (x + 1.0).sum()


def request(a, b):
    with jax.profiler.TraceAnnotation("outer"):
        scale(a).block_until_ready()
        with jax.profiler.TraceAnnotation("inner"):
            scale(b).block_until_ready()
            time.sleep(0.020)
        shift(a).block_until_ready()
        time.sleep(0.010)


def main(out):
    a = jnp.ones((1 << 20,), jnp.float32)
    b = jnp.ones((1 << 21,), jnp.float32)
    request(a, b)                       # compiles
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(2):
            with jax.profiler.TraceAnnotation(trace.MARKER):
                request(a, b)
        jax.profiler.stop_trace()
        trace.keep(trace.find_xplane(d), out)


if __name__ == "__main__":
    main(sys.argv[1])
