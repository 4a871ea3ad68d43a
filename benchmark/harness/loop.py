"""The traffic generator and the measured window.

One general generator reads a traffic file (``benchmark/traffic/*.json``):

    {"loop": "closed", "clients": 1, "queries": ["q6"], ...}

``queries`` is the multiset one round of the schedule holds; the seed
only shuffles the round, so every seed offers the same work in another
order.  A closed loop with one client is the only shape there is code
for: the client sends its next request when the last one's rows are on
the host.  Another shape is refused, not approximated.
"""
import contextlib
import random
import time

#: seconds of requests the profiler stays on for in a traced run (at
#: least one whole request, however long)
TRACE_TARGET_S = 8.0


def schedule(traffic, seed):
    if traffic.get("loop") != "closed" or traffic.get("clients") != 1:
        raise ValueError(
            "the generator offers a closed loop with one client; "
            f"this mix asks for loop={traffic.get('loop')!r} "
            f"clients={traffic.get('clients')!r}")
    round_ = list(traffic["queries"])
    if not round_:
        raise ValueError("the mix names no query")
    random.Random(seed).shuffle(round_)
    return round_


class Profiler:
    """``jax.profiler`` around a steady part of the window: on before
    request ``start_at``, off after the first request that brings the
    traced seconds to TRACE_TARGET_S.  ``before_request`` runs just
    ahead of each traced request, outside its span and its time."""

    def __init__(self, directory, start_at, marker, before_request=None):
        self.directory = directory
        self.start_at = start_at
        self.marker = marker
        self.before_request = before_request
        self.on = False
        self.done = False
        self.traced_s = 0.0
        self.traced = []      # the queries traced, in order

    def span(self, index, query):
        import jax

        if self.done or index < self.start_at:
            return contextlib.nullcontext()
        if not self.on:
            jax.profiler.start_trace(self.directory)
            self.on = True
        if self.before_request is not None:
            self.before_request(query)
        self.traced.append(query)
        return jax.profiler.TraceAnnotation(self.marker)

    def after(self, seconds):
        if self.on:
            self.traced_s += seconds
            if self.traced_s >= TRACE_TARGET_S:
                self.stop()

    def stop(self):
        import jax

        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def run_window(round_, seconds, run, check, profiler=None):
    """Send requests one after another while the clock is under
    ``seconds``; the one in flight is finished and counted.  ``run(q)``
    is timed from the call to its rows; ``check(q, rows)`` returns the
    request's faults (an empty list is a correct answer) and is not in
    the request's time."""
    samples, failed, errors = [], 0, []
    start = time.perf_counter()
    index = 0
    while time.perf_counter() - start < seconds:
        query = round_[index % len(round_)]
        span = profiler.span(index, query) if profiler \
            else contextlib.nullcontext()
        t0 = time.perf_counter()
        try:
            with span:
                rows = run(query)
            took = time.perf_counter() - t0
            faults = check(query, rows)
        except Exception as exc:   # a request that raises has failed
            took = time.perf_counter() - t0
            faults = [f"{type(exc).__name__}: {exc}"]
        if profiler:
            profiler.after(took)
        if faults:
            failed += 1
            errors.extend(f"request {index} ({query}): {f}"[:300]
                          for f in faults)
        else:
            samples.append(took)
        index += 1
    elapsed = time.perf_counter() - start
    if profiler:
        profiler.stop()
    return {"attempted": index, "failed": failed, "samples": samples,
            "elapsed_s": elapsed, "errors": errors[:10]}
