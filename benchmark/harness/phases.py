"""The device's seconds by the names the program gives them.

``harness/trace.py`` keeps ``(start, end, name)`` of each device event
and so cannot tell a gather from a scatter, nor a sort's gathers from a
segment's.  The program can: its ``telemetry/device_trace.py`` reads the
events' metadata (JAX's op path with the program's ``device_phase``
scopes in it), and its ``utils/tracing.last_profile_dir()`` says where
the profiler session around the last traced request wrote.  This file
asks both, once a run, and hands the reduction to the readers in
``layer_metrics/``: leaf seconds a request on the busiest device,
clipped to the traced window.

A program that has no ``device_trace``, or noted no directory (one from
before these existed), reads 0.0 in every reader: never ``None``, never
an exception.
"""
import functools

from . import trace as harness_trace


@functools.lru_cache(maxsize=1)
def _loaded(path):
    from spark_rapids_tpu.telemetry import device_trace

    # the window comes from the harness's own markers: the host plane,
    # most of the file, stays unread
    return device_trace.load(path, marker=None)


@functools.lru_cache(maxsize=4)
def _reduced(path, device, by, window, queries):
    """{key: seconds a request} over every program of ``device``."""
    from spark_rapids_tpu.telemetry import device_trace

    return device_trace.seconds_by_key(_loaded(path), device, by, window,
                                       queries)


def seconds(trace, by, key):
    """Leaf seconds a request of the ops whose ``by`` (``phase``,
    ``primitive`` ...) is ``key``, any program, on the busiest device
    of ``trace`` (a ``harness.trace.Trace``)."""
    if not trace.has_device or not trace.queries:
        return 0.0
    try:
        from spark_rapids_tpu.telemetry import device_trace  # noqa: F401
        from spark_rapids_tpu.utils import tracing

        directory = tracing.last_profile_dir()
    except (ImportError, AttributeError):
        return 0.0
    if not directory:
        return 0.0
    try:
        path = harness_trace.find_xplane(directory)
    except FileNotFoundError:
        return 0.0
    busiest = max(trace.active_devices, key=trace.busy_s)
    if busiest not in _loaded(path).devices:
        return 0.0
    lo, hi = trace.window           # nanoseconds; the program's are ps
    return _reduced(path, busiest, by, (int(lo * 1000), int(hi * 1000)),
                    trace.queries).get(key, 0.0)
