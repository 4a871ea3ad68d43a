"""Median seconds from ``execute`` to rows on the host, over every
completed request of the window (the sample count is on the ``window``
note)."""
import statistics

UNIT = "s"


def reduce(window):
    return statistics.median(window["samples"]) if window["samples"] \
        else None
