"""Completed requests over the elapsed time of the window (to the end
of the request in flight when the clock ran out), an hour's worth."""
UNIT = "queries/h"


def reduce(window):
    if not window["samples"]:
        return None
    return 3600.0 * len(window["samples"]) / window["elapsed_s"]
