"""The 90th percentile of the window's request seconds (linear
interpolation between the order statistics): with some tens of
samples a window it is the highest percentile worth the name."""
import numpy as np

UNIT = "s"


def reduce(window):
    return float(np.percentile(window["samples"], 90)) \
        if window["samples"] else None
