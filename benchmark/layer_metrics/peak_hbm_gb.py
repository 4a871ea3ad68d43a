"""``memory_stats()["peak_bytes_in_use"]`` at the end of the run, the
fullest device, in GB (1e9 bytes)."""
UNIT, LAYER, MOVES = "GB", "memory model", "query_s_p50"


def reduce(trace, notes):
    peaks = notes["memory_peak_bytes"]
    return max(peaks) / 1e9 if peaks else None
