"""The ``p``-th percentile of every request's wall time in the window, in
milliseconds (nearest rank)."""
import math


def read(run, params):
    times = sorted((r.end - r.start) * 1e3 for r in run.requests)
    if not times:
        return None
    rank = max(math.ceil(params["p"] / 100 * len(times)), 1)
    return times[rank - 1]
