"""One sum of the program's counters over another, over the window, as a
percentage."""


def read(run, params):
    den = sum(run.counters.get(c, 0) for c in params["den"])
    if not den:
        return None
    return 100.0 * sum(run.counters.get(c, 0) for c in params["num"]) / den
