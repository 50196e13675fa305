"""The mean over the window's requests of a sum of numbers the request
recorded: ``keys`` are dotted paths into its ``info`` (``phases.join_ms``).
A request that lacks one of them makes the metric silent: a MERGE that took
the host join has no device phases to report."""


def _get(info, path):
    for part in path.split("."):
        if not isinstance(info, dict) or part not in info:
            return None
        info = info[part]
    return info


def read(run, params):
    sums = []
    for r in run.done:
        parts = [_get(r.info, k) for k in params["keys"]]
        if any(p is None for p in parts):
            return None
        sums.append(sum(parts))
    return sum(sums) / len(sums) if sums else None
