"""The sum of several of the program's counters, over the window, for each
request done: ``counter_per_request`` for a quantity the program counts
under more than one name (bytes up and bytes down the link). A counter that
did not move is absent from the run and counts 0; when none of them moved
there is nothing to read."""


def read(run, params):
    done = len(run.done)
    found = [c for c in params["counters"] if c in run.counters]
    if not done or not found:
        return None
    return sum(run.counters[c] for c in found) / done
