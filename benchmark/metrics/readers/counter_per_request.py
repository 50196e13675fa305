"""A counter of the program, over the window, for each request done."""


def read(run, params):
    done = len(run.done)
    if not done or params["counter"] not in run.counters:
        return None
    return run.counters[params["counter"]] / done
