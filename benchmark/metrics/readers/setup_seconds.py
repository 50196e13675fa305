"""``setup_s``: process start to window start, compiles included."""


def read(run, params):
    return run.setup_s
