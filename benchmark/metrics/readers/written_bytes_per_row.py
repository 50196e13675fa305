"""Bytes added under the table's directory in the window over the rows of
the requests that succeeded."""


def read(run, params):
    rows = sum(r.rows for r in run.done)
    return run.bytes_written / rows if rows else None
