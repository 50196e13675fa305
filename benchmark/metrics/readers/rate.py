"""Work completed over the window's whole time, which runs to the end of
the last request started in it. ``of``: ``rows`` (rows of the requests that
succeeded) or ``requests``."""


def read(run, params):
    done = run.done
    if not done or run.window_s <= 0:
        return None
    amount = sum(r.rows for r in done) if params["of"] == "rows" else len(done)
    return amount / run.window_s
