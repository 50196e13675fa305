"""Milliseconds a request spent in the program's spans named ``spans``
(exact names), as a mean over the window's requests."""


def read(run, params):
    names = set(params["spans"])
    done = run.done
    total = sum(s["duration_us"] or 0 for r in done for s in r.spans
                if s["name"] in names)
    found = any(s["name"] in names for r in done for s in r.spans)
    return total / 1e3 / len(done) if found else None
