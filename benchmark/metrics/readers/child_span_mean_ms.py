"""Milliseconds a request spent in the stages of one of the program's spans:
the spans named in ``spans`` (exact names) that start inside a span named
``parent``, on any thread, as a mean over the window's requests.

A span counts once however many spans of the parent's name a request holds
(a refresh pair's two MERGEs each open ``delta.dml.merge.write``); one of
the same name that starts outside every parent (the shared writer under an
OPTIMIZE, say) does not. Where the program opens the parent and no such
stage inside it, as a program older than the stages does, that is 0.0 and
true: the parent's time is there and none of it is in a named stage. Where
no request has the parent span: nothing.
"""


def read(run, params):
    parent, names = params["parent"], set(params["spans"])
    done = run.done
    total, found = 0, False
    for r in done:
        inside = [(s["start_us"], s["start_us"] + s["duration_us"])
                  for s in r.spans
                  if s["name"] == parent and s["duration_us"] is not None]
        if not inside:
            continue
        found = True
        total += sum(s["duration_us"] for s in r.spans
                     if s["name"] in names and s["duration_us"] is not None
                     and any(lo <= s["start_us"] <= hi for lo, hi in inside))
    return total / 1e3 / len(done) if found else None
