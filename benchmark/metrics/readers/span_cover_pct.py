"""How much of a request's root span the program's other spans account for:
the share of the wall time of the span named ``root`` that the union of
every other span inside it covers, on any thread, as a mean over the
window's requests, in percent.

A span counts when it starts inside the root's interval and is not the root
itself; it is cut at the root's end (a background thread may outlive the
request). Spans that started before the root (an enclosing span, a straggler
of the request before) do not count, so an outer span cannot cover the root
by itself. What is left uncovered is time the request spent with no span of
the program open but the root: the part of the request that nothing names.
Works request by request over that request's own spans. Without a trace or
without a span of the root's name: nothing.
"""
from benchmark.harness.trace import covered_ns


def _cover(spans, root):
    lo = root["start_us"]
    hi = lo + root["duration_us"]
    inside = [(s["start_us"], min(s["start_us"] + s["duration_us"], hi))
              for s in spans
              if s is not root and s["duration_us"] is not None
              and lo <= s["start_us"] < hi]
    return covered_ns(inside), hi - lo


def read(run, params):
    if run.trace is None:
        return None
    shares = []
    for r in run.done:
        covered = length = 0
        for s in r.spans:
            if s["name"] == params["root"] and s["duration_us"]:
                c, n = _cover(r.spans, s)
                covered += c
                length += n
        if length:
            shares.append(100.0 * covered / length)
    return sum(shares) / len(shares) if shares else None
