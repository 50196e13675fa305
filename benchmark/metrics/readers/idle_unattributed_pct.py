"""Of the time the device sat idle inside requests, the share that no phase
of the program can be blamed for: the innermost span open at that time is
the request's root span (``root``), or there is none.

Every idle gap of the trace (``Trace.idle_gaps``) is put on the spans' clock
with ``window_perf_ns``, as ``runner._breakdown`` does, clipped to the
requests' intervals (a gap between two requests is the harness's, and counts
for nothing) and cut at span edges. The innermost span at a time is the
shortest span that covers it, on any thread, as ``trace.span_at`` has it.
Each request is swept once over its own spans' sorted edges, so a run of
800 scans and thousands of gaps takes well under a second. Without a trace
or without a span of the root's name: nothing.
"""


def _segments(spans, lo, hi):
    """``[(start, end, name)]`` over ``[lo, hi)``: the innermost open span
    of each stretch between two span edges, None where no span is open."""
    live = [(max(s["start_us"], lo), min(s["start_us"] + s["duration_us"], hi),
             s["duration_us"], s["name"])
            for s in spans if s["duration_us"] is not None]
    live = [s for s in live if s[1] > s[0]]
    edges = sorted({lo, hi, *(s[0] for s in live), *(s[1] for s in live)})
    starting = sorted(live)
    out, active, k = [], [], 0
    for a, b in zip(edges, edges[1:]):
        while k < len(starting) and starting[k][0] <= a:
            active.append(starting[k])
            k += 1
        active = [s for s in active if s[1] > a]
        name = min(active, key=lambda s: s[2])[3] if active else None
        out.append((a, b, name))
    return out


def read(run, params):
    tr = run.trace
    root = params["root"]
    if tr is None or not any(s["name"] == root
                             for r in run.done for s in r.spans):
        return None
    shift = run.window_perf_ns - tr.window[0]
    gaps = sorted(((a + shift) / 1e3, (b + shift) / 1e3)
                  for a, b in tr.idle_gaps(1 << 62))
    idle = unnamed = 0.0
    g = 0
    for r in sorted(run.requests, key=lambda r: r.start):
        lo, hi = r.start * 1e6, r.end * 1e6
        while g < len(gaps) and gaps[g][1] <= lo:
            g += 1
        mine, k = [], g  # this request's share of the gaps, in order
        while k < len(gaps) and gaps[k][0] < hi:
            mine.append((max(gaps[k][0], lo), min(gaps[k][1], hi)))
            k += 1
        if not mine:
            continue
        first = 0  # the first of them that ends after the segment starts
        for a, b, name in _segments(r.spans, lo, hi):
            while first < len(mine) and mine[first][1] <= a:
                first += 1
            k = first
            while k < len(mine) and mine[k][0] < b:
                part = min(mine[k][1], b) - max(mine[k][0], a)
                idle += part
                if name is None or name == root:
                    unnamed += part
                k += 1
    return 100.0 * unnamed / idle if idle else 0.0
