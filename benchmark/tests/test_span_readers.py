"""The two readers that lay the program's spans against a request and
against the device's idle time, on hand-made runs and on the recorded trace.
Run by hand, as this directory's conftest says."""
import os

import pytest

from benchmark.harness import trace
from benchmark.harness.runner import Request, Run
from benchmark.metrics.readers import (counters_per_request,
                                       idle_unattributed_pct, span_cover_pct)

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "data", "recorded.xplane.pb")
ROOT = {"root": "delta.scan"}


def span(name, start_us, duration_us, thread=1):
    return {"name": name, "start_us": start_us, "duration_us": duration_us,
            "thread": thread, "data": {}}


def request(i, start_us, end_us, spans):
    return Request(i, start_us / 1e6, end_us / 1e6, True, spans=spans)


def run_of(requests, busy_us, window_us=(0, 1000)):
    """A traced run whose device was busy in ``busy_us`` (microseconds on the
    spans' clock; the trace's own clock starts 5 ms later)."""
    off = 5_000_000
    dev = trace.DeviceTrace(ops=[
        trace.Event("fusion", a * 1000 + off, b * 1000 + off)
        for a, b in busy_us])
    tr = trace.Trace(window=(window_us[0] * 1000 + off,
                             window_us[1] * 1000 + off), devices={0: dev})
    return Run(cell=None, seed=0, seconds=1.0, traced=True, requests=requests,
               trace=tr, window_perf_ns=window_us[0] * 1000)


def test_cover_unites_nested_spans_on_two_threads():
    spans = [span("delta.scan", 100, 400),
             span("delta.scan.planning", 100, 100),          # 100..200
             span("delta.status", 110, 80),                  # nested: no more
             span("delta.scan.decode", 250, 100, thread=2),  # 250..350
             span("delta.scan.read", 240, 400),              # cut at the root's end
             span("delta.log.update", 50, 100),              # began before the root
             span("delta.merge.router", 300, None)]          # a point event
    run = run_of([request(0, 40, 520, spans)], busy_us=[])
    # 100..200 and 240..500 of 100..500
    assert span_cover_pct.read(run, ROOT) == pytest.approx(100.0 * 360 / 400)


def test_cover_is_a_mean_over_requests_and_silent_without_the_root():
    a = request(0, 0, 100, [span("delta.scan", 0, 100), span("x", 0, 50)])
    b = request(1, 100, 200, [span("delta.scan", 100, 100)])
    run = run_of([a, b], busy_us=[])
    assert span_cover_pct.read(run, ROOT) == pytest.approx(25.0)
    assert span_cover_pct.read(run, {"root": "delta.dml.merge"}) is None
    run.trace = None
    assert span_cover_pct.read(run, ROOT) is None


def test_idle_is_cut_at_span_edges_and_at_the_requests():
    spans = [span("delta.scan", 100, 300),                   # 100..400
             span("delta.scan.read", 150, 150),              # 150..300
             span("delta.scan.decode", 200, 50, thread=2)]   # 200..250
    reqs = [request(0, 90, 410, spans),
            request(1, 600, 700, [span("delta.scan", 600, 100),
                                  span("delta.scan.read", 600, 100)])]
    # busy 0..120 and 260..1000: one gap, 120..260, that straddles two span
    # edges: 30 us under the root alone, 50 under .read, 50 under .decode,
    # 10 under .read again
    run = run_of(reqs, busy_us=[(0, 120), (260, 1000)])
    assert idle_unattributed_pct.read(run, ROOT) == pytest.approx(100 * 30 / 140)
    # idle before the first span of the request (90..100) has no span at
    # all and counts; idle between the requests (410..600) counts for nothing
    run = run_of(reqs, busy_us=[(0, 80), (100, 405), (590, 1000)])
    assert idle_unattributed_pct.read(run, ROOT) == pytest.approx(100.0)
    run = run_of(reqs, busy_us=[(0, 415), (590, 1000)])
    assert idle_unattributed_pct.read(run, ROOT) == 0.0  # no idle in requests
    assert idle_unattributed_pct.read(run, {"root": "delta.dml.merge"}) is None


def test_counters_per_request_sums_what_moved():
    run = run_of([request(0, 0, 10, []), request(1, 10, 20, [])], busy_us=[])
    run.counters = {"link.d2h.bytes": 300}
    both = {"counters": ["link.h2d.bytes", "link.d2h.bytes"]}
    assert counters_per_request.read(run, both) == 150
    run.counters["link.h2d.bytes"] = 100
    assert counters_per_request.read(run, both) == 200
    assert counters_per_request.read(run, {"counters": ["device.compiles"]}) is None


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_attributes_its_idle_time():
    """Six MERGEs were recorded; lay six requests over the window, each with
    a root span and one phase over its second half."""
    tr = trace.read(RECORDED)
    lo, hi = tr.window
    step = (hi - lo) // 6 // 1000  # microseconds
    reqs = []
    for i in range(6):
        a = i * step
        reqs.append(request(i, a, a + step, [
            span("delta.dml.merge", a, step),
            span("delta.dml.merge.write", a + step // 2, step - step // 2)]))
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, requests=reqs,
              trace=tr, window_perf_ns=0)
    root = {"root": "delta.dml.merge"}
    assert span_cover_pct.read(run, root) == pytest.approx(50.0, abs=0.01)
    share = idle_unattributed_pct.read(run, root)
    assert 0 < share < 100
    # the whole idle time, attributed or not, is the window less the busy time
    whole = Run(cell=None, seed=0, seconds=1.0, traced=True, trace=tr,
                window_perf_ns=0,
                requests=[request(0, 0, (hi - lo) / 1000,
                                  [span("delta.dml.merge", 0, (hi - lo) // 1000)])])
    assert idle_unattributed_pct.read(whole, root) == pytest.approx(100.0)
