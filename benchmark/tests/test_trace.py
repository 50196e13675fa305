"""The reduction from a trace to busy time is a union, not a sum."""
import os

import pytest

from benchmark.harness import trace

RECORDED = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "data", "recorded.xplane.pb")


def test_unite_and_clip():
    assert trace.unite([(5, 9), (0, 3), (2, 4), (9, 9)]) == [(0, 4), (5, 9)]
    assert trace.covered_ns([(0, 10), (2, 5), (8, 12)]) == 12
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_busy_is_the_union_of_one_line_inside_the_window():
    dev = trace.DeviceTrace(
        ops=[trace.Event("fusion", 10, 20), trace.Event("sort", 15, 30),
             trace.Event("late", 95, 120)],
        modules=[trace.Event("jit_kernel(1)", 10, 30)])
    t = trace.Trace(window=(0, 100), devices={0: dev})
    assert t.busy_s() == pytest.approx((20 + 5) / 1e9)  # not 10+15+25+20
    assert t.idle_gaps(2)[0] == (30, 95)
    assert t.top_ops(1)[0][0] == "sort"
    idle = trace.Trace(window=(0, 100), devices={0: trace.DeviceTrace()})
    assert idle.busy_s() == 0.0


def test_span_at_picks_the_innermost():
    spans = [{"name": "delta.scan", "start_us": 0, "duration_us": 100},
             {"name": "delta.scan.read", "start_us": 40, "duration_us": 20},
             {"name": "open", "start_us": 0, "duration_us": None}]
    assert trace.span_at(spans, 50) == "delta.scan.read"
    assert trace.span_at(spans, 10) == "delta.scan"
    assert trace.span_at(spans, 500) == "no span"


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="no recorded trace")
def test_recorded_chip_trace_gives_a_union_not_a_sum():
    t = trace.read(RECORDED)
    assert t.devices, "the recorded trace has a device plane"
    busy = t.busy_s()
    assert 0 < busy <= t.window_s
    dev = next(iter(t.devices.values()))
    lo, hi = t.window
    summed = sum(min(e.end, hi) - max(e.start, lo)
                 for e in dev.ops + dev.modules
                 if min(e.end, hi) > max(e.start, lo)) / 1e9
    assert summed > busy  # ops and modules lie over each other
    every = t.module_events("^jit_kernel")
    probe = t.module_events("^jit_kernel", r"%(s_keys|t_match_sorted)\b")
    sort = t.module_events("^jit_kernel", r"%keys\b")
    # six MERGEs: the slab's sort, the probe and the pair compaction each
    assert (len(every), len(probe), len(sort)) == (18, 12, 6)
    assert not t.module_events("^jit_kernel", "%env__")
