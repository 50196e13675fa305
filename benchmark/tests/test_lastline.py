"""The last line is checked before it is printed: a good line passes, and
each way PR 22's could have been wrong is refused."""
import copy
import json
import os

import pytest

from benchmark.harness import lastline

EXPECTED = {"scan_plan_ms": "ms", "mask_roofline": "%"}


def good(trace=True):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1_000_000}
    if trace:
        device.update(busy_s=1.5, window_s=20.0)
    return lastline.build(
        True, 100, 0,
        {"scan_plan_ms": {"value": 1.25, "unit": "ms"},
         "mask_roofline": {"value": 12.5, "unit": "%"}},
        device, {"rows_missing": {"value": 0, "limit": 0}},
        {"device_ops": [["fusion", 0.5]], "idle_gaps": [["delta.scan", 0.1]]})


def test_good_line_passes_and_round_trips():
    line = good()
    lastline.check(line, EXPECTED, True, 1)
    assert lastline.check_text(lastline.render(line)) == line
    assert list(line)[-1] == "compared"


def _broken(edit):
    line = copy.deepcopy(good())
    edit(line)
    return line


@pytest.mark.parametrize("name,edit", [
    ("missing metric", lambda l: l["metrics"].pop("mask_roofline")),
    ("metric not listed", lambda l: l["metrics"].update(
        extra={"value": 1, "unit": "ms"})),
    ("wrong unit", lambda l: l["metrics"]["scan_plan_ms"].update(unit="s")),
    ("value not a number", lambda l: l["metrics"]["scan_plan_ms"].update(
        value="1.25")),
    ("value NaN", lambda l: l["metrics"]["scan_plan_ms"].update(
        value=float("nan"))),
    ("roofline over 105", lambda l: l["metrics"]["mask_roofline"].update(
        value=140.0)),
    ("roofline 0", lambda l: l["metrics"]["mask_roofline"].update(value=0.0)),
    ("busy_s 0", lambda l: l["device"].update(busy_s=0.0)),
    ("busy_s over window_s", lambda l: l["device"].update(busy_s=21.0)),
    ("busy_s missing", lambda l: l["device"].pop("busy_s")),
    ("window_s missing", lambda l: l["device"].pop("window_s")),
    ("memory missing", lambda l: l["device"].pop("memory_peak_bytes")),
    ("too few chips", lambda l: l["device"].update(count=0)),
    ("key missing", lambda l: l.pop("failed")),
    ("nothing attempted", lambda l: l.update(attempted=0)),
    ("correct not a bool", lambda l: l.update(correct="true")),
    ("breakdown too long", lambda l: l["breakdown"].update(
        device_ops=[["op", 0.1]] * 11)),
    ("compared not last", lambda l: l.update(late=1)),
])
def test_each_fault_is_refused(name, edit):
    with pytest.raises(lastline.LastLineError):
        lastline.check(_broken(edit), EXPECTED, True, 1)


def test_untraced_line_needs_no_busy_time():
    lastline.check(good(trace=False), EXPECTED, False, 1)


@pytest.mark.parametrize("text", [
    '{"correct": true}\nlate log line',
    'I0000 stop_trace\n{"correct": true}',
    '{"correct": true} ',
    '[1, 2]',
    'not json',
])
def test_trailing_or_leading_output_is_refused(text):
    with pytest.raises(lastline.LastLineError):
        lastline.check_text(text)


def test_emit_writes_one_line_and_nothing_on_a_bad_result():
    r, w = os.pipe()
    lastline.emit(good(), EXPECTED, True, 1, w)
    data = os.read(r, 1 << 20).decode()
    assert data.endswith("\n") and data.count("\n") == 1
    assert json.loads(data)["device"]["busy_s"] == 1.5
    r2, w2 = os.pipe()
    with pytest.raises(lastline.LastLineError):
        lastline.emit(_broken(lambda l: l["device"].update(busy_s=0.0)),
                      EXPECTED, True, 1, w2)
    os.close(w2)
    assert os.read(r2, 1 << 20) == b""
