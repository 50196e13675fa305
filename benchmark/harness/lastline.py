"""The last line of standard output: built, checked against the driver's
contract, and only then printed.

PR 22 was refused because a traced run's last line was not the object the
driver reads. So one function makes the line, :func:`check` refuses every
way it can be wrong, and :func:`emit` prints it to a descriptor on which
nothing else has written: ``run.py`` points file descriptor 1 at standard
error before anything is imported, and hands the real standard output here.
"""
from __future__ import annotations

import json
import math
import os
from typing import Any, Dict, List, Optional

KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


class LastLineError(ValueError):
    """The result does not meet the contract; nothing is printed."""


def build(correct: bool, attempted: int, failed: int,
          metrics: Dict[str, Dict[str, Any]], device: Dict[str, Any],
          compared: Dict[str, Dict[str, Any]],
          breakdown: Optional[Dict[str, List]] = None,
          notes: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """``notes`` is for whoever reads a run by hand; the driver ignores it."""
    line: Dict[str, Any] = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    if notes is not None:
        line["notes"] = notes
    line["compared"] = compared  # last, as the contract asks
    return line


def _number(x: Any, what: str) -> float:
    if isinstance(x, bool) or not isinstance(x, (int, float)):
        raise LastLineError(f"{what} is {x!r}, not a number")
    if not math.isfinite(x):
        raise LastLineError(f"{what} is {x!r}")
    return x


def check(line: Dict[str, Any], expected: Dict[str, str], trace: bool,
          chips: int) -> None:
    """Raise :class:`LastLineError` unless ``line`` is what the driver
    reads: ``expected`` maps each metric this run must report to its unit."""
    for key in KEYS:
        if key not in line:
            raise LastLineError(f"key {key!r} is missing")
    if not isinstance(line["correct"], bool):
        raise LastLineError("correct is not true or false")
    for key in ("attempted", "failed"):
        if isinstance(line[key], bool) or not isinstance(line[key], int) \
                or line[key] < 0:
            raise LastLineError(f"{key} is {line[key]!r}")
    if line["attempted"] < 1 or line["failed"] > line["attempted"]:
        raise LastLineError(f"attempted {line['attempted']}, failed "
                            f"{line['failed']}")
    metrics = line["metrics"]
    missing = sorted(set(expected) - set(metrics))
    if missing:
        raise LastLineError(f"metrics missing from the line: {missing}")
    extra = sorted(set(metrics) - set(expected))
    if extra:
        raise LastLineError(f"metrics the cell does not list: {extra}")
    for name, unit in expected.items():
        m = metrics[name]
        if set(m) != {"value", "unit"} or m["unit"] != unit:
            raise LastLineError(f"metric {name} is {m!r}; unit {unit!r}")
        value = _number(m["value"], f"metric {name}")
        if (name.endswith("_roofline") or "mfu" in name.split("_")) \
                and not 0 < value <= 105:
            raise LastLineError(f"{name} is {value}% of a peak")
    device = line["device"]
    for key in DEVICE_KEYS:
        if key not in device:
            raise LastLineError(f"device.{key} is missing")
    if device["count"] < chips:
        raise LastLineError(f"device.count {device['count']} < {chips}")
    if _number(device["memory_peak_bytes"], "memory_peak_bytes") <= 0:
        raise LastLineError("memory_peak_bytes is not above 0")
    if trace:
        for key in ("busy_s", "window_s"):
            if key not in device:
                raise LastLineError(f"device.{key} is missing in a traced run")
            _number(device[key], f"device.{key}")
        if not 0 < device["busy_s"] <= device["window_s"]:
            raise LastLineError(
                f"busy_s {device['busy_s']} is not above 0 and at most "
                f"window_s {device['window_s']}")
    if "breakdown" in line:
        for key, rows in line["breakdown"].items():
            if key not in ("device_ops", "idle_gaps") or len(rows) > 10:
                raise LastLineError(f"breakdown.{key}: {len(rows)} entries")
            for row in rows:
                if len(row) != 2 or not isinstance(row[0], str):
                    raise LastLineError(f"breakdown.{key} entry {row!r}")
                _number(row[1], f"breakdown.{key} seconds")
    if list(line)[-1] != "compared":
        raise LastLineError("the numbers compared do not come last")


def render(line: Dict[str, Any]) -> str:
    text = json.dumps(line, allow_nan=False, separators=(", ", ": "))
    check_text(text)
    return text


def check_text(text: str) -> Dict[str, Any]:
    """What the driver does with the output: the last line, alone, has to
    be the object."""
    if "\n" in text.strip("\n") or text != text.strip():
        raise LastLineError("more than the one line would be printed")
    try:
        obj = json.loads(text)
    except ValueError as e:
        raise LastLineError(f"the line is not JSON: {e}") from None
    if not isinstance(obj, dict):
        raise LastLineError("the line is not a JSON object")
    return obj


def emit(line: Dict[str, Any], expected: Dict[str, str], trace: bool,
         chips: int, fd: int) -> None:
    """Check, then write the one line to ``fd`` and close it."""
    check(line, expected, trace, chips)
    data = (render(line) + "\n").encode()
    while data:
        data = data[os.write(fd, data):]
    os.close(fd)
