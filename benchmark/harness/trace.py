"""From a profiler trace (``.xplane.pb``) to what the metrics read.

Busy time is the UNION of the intervals in which an operation ran on a
device, never a sum: a device plane has an ``XLA Ops`` line, an
``XLA Modules`` line and step lines that lie over each other, and a sum of
them, or of two lines, passes the window's length. The reduction takes the
one line that holds single operations (``XLA Ops``; ``XLA Modules`` where a
plane has no such line), clips it to the traced window, and unites.

Times in a trace count from the start of the profiler's session. The
harness opens one ``TraceAnnotation`` named :data:`WINDOW` around the
measured window and notes ``perf_counter_ns`` as it does, which puts the
program's spans (``perf_counter`` microseconds) on the trace's clock.
"""
from __future__ import annotations

import glob
import os
import re
from bisect import bisect_left
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench_window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"

Interval = Tuple[int, int]  # start, end in ns on the trace's clock


class TraceError(RuntimeError):
    """The trace cannot give the numbers asked of it."""


def unite(intervals: Iterable[Interval]) -> List[Interval]:
    """The union of ``intervals`` as disjoint intervals in order."""
    out: List[List[int]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def covered_ns(intervals: Iterable[Interval]) -> int:
    return sum(b - a for a, b in unite(intervals))


@dataclass
class Event:
    name: str
    start: int
    end: int


@dataclass
class DeviceTrace:
    ops: List[Event] = field(default_factory=list)
    modules: List[Event] = field(default_factory=list)


@dataclass
class Trace:
    """One traced window. ``window`` is the annotation's interval."""

    window: Interval
    devices: Dict[int, DeviceTrace]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _busy(self, dev: DeviceTrace) -> List[Interval]:
        events = dev.ops or dev.modules
        return unite(clip(((e.start, e.end) for e in events), *self.window))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        used = [covered_ns(self._busy(d)) for d in self.devices.values()]
        used = [u for u in used if u > 0]
        return sum(used) / len(used) / 1e9 if used else 0.0

    def module_events(self, pattern: str,
                      operand: Optional[str] = None) -> List[Event]:
        """Module executions that start inside the window, whose name
        matches ``pattern`` and, where ``operand`` is given, one of whose
        operations matches it in its HLO text. All the program's kernels are
        jitted from functions called ``kernel``, so the module's name alone
        does not tell them apart; the names of a kernel's arguments, which
        the text of its operations carries (``%s_keys.1``,
        ``%env__ss_quantity___values.1``), do. Operations and modules are on
        one device's clock, so which operation lies in which module is
        exact."""
        name_rx = re.compile(pattern)
        text_rx = re.compile(operand) if operand else None
        out: List[Event] = []
        for dev in self.devices.values():
            ops = sorted(dev.ops, key=lambda e: e.start)
            starts = [e.start for e in ops]
            verdict: Dict[str, bool] = {}  # one program, one verdict
            for m in dev.modules:
                if not name_rx.search(m.name):
                    continue
                if text_rx is not None and m.name not in verdict:
                    lo, hi = bisect_left(starts, m.start), bisect_left(starts, m.end)
                    verdict[m.name] = any(text_rx.search(o.name)
                                          for o in ops[lo:hi])
                if (text_rx is None or verdict[m.name]) \
                        and self.window[0] <= m.start < self.window[1]:
                    out.append(m)
        return out

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """The operations that took most device time in the window, each
        under its HLO name and the shape it makes."""
        total: Dict[str, int] = {}
        for d in self.devices.values():
            for e in d.ops or d.modules:
                a, b = max(e.start, self.window[0]), min(e.end, self.window[1])
                if b > a:
                    name = short_name(e.name)
                    total[name] = total.get(name, 0) + (b - a)
        rows = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [(name, ns / 1e9) for name, ns in rows]

    def idle_gaps(self, n: int = 10) -> List[Interval]:
        """The longest stretches of the window in which no device ran an
        operation."""
        busy = unite(i for d in self.devices.values() for i in self._busy(d))
        edges = [self.window[0]] + [t for i in busy for t in i] + [self.window[1]]
        gaps = [(edges[k], edges[k + 1]) for k in range(0, len(edges), 2)
                if edges[k + 1] > edges[k]]
        return sorted(gaps, key=lambda g: g[0] - g[1])[:n]


_HLO = re.compile(r"^%?([\w.\-]+) = \(?([a-z0-9]+\[[0-9,]*\])")


def short_name(name: str) -> str:
    """An operation's event carries its whole HLO text: keep the
    instruction's name and the first shape it makes."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise TraceError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return found[-1]


def read(path: str) -> Trace:
    """Reduce one ``.xplane.pb``. Raises :class:`TraceError` when the
    window's annotation is not in it."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    window: Optional[Interval] = None
    devices: Dict[int, DeviceTrace] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), DeviceTrace())
            for line in plane.lines:
                if line.name == OPS_LINE:
                    target = dev.ops
                elif line.name == MODULES_LINE:
                    target = dev.modules
                else:
                    continue
                for e in line.events:
                    start = int(e.start_ns)
                    target.append(Event(e.name, start,
                                        start + int(e.duration_ns)))
        elif plane.name.startswith("/host:") and window is None:
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        start = int(e.start_ns)
                        window = (start, start + int(e.duration_ns))
                        break
                if window is not None:
                    break
    if window is None:
        raise TraceError(f"no {WINDOW!r} annotation in {path}")
    return Trace(window, devices)


def span_at(spans: Sequence[Dict], t_us: float) -> str:
    """The innermost of the program's spans that covers ``t_us``."""
    best, best_len = "no span", None
    for s in spans:
        dur = s["duration_us"]
        if dur is None or not s["start_us"] <= t_us <= s["start_us"] + dur:
            continue
        if best_len is None or dur < best_len:
            best, best_len = s["name"], dur
    return best
