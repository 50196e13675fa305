"""One run of one cell: set up, measure a window, compare, report.

The order is the contract's: look for the chip, make the table and the
traffic from the seed, warm up the cell's shapes (all of that is
``setup_s``), run the window with or without the profiler, read the device's
peak memory, and only then run the plain reference and compare.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from benchmark.harness import lastline, trace as trace_mod
from benchmark.harness.cell import Cell, load_cell
from benchmark.harness.preflight import (cache_entries, host_memory, log,
                                         memory_peak_bytes, preflight,
                                         release_memory)


@dataclass
class Request:
    """One request of the window, timed on ``perf_counter``."""

    index: int
    start: float
    end: float
    ok: bool
    rows: int = 0  # source rows of a MERGE, rows returned by a scan
    info: Dict[str, Any] = field(default_factory=dict)
    result: Any = None  # what came back, kept for the comparison
    spans: List[Dict[str, Any]] = field(default_factory=list)
    error: Optional[str] = None


@dataclass
class Run:
    """What the metric readers read."""

    cell: Cell
    seed: int
    seconds: float
    traced: bool
    setup_s: float = 0.0
    window_start: float = 0.0
    window_end: float = 0.0
    requests: List[Request] = field(default_factory=list)
    counters: Dict[str, int] = field(default_factory=dict)  # over the window
    bytes_written: int = 0  # under the table's directory, over the window
    trace: Optional[trace_mod.Trace] = None
    window_perf_ns: int = 0  # perf_counter_ns as the annotation opened
    device_kind: str = ""

    @property
    def window_s(self) -> float:
        return self.window_end - self.window_start

    @property
    def done(self) -> List[Request]:
        return [r for r in self.requests if r.ok]


@dataclass
class Context:
    """What a traffic kind is given."""

    cell: Cell
    seed: int
    scale: float
    sut: Any
    table: Any  # the configuration's table module
    gen: Any
    base: Any  # the loaded rows, as the reference holds them


class _CompileWatch:
    """Counts what XLA compiles, or fetches from the persistent cache, from
    JAX's own monitoring events."""

    def __init__(self):
        self.events: List[tuple] = []
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, secs: float, **_kw) -> None:
        if "compil" in name or "cache" in name:
            self.events.append((time.perf_counter(), name, secs))

    def between(self, t0: float, t1: float) -> Dict[str, Any]:
        inside = [(n, s) for t, n, s in self.events if t0 <= t <= t1]
        backend = [s for n, s in inside if n.endswith("backend_compile_duration")]
        return {"backend_compiles": len(backend),
                "backend_compile_s": round(sum(backend), 3),
                "events": len(inside)}


def _scaled(params: Dict[str, Any], scale: float) -> Dict[str, Any]:
    """The table's parameters at a test's size: fewer rows, the same
    shapes. Only the tests pass a scale other than 1."""
    if scale == 1:
        return params
    out = dict(params)
    out["rows"] = max(int(params["rows"] * scale), 2000)
    return out


def _scaled_layout(layout: Dict[str, Any], scale: float) -> Dict[str, Any]:
    confs = {k: max(int(v * scale), 50) if k.endswith("targetFileRows") else v
             for k, v in layout["write_confs"].items()}
    return dict(layout, write_confs=confs)


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             scale: float = 1.0, need_tpu: bool = True,
             sut_factory: Optional[Callable[[str, Dict[str, Any]], Any]] = None,
             t_process: Optional[float] = None) -> Dict[str, Any]:
    """Run the cell once and return the checked result line. ``scale``,
    ``need_tpu`` and ``sut_factory`` are for the tests and the controls."""
    t_process = time.perf_counter() if t_process is None else t_process
    cell = load_cell(workload)
    facts = preflight(cell.chips, need_tpu=need_tpu)
    watch = _CompileWatch()
    run = Run(cell, seed, seconds, traced, device_kind=facts["device"]["kind"])
    workdir = tempfile.mkdtemp(prefix="bench_")
    try:
        line = _run(cell, run, facts, watch, workdir, scale, sut_factory,
                    t_process)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return line


def _run(cell, run, facts, watch, workdir, scale, sut_factory, t_process):
    import jax

    from benchmark.harness.engine import EngineTable, table_bytes

    table = cell.table_module()
    kind = cell.traffic_kind()
    cfg = dict(cell.config)
    if scale != 1:
        cfg["layout"] = _scaled_layout(cfg["layout"], scale)
    path = os.path.join(workdir, "table")
    sut = (sut_factory or EngineTable)(path, cfg)

    split: Dict[str, float] = {}

    def lap(name: str, t0: float) -> float:
        now = time.perf_counter()
        split[name] = round(now - t0, 3)
        log(f"setup: {name} {split[name]} s; {host_memory()}")
        return now

    t = time.perf_counter()
    split["start"] = round(t - t_process, 3)
    gen = table.Generator(_scaled(cfg["table"], scale), run.seed)
    base = gen.base()
    t = lap("generate", t)
    data = table.to_arrow(base)
    t = lap("to_arrow", t)
    sut.load(data)
    del data
    release_memory()
    t = lap("write", t)
    ctx = Context(cell, run.seed, scale, sut, table, gen, base)
    state = kind.prepare(ctx)
    t = lap("prepare_traffic", t)
    kind.warm_up(ctx, state)
    release_memory()
    t = lap("warm_up", t)
    cache_dir = facts["cache"]["dir"]
    entries_setup = cache_entries(cache_dir)
    run.setup_s = time.perf_counter() - t_process
    log(f"setup_s {run.setup_s:.3f}; compile cache entries "
        f"{facts['cache']['entries']} -> {entries_setup}")

    # -- the window ------------------------------------------------------------
    sut.drain_spans()
    counters0 = sut.counters()
    bytes0 = table_bytes(path)
    trace_dir = os.path.join(workdir, "trace")
    annotation = None
    if run.traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        annotation = jax.profiler.TraceAnnotation(trace_mod.WINDOW)
        run.window_perf_ns = time.perf_counter_ns()
        annotation.__enter__()
    jax.config.update("jax_log_compiles", True)  # names what compiles inside
    # a mix whose work is made in set-up ends its window with that work
    limit = kind.max_requests(ctx, state) if hasattr(kind, "max_requests") \
        else float("inf")
    run.window_start = time.perf_counter()
    deadline = run.window_start + run.seconds
    try:
        i = 0
        while time.perf_counter() < deadline and i < limit:
            t0 = time.perf_counter()
            try:
                out = kind.request(ctx, state, i)
                req = Request(i, t0, time.perf_counter(), True, **out)
            except Exception as e:  # noqa: BLE001 — a failed request is counted
                req = Request(i, t0, time.perf_counter(), False,
                              error=f"{type(e).__name__}: {e}"[:300])
                log(f"request {i} failed: {req.error}")
            if run.traced:
                req.spans = sut.drain_spans()
            run.requests.append(req)
            i += 1
    finally:
        run.window_end = time.perf_counter()
        jax.config.update("jax_log_compiles", False)
        if annotation is not None:
            annotation.__exit__(None, None, None)
            jax.profiler.stop_trace()
    counters1 = sut.counters()
    run.counters = {k: v - counters0.get(k, 0) for k, v in counters1.items()
                    if v != counters0.get(k, 0)}
    run.bytes_written = table_bytes(path) - bytes0
    device = dict(facts["device"], memory_peak_bytes=memory_peak_bytes()) \
        if facts["device"]["platform"] != "cpu" else \
        dict(facts["device"], memory_peak_bytes=1)  # the CPU reports none
    compiles = watch.between(run.window_start, run.window_end)
    entries_window = cache_entries(cache_dir)
    log(f"window: {host_memory()}")
    log(f"window: {len(run.requests)} requests in {run.window_s:.3f} s; "
        f"compile events inside {json.dumps(compiles)}; new compile cache "
        f"entries inside {entries_window - entries_setup}")
    log("window counters:", json.dumps({
        k: v for k, v in sorted(run.counters.items())
        if k.split(".")[0] in ("merge", "scan", "columnCache", "stateCache",
                               "dist")}))

    # -- compare, with the plain reference, what the window produced -------------
    t = time.perf_counter()
    release_memory()
    compared = kind.check(ctx, state, run.requests)
    failed = sum(1 for r in run.requests if not r.ok)
    compared["requests_failed"] = {"value": failed, "limit": 0}
    correct = all(c["value"] <= c["limit"] for c in compared.values())
    log(f"compare: {time.perf_counter() - t:.3f} s; {host_memory()}")

    # -- metrics -------------------------------------------------------------------
    breakdown = None
    if run.traced:
        run.trace = trace_mod.read(trace_mod.find_xplane(trace_dir))
        busy = run.trace.busy_s()
        if busy <= 0:
            why = {k: run.counters.get(k, 0) for k in (
                "scan.device.declined", "scan.device.fallback",
                "merge.device.declined", "merge.device.fallback")}
            raise lastline.LastLineError(
                f"no operation ran on a device in the traced window of "
                f"{run.trace.window_s:.3f} s; the counters say {why}")
        device.update(busy_s=busy, window_s=run.trace.window_s)
        breakdown = _breakdown(run)
    metrics: Dict[str, Dict[str, Any]] = {}
    for m in (cell.per_layer if run.traced else cell.end_to_end):
        value = m.read(run)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    notes = {"setup_split_s": split, "window_s": run.window_s,
             "compiles_in_window": compiles["backend_compiles"],
             "cache_entries_new_in_window": entries_window - entries_setup,
             "cache_entries_new_in_setup":
                 entries_setup - facts["cache"]["entries"]}
    line = lastline.build(correct, len(run.requests), failed, metrics, device,
                          compared, breakdown, notes)
    for name, c in compared.items():
        log(f"compared {name}: {c['value']} (limit {c['limit']})")
    return line


def _breakdown(run: Run) -> Dict[str, List]:
    """The device operations that took most time, and the longest idle
    gaps, each named by the innermost span of the program that was open in
    the middle of it."""
    tr = run.trace
    spans = [s for r in run.requests for s in r.spans]
    gaps = []
    for a, b in tr.idle_gaps(10):
        mid_us = ((a + b) / 2 - tr.window[0] + run.window_perf_ns) / 1000
        gaps.append([trace_mod.span_at(spans, mid_us), (b - a) / 1e9])
    return {"device_ops": [[n, s] for n, s in tr.top_ops(10)],
            "idle_gaps": gaps}


def expected_metrics(cell: Cell, traced: bool) -> Dict[str, str]:
    return {m.name: m.unit
            for m in (cell.per_layer if traced else cell.end_to_end)}
