"""Structured telemetry — the engine-wide observability subsystem.

Reference: ``metering/DeltaLogging.scala:50-109`` wraps every user action in
``recordDeltaOperation(opType)`` / ``recordDeltaEvent`` with hierarchical op
types (e.g. ``delta.commit.retry.conflictCheck``) and JSON payloads; the OSS
backend is a no-op stub. Here the backend is real, in three pieces:

1. **Hierarchical spans** — :func:`record_operation` nests via a contextvar
   parent stack, so ``delta.commit`` contains its ``prepare`` /
   ``conflictCheck`` / ``write`` / ``postCommit`` phases and a scan contains
   its planning/prune phases. Spans export as Chrome trace-event JSON
   (:func:`export_chrome_trace`) loadable in Perfetto / ``chrome://tracing``.
   Each span also opens a ``jax.profiler.TraceAnnotation`` of its name:
   while a profiler session is open (``jax.profiler.trace``) the span is a
   host event of the ``.xplane.pb`` itself, on the profiler's clock beside
   the device planes; with no session open that is a flag test. Contextvars
   give each thread its own stack: concurrent writers never parent each
   other's spans.

2. **A metrics registry** — monotonic counters (:func:`bump_counter`),
   gauges (:func:`set_gauge`) and fixed log2-bucket latency histograms
   (:func:`observe`), with Prometheus text exposition
   (:func:`prometheus_text`) and a JSON snapshot
   (:func:`metrics_snapshot`). Gauges and histograms take labels (e.g. the
   table path); counters stay label-free name strings — they are the hot
   path and a dict bump must stay a dict bump.

3. **Events** — :func:`record_event` point-in-time payloads (the analogue of
   ``recordDeltaEvent``), e.g. the per-commit ``delta.commit.stats``.

Everything lands in one in-process ring buffer (size:
``delta.tpu.telemetry.bufferSize``, default 4096) and a standard ``logging``
logger. ``delta.tpu.telemetry.enabled=False`` suppresses events and spans
entirely (zero allocation on the hot path); counters keep working — they are
cheap and the serving-envelope numbers must survive an event blackout.

Spans are also DISTRIBUTED traces: every root span mints a 128-bit hex
``trace_id``, span ids are namespaced with a random per-process high word so
two hosts can never collide, and :func:`span_context(wire=True)` serializes
the identity as a traceparent-shaped string that
:func:`adopt_span_context` (and the ``DELTA_TPU_TRACEPARENT`` environment
variable, for spawned worker processes) accepts — a sharded job's per-item /
per-worker / per-host spans all parent under the coordinator's root. Sampled
traces (head sampling via ``delta.tpu.trace.sampleRate``; forced on error
and while SLO objectives burn) additionally stream each completed span to
registered span sinks — ``obs/trace_store`` spools them as JSONL for
cross-process stitching.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import gc
import itertools
import json
import logging
import os
import random
import re
import sys
import threading
import time
from bisect import bisect_left
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Deque, Dict, Iterator, List, Optional, Sequence, Tuple

from delta_tpu.utils.config import conf

logger = logging.getLogger("delta_tpu.usage")

__all__ = [
    "record_event", "record_operation", "with_status", "recent_events",
    "clear_events", "UsageEvent", "bump_counter", "counters",
    "clear_counters", "set_gauge", "gauges", "observe", "histograms",
    "prometheus_text", "metrics_snapshot",
    "export_chrome_trace", "current_span", "add_span_data", "reset_all",
    "HISTOGRAM_BUCKETS", "span_stack_snapshot", "add_failure_hook",
    "remove_failure_hook", "span_context", "adopt_span_context", "propagated",
    "histogram_rows", "bucket_quantile", "drop_labeled_series",
    "current_trace_id", "last_sampled_trace_id", "add_span_sink",
    "remove_span_sink", "TRACEPARENT_ENV", "exc_text", "open_spans",
    "add_span_counts", "span_stages", "GC_EVENT_US",
]


@dataclass
class UsageEvent:
    op_type: str
    timestamp_ms: int
    duration_ms: Optional[int] = None
    tags: Dict[str, str] = field(default_factory=dict)
    data: Dict[str, Any] = field(default_factory=dict)
    error: Optional[str] = None
    # span identity (0/None on plain events recorded outside any operation)
    span_id: int = 0
    parent_id: Optional[int] = None
    depth: int = 0
    # trace-export timeline: microseconds on the perf_counter clock
    start_us: int = 0
    duration_us: Optional[int] = None
    thread_id: int = 0
    thread_name: str = ""
    # distributed-trace identity: 32-hex trace id shared across processes,
    # plus the span start on the EPOCH clock (µs) — perf_counter is
    # per-process and cannot order spans from two hosts on one timeline
    trace_id: str = ""
    wall_us: int = 0

    def to_json(self) -> str:
        return json.dumps(
            {
                "opType": self.op_type,
                "timestamp": self.timestamp_ms,
                "durationMs": self.duration_ms,
                "tags": self.tags,
                "data": self.data,
                "error": self.error,
                "spanId": self.span_id or None,
                "parentId": self.parent_id,
                "traceId": self.trace_id or None,
            },
            separators=(",", ":"),
            default=str,
        )


_BUFFER: Deque[UsageEvent] = deque(maxlen=4096)
_LOCK = threading.Lock()
_SPAN_IDS = itertools.count(1)
# span ids are globally unique across a distributed job: a random 32-bit
# per-process namespace in the high word, the local counter in the low —
# two hosts' spools can stitch into one trace without id collisions
_SPAN_NS = int.from_bytes(os.urandom(4), "big") << 32
# innermost-last tuple of active span ids for THIS thread/context
_SPAN_STACK: "contextvars.ContextVar[Tuple[int, ...]]" = contextvars.ContextVar(
    "delta_telemetry_span_stack", default=()
)
# spans currently open (still mutable via add_span_data), by span id
_ACTIVE: Dict[int, UsageEvent] = {}
# callables invoked when a span closes with an exception: fn(event, exc).
# Empty by default — the error path pays one truthiness check. Consumers
# (obs/flight_recorder) must never raise; failures are swallowed here so a
# broken hook can't mask the original error.
_FAILURE_HOOKS: List[Any] = []


# -- distributed trace identity ----------------------------------------------

#: environment variable a coordinator sets on spawned worker processes so
#: every root span in the child adopts the coordinator's trace
TRACEPARENT_ENV = "DELTA_TPU_TRACEPARENT"


class _TraceState:
    """Mutable per-trace identity: the 128-bit hex trace id, the head-sampling
    decision (mutable — an error anywhere in the trace force-samples it), and
    the remote parent span id when the trace was adopted over the wire."""

    __slots__ = ("trace_id", "sampled", "remote_parent")

    def __init__(self, trace_id: str, sampled: bool,
                 remote_parent: Optional[int] = None):
        self.trace_id = trace_id
        self.sampled = sampled
        self.remote_parent = remote_parent


# the current trace for THIS context: set by the root span (reset when it
# closes) or by adopt_span_context, so sequential roots get fresh traces
_TRACE: "contextvars.ContextVar[Optional[_TraceState]]" = contextvars.ContextVar(
    "delta_telemetry_trace", default=None
)
# process-wide remote parent parsed once from TRACEPARENT_ENV (spawned
# workers: EVERY root span in the process joins the coordinator's trace)
_PROCESS_REMOTE: Optional[_TraceState] = None
_PROCESS_REMOTE_READ = False
# completed spans of sampled traces stream here: fn(event) after the span
# closes (obs/trace_store spools them as JSONL). Lazily installed on the
# first sampled close so importing telemetry never drags in the obs layer.
_SPAN_SINKS: List[Any] = []
_SINKS_PROBED = False
_LAST_SAMPLED_TRACE: str = ""


def _parse_traceparent(carrier: str) -> _TraceState:
    """Parse a ``00-<32hex traceId>-<16hex parentSpanId>-<2hex flags>``
    wire carrier (traceparent-shaped; flags bit 0 = sampled)."""
    parts = carrier.strip().split("-")
    if len(parts) != 4 or len(parts[1]) != 32 or len(parts[2]) != 16:
        raise ValueError(f"malformed trace carrier: {carrier!r}")
    int(parts[1], 16)
    parent = int(parts[2], 16)
    sampled = bool(int(parts[3], 16) & 1)
    return _TraceState(parts[1], sampled, parent or None)


def _process_remote() -> Optional[_TraceState]:
    global _PROCESS_REMOTE, _PROCESS_REMOTE_READ
    if not _PROCESS_REMOTE_READ:
        _PROCESS_REMOTE_READ = True
        raw = os.environ.get(TRACEPARENT_ENV)
        if raw:
            try:
                _PROCESS_REMOTE = _parse_traceparent(raw)
            except ValueError:
                logger.warning("ignoring malformed %s=%r", TRACEPARENT_ENV, raw)
    return _PROCESS_REMOTE


def _slo_burning() -> bool:
    """True while any SLO objective fires — forced sampling during burn
    windows so the alert always has an exemplar trace. Probed via
    sys.modules: telemetry must not import the obs layer, and a process
    that never evaluated SLOs pays one dict lookup."""
    slo = sys.modules.get("delta_tpu.obs.slo")
    if slo is None:
        return False
    try:
        return slo.firing_count() > 0
    except Exception:  # noqa: BLE001
        return False


def _new_trace_state() -> _TraceState:
    remote = _process_remote()
    if remote is not None:
        return _TraceState(remote.trace_id, remote.sampled,
                           remote.remote_parent)
    rate = _conf_snapshot()[3]
    if rate >= 1.0:
        sampled = True
    else:
        sampled = rate > 0.0 and random.random() < rate
        if not sampled and _slo_burning():
            sampled = True
    return _TraceState(os.urandom(16).hex(), sampled)


def _emit_span(ev: UsageEvent) -> None:
    """Stream a completed span of a sampled trace to the sinks (called
    OUTSIDE ``_LOCK`` — sinks take their own locks and read conf)."""
    global _SINKS_PROBED
    if not _SINKS_PROBED:
        _SINKS_PROBED = True
        try:
            from delta_tpu.obs import trace_store

            trace_store.install()
        except Exception:  # noqa: BLE001 — tracing must never break the op
            logger.debug("trace spool install failed", exc_info=True)
    for sink in list(_SPAN_SINKS):
        try:
            sink(ev)
        except Exception:  # noqa: BLE001
            logger.debug("trace span sink raised", exc_info=True)


def add_span_sink(fn) -> None:
    """Register ``fn(event)`` to receive every completed span/event of a
    sampled trace. Sinks must be fast and must not raise."""
    if fn not in _SPAN_SINKS:
        _SPAN_SINKS.append(fn)


def remove_span_sink(fn) -> None:
    try:
        _SPAN_SINKS.remove(fn)
    except ValueError:
        pass


def current_trace_id() -> Optional[str]:
    """The trace id of the current context (inside a span or an adopted
    wire context), or None."""
    t = _TRACE.get()
    return t.trace_id if t is not None else None


def last_sampled_trace_id() -> Optional[str]:
    """The most recently completed SAMPLED span's trace id — the exemplar
    an SLO alert or incident attaches when it has no ambient span."""
    return _LAST_SAMPLED_TRACE or None


# (generation, enabled, buffer_size, sample_rate) — the conf reads on the
# per-span hot path, re-resolved only when conf mutates. Benign race: a
# stale read costs one redundant resolve, never a wrong value for the
# generation it is keyed to.
_CONF_CACHE: Tuple[int, bool, int, float] = (-1, True, 4096, 1.0)


def _conf_snapshot() -> Tuple[int, bool, int, float]:
    global _CONF_CACHE
    cached = _CONF_CACHE
    gen = conf.generation()
    if cached[0] == gen:
        return cached
    enabled = conf.get_bool("delta.tpu.telemetry.enabled", True)
    try:
        size = int(conf.get("delta.tpu.telemetry.bufferSize", 4096))
    except (TypeError, ValueError):
        size = 4096
    if size <= 0:
        size = 4096
    try:
        rate = float(conf.get("delta.tpu.trace.sampleRate", 1.0))
    except (TypeError, ValueError):
        rate = 1.0
    cached = (gen, enabled, size, rate)
    _CONF_CACHE = cached
    if enabled and _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    return cached


def _enabled() -> bool:
    return _conf_snapshot()[1]


def _buffer_size() -> int:
    """Resolve the configured ring size OUTSIDE the telemetry lock — the
    conf lock must never be taken while holding ``_LOCK``."""
    return _conf_snapshot()[2]


def _buffer_locked(size: int) -> Deque[UsageEvent]:
    """The ring buffer at ``size``; callers hold ``_LOCK``."""
    global _BUFFER
    if _BUFFER.maxlen != size:
        _BUFFER = deque(_BUFFER, maxlen=size)
    return _BUFFER


def _now_us() -> int:
    return time.perf_counter_ns() // 1000


def record_event(op_type: str, data: Optional[Dict[str, Any]] = None, **tags: str) -> None:
    if not _enabled():
        return
    th = threading.current_thread()
    tstate = _TRACE.get()
    ev = UsageEvent(op_type, int(time.time() * 1000),
                    tags={k: str(v) for k, v in tags.items()},
                    data=data or {},
                    parent_id=(_SPAN_STACK.get() or (None,))[-1],
                    start_us=_now_us(),
                    thread_id=th.ident or 0, thread_name=th.name,
                    trace_id=tstate.trace_id if tstate else "",
                    wall_us=time.time_ns() // 1000)
    size = _buffer_size()
    with _LOCK:
        _buffer_locked(size).append(ev)
    if tstate is not None and tstate.sampled:
        _emit_span(ev)
    if logger.isEnabledFor(logging.DEBUG):
        logger.debug("%s", ev.to_json())


@contextlib.contextmanager
def record_operation(op_type: str, data: Optional[Dict[str, Any]] = None, **tags: str) -> Iterator[UsageEvent]:
    """Wrap an operation in a span: duration + error capture + parent/child
    nesting + a host event in an open JAX profiler session. The yielded
    event is live — mutate ``ev.data`` (or call :func:`add_span_data` from
    anywhere below) to attach payloads before the span closes."""
    if not _enabled():
        # zero-overhead: no span bookkeeping, no buffer append, no timing
        yield UsageEvent(op_type, 0, data=dict(data or {}))
        return
    th = threading.current_thread()
    stack = _SPAN_STACK.get()
    tstate = _TRACE.get()
    ttoken = None
    if tstate is None:
        # this is a trace root: mint the 128-bit trace id (or join the
        # process-wide remote parent) and decide head sampling once
        tstate = _new_trace_state()
        ttoken = _TRACE.set(tstate)
    ev = UsageEvent(op_type, int(time.time() * 1000),
                    tags={k: str(v) for k, v in tags.items()},
                    data=dict(data or {}),
                    span_id=_SPAN_NS | next(_SPAN_IDS),
                    parent_id=stack[-1] if stack else tstate.remote_parent,
                    depth=len(stack),
                    start_us=_now_us(),
                    thread_id=th.ident or 0, thread_name=th.name,
                    trace_id=tstate.trace_id,
                    wall_us=time.time_ns() // 1000)
    with _LOCK:
        _ACTIVE[ev.span_id] = ev
    token = _SPAN_STACK.set(stack + (ev.span_id,))
    start_ns = time.perf_counter_ns()
    try:
        with _maybe_jax_trace(op_type):
            yield ev
    except BaseException as e:
        ev.error = f"{type(e).__name__}: {e}"
        # an error anywhere force-samples the whole trace: the incident the
        # flight recorder writes must link to a spooled, stitchable trace
        tstate.sampled = True
        # span still on the stack and in _ACTIVE here: hooks see the full
        # failing span chain via span_stack_snapshot()
        if _FAILURE_HOOKS:
            for hook in list(_FAILURE_HOOKS):
                try:
                    hook(ev, e)
                except Exception:  # noqa: BLE001 — never mask the original
                    logger.debug("telemetry failure hook raised", exc_info=True)
        raise
    finally:
        _SPAN_STACK.reset(token)
        if ttoken is not None:
            _TRACE.reset(ttoken)
        dur_us = (time.perf_counter_ns() - start_ns) // 1000
        ev.duration_us = int(dur_us)
        ev.duration_ms = int(dur_us // 1000)
        size = _buffer_size()
        with _LOCK:
            _ACTIVE.pop(ev.span_id, None)
            _buffer_locked(size).append(ev)
        if tstate.sampled:
            global _LAST_SAMPLED_TRACE
            _LAST_SAMPLED_TRACE = tstate.trace_id
            _emit_span(ev)
        # to_json serialises tags+data — only pay for it when debug logging
        # is actually on (this is the per-span hot path)
        if logger.isEnabledFor(logging.DEBUG):
            logger.debug("%s", ev.to_json())


def current_span() -> Optional[UsageEvent]:
    """The innermost open span in this context, or None."""
    stack = _SPAN_STACK.get()
    if not stack:
        return None
    with _LOCK:
        return _ACTIVE.get(stack[-1])


def open_spans() -> List[UsageEvent]:
    """The open span chain of THIS context, outermost first — the live
    events themselves (``op_type`` is fixed; ``data`` still mutates)."""
    stack = _SPAN_STACK.get()
    if not stack:
        return []
    with _LOCK:
        return [ev for ev in map(_ACTIVE.get, stack) if ev is not None]


def span_stack_snapshot() -> List[Dict[str, Any]]:
    """The open span chain for THIS context, outermost first, as JSON-able
    dicts (opType/spanId/parentId/depth/tags/data/elapsedMs/error). The raw
    events stay private — they are still live and mutating."""
    stack = _SPAN_STACK.get()
    if not stack:
        return []
    now = _now_us()
    out: List[Dict[str, Any]] = []
    with _LOCK:
        # copy payload dicts under the lock — the events are live
        for sid in stack:
            ev = _ACTIVE.get(sid)
            if ev is None:
                continue
            out.append({
                "opType": ev.op_type,
                "spanId": ev.span_id,
                "parentId": ev.parent_id,
                "depth": ev.depth,
                "tags": dict(ev.tags),
                "data": dict(ev.data),
                "elapsedMs": max(0, (now - ev.start_us) // 1000),
                "error": ev.error,
            })
    return out


# -- cross-thread span propagation -------------------------------------------
#
# Contextvars isolate each thread's span stack — correct for concurrent
# writers, wrong for the engine's OWN worker threads: a Parquet decode pool,
# a checkpoint part writer, or the MERGE staging/uploader threads would each
# start an orphan span root, and the decode/compute overlap the router
# assumes becomes invisible in `export_chrome_trace`. The carrier pattern
# fixes it: capture the submitting context's open span chain at submit time
# (`span_context` / `propagated`), restore it inside the worker
# (`adopt_span_context`), and the worker's spans parent under the submitting
# operation while keeping their own thread lane in the trace.


class SpanContextCarrier(tuple):
    """In-process carrier: compares and unpacks exactly like the legacy
    span-id tuple, plus the trace state (``.trace``) so adopting threads
    keep the trace id and sampling decision."""

    trace: Optional[_TraceState] = None


def span_context(wire: bool = False) -> Any:
    """The open span chain of THIS context as an opaque carrier — capture at
    task-submit time, hand to the worker thread, restore with
    :func:`adopt_span_context`.

    With ``wire=True``, returns instead a serializable traceparent-shaped
    string (``00-<traceId>-<parentSpanId>-<flags>``) for crossing a PROCESS
    boundary — put it in a job payload or the ``DELTA_TPU_TRACEPARENT``
    environment of a spawned worker. None when no trace is active."""
    stack = _SPAN_STACK.get()
    tstate = _TRACE.get()
    if wire:
        if tstate is None:
            return None
        parent = stack[-1] if stack else (tstate.remote_parent or 0)
        return "00-%s-%016x-%s" % (tstate.trace_id, parent,
                                   "01" if tstate.sampled else "00")
    carrier = SpanContextCarrier(stack)
    carrier.trace = tstate
    return carrier


@contextlib.contextmanager
def adopt_span_context(carrier) -> Iterator[None]:
    """Run the body under ``carrier`` (a :func:`span_context` capture, or its
    ``wire=True`` string form): spans opened inside parent under the
    carrier's innermost span instead of starting an orphan root in the
    worker thread — and they join the carrier's trace."""
    if isinstance(carrier, str):
        tstate: Optional[_TraceState] = _parse_traceparent(carrier)
        stack: Tuple[int, ...] = ()
    else:
        tstate = getattr(carrier, "trace", None)
        stack = tuple(carrier)
    token = _SPAN_STACK.set(stack)
    ttoken = _TRACE.set(tstate) if tstate is not None else None
    try:
        yield
    finally:
        if ttoken is not None:
            _TRACE.reset(ttoken)
        _SPAN_STACK.reset(token)


def propagated(fn):
    """Wrap ``fn`` so it executes under the CURRENT context's span chain —
    the one-liner for thread pools::

        pool.map(telemetry.propagated(read_one), jobs)

    The capture happens NOW (at wrap time, i.e. task submit), not when the
    worker runs. Zero-overhead: with telemetry disabled or no span open,
    ``fn`` is returned unchanged."""
    if not _enabled():
        return fn
    carrier = _SPAN_STACK.get()
    if not carrier:
        return fn
    tstate = _TRACE.get()

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        token = _SPAN_STACK.set(carrier)
        ttoken = _TRACE.set(tstate) if tstate is not None else None
        try:
            return fn(*args, **kwargs)
        finally:
            if ttoken is not None:
                _TRACE.reset(ttoken)
            _SPAN_STACK.reset(token)

    return wrapper


def add_failure_hook(fn) -> None:
    """Register ``fn(event, exc)`` to run when any span exits with an
    exception (before the span closes, so the open stack is inspectable).
    Hooks must be fast and must not raise."""
    if fn not in _FAILURE_HOOKS:
        _FAILURE_HOOKS.append(fn)


def remove_failure_hook(fn) -> None:
    try:
        _FAILURE_HOOKS.remove(fn)
    except ValueError:
        pass


def add_span_data(**kv: Any) -> None:
    """Merge key/values into the innermost open span's data payload — how a
    layer deep inside an operation (e.g. DML rewrite metrics) reports into
    the span that wraps it, without threading the event object through."""
    ev = current_span()
    if ev is not None:
        ev.data.update(kv)


def add_span_counts(**kv: float) -> None:
    """Add each value to the number the innermost open span's data holds
    under that key (absent counts as 0) — tallies that several calls below
    one span contribute to: bytes moved over the link, compiles."""
    ev = current_span()
    if ev is not None:
        data = ev.data
        for k, v in kv.items():
            data[k] = data.get(k, 0) + v


@contextlib.contextmanager
def span_stages() -> Iterator[Any]:
    """Consecutive child spans that tile the enclosing one. Yields
    ``enter(op_type, data=None)``: it closes the stage that is open, opens
    the next and returns its live event (entering the stage that is open
    already changes nothing, so a helper and its caller may both name it).
    The last stage closes with the block; an exception closes the open
    stage with the error on it."""
    cm: Any = None
    ev: Optional[UsageEvent] = None

    def enter(op_type: str, data: Optional[Dict[str, Any]] = None) -> UsageEvent:
        nonlocal cm, ev
        if ev is not None and ev.op_type == op_type:
            return ev
        if cm is not None:
            cm.__exit__(None, None, None)
        cm = record_operation(op_type, data)
        ev = cm.__enter__()
        return ev

    try:
        yield enter
    except BaseException as e:
        if cm is not None:
            cm.__exit__(type(e), e, e.__traceback__)  # re-raised below
        raise
    else:
        if cm is not None:
            cm.__exit__(None, None, None)


# -- the interpreter's own pauses --------------------------------------------
#
# A collection stops every thread of the process and no span shows it. One
# ``gc.callbacks`` entry, installed when telemetry is first found enabled,
# counts every collection (``host.gc.collections``, ``host.gc.pauseUs``) and
# keeps a pause of GC_EVENT_US or more as an event ``host.gc`` that has a
# start and a length on the spans' clock, so whoever lays spans against a
# timeline (the benchmark's idle gaps) names the gap a collection made.
#
# A collection can start between any two bytecodes, also while its thread
# holds ``_LOCK``, and there is one every few hundred allocations: the
# callback takes no lock and touches nothing but what is below. Whoever
# reads counters or events folds that in first, under the lock
# (`_fold_gc_locked`).

#: a collection at least this long is an event, not only a count
GC_EVENT_US = 1000

# written by the callback alone (collections never overlap: the interpreter
# runs one at a time): when the one under way started; collections and
# microseconds since the process began; the pauses not yet in the ring
_GC_START_NS = 0
_GC_TOTAL = [0, 0]
_GC_EVENTS: List[UsageEvent] = []
# how much of _GC_TOTAL the counters hold already; written under _LOCK
_GC_FOLDED = [0, 0]
_GC_COUNTERS = ("host.gc.collections", "host.gc.pauseUs")


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    global _GC_START_NS
    if phase == "start":
        _GC_START_NS = time.perf_counter_ns()
        return
    us = (time.perf_counter_ns() - _GC_START_NS) // 1000
    _GC_TOTAL[0] += 1
    _GC_TOTAL[1] += us
    if us >= GC_EVENT_US and _enabled():
        # no parent: the pause is the process's, whatever span this thread
        # had open when the collector came round
        th = threading.current_thread()
        _GC_EVENTS.append(UsageEvent(
            "host.gc", int(time.time() * 1000), duration_ms=us // 1000,
            data={"generation": info.get("generation"),
                  "collected": info.get("collected")},
            start_us=_GC_START_NS // 1000, duration_us=us,
            thread_id=th.ident or 0, thread_name=th.name))


def _fold_gc_locked() -> None:
    """The collections since the last fold into the counters, their events
    into the ring; callers hold ``_LOCK``. Each total is read once and only
    grows, so a collection that comes round meanwhile is folded next time."""
    for i, name in enumerate(_GC_COUNTERS):
        total = _GC_TOTAL[i]
        if total != _GC_FOLDED[i]:
            _COUNTERS[name] = _COUNTERS.get(name, 0) + total - _GC_FOLDED[i]
            _GC_FOLDED[i] = total
    n = len(_GC_EVENTS)
    if n:
        _BUFFER.extend(_GC_EVENTS[:n])
        del _GC_EVENTS[:n]


def exc_text(e: BaseException) -> str:
    """An exception as it rides an event payload: ``Type: message[:300]``."""
    return f"{type(e).__name__}: {str(e)[:300]}"


@contextlib.contextmanager
def with_status(message: str, **tags: str) -> Iterator[None]:
    """Human-readable job description around a long step — the analogue of
    the reference's ``DeltaProgressReporter.withStatusCode`` ("Filtering
    files for query", `PartitionFiltering.scala:34`). Logs at INFO on entry
    and records a `delta.status` usage event with the duration on exit, so
    operators can see WHAT a long-running command is doing, not just that
    it is running. For the long steps (a checkpoint, VACUUM's listing): a
    scan's planning is `delta.scan.planning` and opens none."""
    logger.info("%s", message)
    with record_operation("delta.status", {"message": message}, **tags):
        yield


_NO_TRACE = contextlib.nullcontext()


def _maybe_jax_trace(name: str):
    """The span as a host event of an open profiler session. jax is never
    imported for this: a process that has not loaded it has no session."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NO_TRACE
    return profiler.TraceAnnotation(name)


def _prefix_match(name: str, prefix: str) -> bool:
    """Dotted-name boundary match: ``"delta.commit"`` matches itself and
    ``delta.commit.*`` but NOT ``delta.commitFoo``."""
    return not prefix or name == prefix or name.startswith(prefix + ".")


def recent_events(op_prefix: str = "") -> List[UsageEvent]:
    with _LOCK:
        _fold_gc_locked()
        return [e for e in _BUFFER if _prefix_match(e.op_type, op_prefix)]


def clear_events() -> None:
    with _LOCK:
        _fold_gc_locked()
        _BUFFER.clear()


# -- monotonic counters ------------------------------------------------------
#
# Cheap process-wide tallies for questions like "what fraction of scan
# plans actually served from the resident state cache, and why did the
# rest fall back?" — the serving envelope as a NUMBER, not a hope.
# Deliberately label-free and NOT gated on telemetry.enabled: a name lookup
# plus an int add, even during an event blackout.

_COUNTERS: Dict[str, int] = {}


def bump_counter(name: str, by: int = 1) -> None:
    with _LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0) + by


def counters(prefix: str = "") -> Dict[str, int]:
    with _LOCK:
        _fold_gc_locked()
        return {k: v for k, v in _COUNTERS.items() if _prefix_match(k, prefix)}


def clear_counters() -> None:
    with _LOCK:
        _fold_gc_locked()
        _COUNTERS.clear()


# -- gauges + histograms -----------------------------------------------------

LabelKey = Tuple[str, Tuple[Tuple[str, str], ...]]

#: Fixed log2 bucket upper bounds (ms when observing latencies):
#: 1, 2, 4, ..., 65536; values above the last bound land in +Inf.
HISTOGRAM_BUCKETS: Tuple[float, ...] = tuple(float(2 ** i) for i in range(17))

_GAUGES: Dict[LabelKey, float] = {}
_HISTOGRAMS: Dict[LabelKey, "_Histogram"] = {}


class _Histogram:
    __slots__ = ("counts", "sum", "count")

    def __init__(self):
        self.counts = [0] * (len(HISTOGRAM_BUCKETS) + 1)  # last = +Inf
        self.sum = 0.0
        self.count = 0


def _label_key(name: str, labels: Dict[str, str]) -> LabelKey:
    return name, tuple(sorted((k, str(v)) for k, v in labels.items()))


def set_gauge(name: str, value: float, **labels: str) -> None:
    with _LOCK:
        _GAUGES[_label_key(name, labels)] = float(value)


def gauges(prefix: str = "") -> Dict[LabelKey, float]:
    with _LOCK:
        return {k: v for k, v in _GAUGES.items() if _prefix_match(k[0], prefix)}


def observe(name: str, value: float, **labels: str) -> None:
    """Record ``value`` into the fixed-log-bucket histogram ``name``."""
    value = float(value)
    key = _label_key(name, labels)
    ix = bisect_left(HISTOGRAM_BUCKETS, value)
    with _LOCK:
        h = _HISTOGRAMS.get(key)
        if h is None:
            h = _HISTOGRAMS[key] = _Histogram()
        h.counts[ix] += 1
        h.sum += value
        h.count += 1


def histograms(prefix: str = "") -> Dict[LabelKey, "_Histogram"]:
    with _LOCK:
        return {k: v for k, v in _HISTOGRAMS.items() if _prefix_match(k[0], prefix)}


def histogram_rows(prefix: str = "") -> List[Tuple[str, Tuple[Tuple[str, str], ...], List[int], float, int]]:
    """Immutable ``(name, labels, bucket_counts, sum, count)`` rows for every
    labeled histogram matching ``prefix`` — the payloads are COPIED under the
    lock, so the obs scraper (`obs/timeseries`) can diff cumulative bucket
    counts across scrapes without holding any reference to live state."""
    with _LOCK:
        return [(n, lb, list(h.counts), h.sum, h.count)
                for (n, lb), h in _HISTOGRAMS.items()
                if _prefix_match(n, prefix)]


def drop_labeled_series(**labels: str) -> int:
    """Remove every gauge/histogram series whose label set contains ALL of
    ``labels`` (e.g. ``drop_labeled_series(table=<hash>)``); returns the
    series dropped. The registry otherwise never forgets a labeled series,
    so per-table series would accumulate for the life of a long-running
    process under table churn — the fleet registry calls this when a
    table's handle dies (obs/fleet.live_tables). Counters are label-free
    and unaffected."""
    want = {(k, str(v)) for k, v in labels.items()}
    dropped = 0
    with _LOCK:
        for store in (_GAUGES, _HISTOGRAMS):
            dead = [key for key in store if want <= set(key[1])]
            for key in dead:
                del store[key]
            dropped += len(dead)
    return dropped


def clear_metrics() -> None:
    with _LOCK:
        _GAUGES.clear()
        _HISTOGRAMS.clear()


def reset_all() -> None:
    """Events + counters + gauges + histograms back to empty (tests)."""
    with _LOCK:
        _fold_gc_locked()
        _BUFFER.clear()
        _COUNTERS.clear()
        _GAUGES.clear()
        _HISTOGRAMS.clear()


# -- exposition --------------------------------------------------------------

_NAME_SANITIZE = re.compile(r"[^a-zA-Z0-9_:]")


def _prom_name(name: str) -> str:
    return _NAME_SANITIZE.sub("_", name)


def _prom_escape(v: str) -> str:
    # text-format label values require \\, \", \n escaping
    return v.replace("\\", "\\\\").replace('"', '\\"').replace("\n", "\\n")


def _prom_labels(labels: Tuple[Tuple[str, str], ...], extra: str = "") -> str:
    parts = [f'{_prom_name(k)}="{_prom_escape(v)}"' for k, v in labels]
    if extra:
        parts.append(extra)
    return "{" + ",".join(parts) + "}" if parts else ""


def _fmt(v: float) -> str:
    return str(int(v)) if float(v).is_integer() else repr(float(v))


def _metric_descriptions() -> Dict[str, str]:
    """One-line ``# HELP`` text per cataloged metric name (lazy import —
    the obs layer sits above telemetry; a broken catalog must never break
    exposition)."""
    try:
        from delta_tpu.obs.metric_names import DESCRIPTIONS

        return DESCRIPTIONS
    except Exception:  # noqa: BLE001
        return {}


def prometheus_text() -> str:
    """Prometheus text-format exposition of every counter, gauge, and
    histogram (stable ordering — scrape-diff friendly). Cataloged names
    (``obs/metric_names.DESCRIPTIONS``) get a ``# HELP`` line so scrapers
    classify and document each series; ``# TYPE`` is emitted once per metric
    name (label sets of one gauge/histogram share their header)."""
    with _LOCK:
        _fold_gc_locked()
        ctrs = sorted(_COUNTERS.items())
        gags = sorted(_GAUGES.items())
        hists = sorted(_HISTOGRAMS.items(), key=lambda kv: kv[0])
        hist_rows = [(k, list(h.counts), h.sum, h.count) for k, h in hists]
    descs = _metric_descriptions()
    lines: List[str] = []

    def _header(name: str, pn: str, kind: str, seen: set) -> None:
        if name in seen:
            return
        seen.add(name)
        if name in descs:
            lines.append(f"# HELP {pn} {descs[name]}")
        lines.append(f"# TYPE {pn} {kind}")

    seen_ctr: set = set()
    for name, value in ctrs:
        pn = _prom_name(name) + "_total"
        _header(name, pn, "counter", seen_ctr)
        lines.append(f"{pn} {value}")
    seen_g: set = set()
    for (name, labels), value in gags:
        pn = _prom_name(name)
        _header(name, pn, "gauge", seen_g)
        lines.append(f"{pn}{_prom_labels(labels)} {_fmt(value)}")
    seen_h: set = set()
    for (name, labels), counts, total, count in hist_rows:
        pn = _prom_name(name)
        _header(name, pn, "histogram", seen_h)
        cum = 0
        for bound, c in zip(HISTOGRAM_BUCKETS, counts):
            cum += c
            le = _prom_labels(labels, f'le="{_fmt(bound)}"')
            lines.append(f"{pn}_bucket{le} {cum}")
        cum += counts[-1]
        inf_labels = _prom_labels(labels, 'le="+Inf"')
        lines.append(f"{pn}_bucket{inf_labels} {cum}")
        lines.append(f"{pn}_sum{_prom_labels(labels)} {_fmt(total)}")
        lines.append(f"{pn}_count{_prom_labels(labels)} {count}")
    return "\n".join(lines) + ("\n" if lines else "")


def _labels_suffix(labels: Tuple[Tuple[str, str], ...]) -> str:
    return "{" + ",".join(f"{k}={v}" for k, v in labels) + "}" if labels else ""


def bucket_quantile(counts: Sequence[int], count: int, q: float) -> Optional[float]:
    """Upper bucket bound where the cumulative count crosses q (approximate,
    conservative-upward — the usual bucket-quantile estimate). Public: the
    obs scraper extracts windowed quantiles from cumulative-bucket deltas
    with exactly this rule, so /slo and a histogram summary never disagree.
    Returns None for an empty histogram or a crossing past the last bound
    (the +Inf bucket) — callers choose their own sentinel."""
    if count <= 0:
        return None
    target = q * count
    cum = 0
    for bound, c in zip(HISTOGRAM_BUCKETS, counts):
        cum += c
        if cum >= target:
            return bound
    return None  # beyond the last bound (+Inf bucket) — keep JSON strict


def metrics_snapshot() -> Dict[str, Any]:
    """JSON-able snapshot of the whole registry."""
    with _LOCK:
        _fold_gc_locked()
        ctrs = dict(_COUNTERS)
        gags = dict(_GAUGES)
        hists = [((n, lb), list(h.counts), h.sum, h.count)
                 for (n, lb), h in _HISTOGRAMS.items()]
    out: Dict[str, Any] = {
        "counters": dict(sorted(ctrs.items())),
        "gauges": {f"{n}{_labels_suffix(lb)}": v
                   for (n, lb), v in sorted(gags.items())},
        "histograms": {},
    }
    for (n, lb), counts, total, count in sorted(hists, key=lambda r: r[0]):
        buckets = {_fmt(b): c for b, c in zip(HISTOGRAM_BUCKETS, counts) if c}
        if counts[-1]:
            buckets["+Inf"] = counts[-1]
        out["histograms"][f"{n}{_labels_suffix(lb)}"] = {
            "count": count, "sum": round(total, 3), "buckets": buckets,
        }
    return out


# -- Chrome trace-event export (Perfetto / chrome://tracing) -----------------

#: default thread names (Thread-12, ThreadPoolExecutor-0_3, MainThread is
#: kept — it IS informative); engine pools override these on a recycled tid
_GENERIC_THREAD = re.compile(r"(Thread-\d+.*|ThreadPoolExecutor-\d+_\d+)")


def export_chrome_trace(path: Optional[str] = None, op_prefix: str = "",
                        limit: Optional[int] = None) -> Dict[str, Any]:
    """Export the event ring buffer as Chrome trace-event JSON.

    Spans become complete ("X") events with real durations; point events
    become instants ("i"). Spans still OPEN at export time (in ``_ACTIVE``,
    not yet in the ring buffer) are emitted too, with their duration clamped
    to "now" and ``args.incomplete = true`` — an export taken mid-operation
    must show the operation, not silently drop it. Thread-name metadata rows
    keep multi-writer traces readable. Load the result in
    https://ui.perfetto.dev or ``chrome://tracing``. ``metadata.clock``
    holds ``perf_counter_ns`` and ``time_ns`` read together at export:
    ``ts`` is ``perf_counter`` microseconds, so the pair lays the file
    against any timeline on the epoch clock (a JAX profile names its own
    start; under an open profiler session the spans are in that profile
    already, see the module docstring).

    ``op_prefix`` keeps only ops on a dotted-name boundary match
    (``delta.commit`` matches ``delta.commit.*``); ``limit`` keeps only the
    NEWEST N ring events (open spans always export — they are the current
    operation)."""
    pid = os.getpid()
    now_us = _now_us()
    with _LOCK:
        _fold_gc_locked()
        events = list(_BUFFER)
        # open spans are still LIVE (add_span_data mutates ev.data with no
        # lock): copy their payloads while we hold the lock, or a concurrent
        # mutation mid-iteration blows up the export
        open_clamped = [
            (ev.op_type, ev.thread_id or 0, ev.thread_name,
             dict(ev.tags), dict(ev.data), ev.error,
             ev.span_id, ev.parent_id, ev.start_us,
             max(0, now_us - ev.start_us))
            for ev in sorted(_ACTIVE.values(), key=lambda e: e.start_us)
            if _prefix_match(ev.op_type, op_prefix)
        ]
    if op_prefix:
        events = [e for e in events if _prefix_match(e.op_type, op_prefix)]
    if limit is not None and limit >= 0:
        events = events[-limit:] if limit else []
    rows: List[Dict[str, Any]] = []
    seen_tids: Dict[int, str] = {}

    def _note_tid(tid: int, tname: str) -> None:
        # prefer an engine-named lane (delta-scan-decode_3, merge-slab-
        # upload, delta-journal-writer, ...) over a generic Thread-N: the
        # OS recycles thread ids across pool generations, and the named
        # pools are what make a multi-lane trace readable in Perfetto
        name = tname or str(tid)
        cur = seen_tids.get(tid)
        if cur is None:
            seen_tids[tid] = name
        elif _GENERIC_THREAD.fullmatch(cur) and not _GENERIC_THREAD.fullmatch(name):
            seen_tids[tid] = name

    for ev in events:
        tid = ev.thread_id or 0
        _note_tid(tid, ev.thread_name)
        args: Dict[str, Any] = {}
        if ev.tags:
            args.update(ev.tags)
        if ev.data:
            args.update(ev.data)
        if ev.error:
            args["error"] = ev.error
        if ev.span_id:
            args["spanId"] = ev.span_id
        if ev.parent_id:
            args["parentId"] = ev.parent_id
        if ev.trace_id:
            args["traceId"] = ev.trace_id
        row: Dict[str, Any] = {
            "name": ev.op_type,
            "cat": "delta",
            "pid": pid,
            "tid": tid,
            "ts": ev.start_us,
            "args": args,
        }
        if ev.duration_us is not None:
            row["ph"] = "X"
            row["dur"] = ev.duration_us
        else:
            row["ph"] = "i"
            row["s"] = "t"
        rows.append(row)
    for (op_type, tid, tname, tags, data, error,
         span_id, parent_id, start_us, dur) in open_clamped:
        _note_tid(tid, tname)
        args = dict(tags)
        args.update(data)
        if error:
            args["error"] = error
        args["spanId"] = span_id
        if parent_id:
            args["parentId"] = parent_id
        args["incomplete"] = True
        rows.append({
            "name": op_type, "cat": "delta", "pid": pid, "tid": tid,
            "ts": start_us, "ph": "X", "dur": dur, "args": args,
        })
    # metadata rows: the process lane plus one thread_name per tid, so the
    # registered pools (delta-scan-decode, delta-merge-slab-upload,
    # delta-merge-device-probe, delta-ckpt-part, ... — see
    # analysis/passes/pool_naming.REGISTERED_POOLS) render as labeled lanes
    rows.append({
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "delta-tpu"},
    })
    for tid, tname in seen_tids.items():
        rows.append({
            "name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
            "args": {"name": tname},
        })
    trace = {"traceEvents": rows, "displayTimeUnit": "ms",
             "metadata": {"clock": {"perf_counter_ns": time.perf_counter_ns(),
                                    "time_ns": time.time_ns()}}}
    if path is not None:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(trace, f, default=str)
    return trace
