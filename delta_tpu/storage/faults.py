"""Deterministic, seeded fault injection for the storage/txn stack.

The LogStore contract (atomic visibility, mutual exclusion, consistent
listing — ``storage/LogStore.scala:44-138``) is what makes every fast path
in this engine trustworthy, yet real stores fail in ways the happy path
never exercises: connections reset mid-PUT, processes die between staging
and publishing a commit, multi-part checkpoints tear, ``_last_checkpoint``
goes stale, listings lag writes. :class:`FaultInjectingLogStore` wraps any
store and injects those failures at **named fault points**, following a
**reproducible seeded plan** — the same seed over the same workload yields
the same fault sequence, so every torture-test failure is replayable.

Fault kinds (:data:`ALL_KINDS`):

* ``transient`` — raise :class:`TransientIOError`; on a non-idempotent
  commit write a seeded coin decides whether the error fires *before* or
  *after* the underlying write (a lost response — the ambiguous-commit case
  reconciled in ``txn/transaction.py``).
* ``crash_before_publish`` — stage a ``.tmp`` orphan next to the target
  (what a died ``LocalLogStore.write`` leaves behind), then raise
  :class:`SimulatedCrash` without publishing.
* ``crash_after_publish`` — perform the write, then raise
  :class:`SimulatedCrash`: the commit is durable but the writer never
  learned.
* ``torn_checkpoint`` — crash a multi-part checkpoint part write, leaving a
  partial (incomplete) checkpoint that must never block readers.
* ``stale_last_checkpoint`` — silently drop a ``_last_checkpoint`` update,
  leaving the pointer behind the log.
* ``listing_lag`` — omit the newest log file from one listing (object-store
  eventual consistency).
* ``slow`` — sleep briefly (tail-latency stand-in; exercises nothing but
  timing assumptions, deliberately).

A *crash* is simulated by raising :class:`SimulatedCrash` — a
``BaseException`` so no ``except Exception`` recovery path can swallow it,
exactly like a process death — and the workload resumes with a fresh
``DeltaLog`` (see ``delta_tpu/testing/harness.py``).

Installation: set session conf ``delta.tpu.faults.plan`` to a
:class:`FaultPlan` (tests) or a spec string like
``"seed=42,rate=0.05,kinds=transient|crash_after_publish"``;
``DeltaLog`` wraps its store via :func:`maybe_wrap` at construction. With
the conf unset, :func:`maybe_wrap` returns the store unchanged — zero
wrapper, zero overhead (asserted by
``tests/test_faults.py::test_maybe_wrap_zero_overhead_when_unset``).
"""
from __future__ import annotations

import threading
import time
from typing import (Any, Dict, Iterable, Iterator, List, Optional, Sequence,
                    Tuple)

from delta_tpu.protocol import filenames
from delta_tpu.storage.logstore import FileStatus, LogStore
from delta_tpu.utils.retries import TransientIOError

__all__ = [
    "SimulatedCrash",
    "FaultPlan",
    "FaultInjectingLogStore",
    "ALL_KINDS",
    "fire",
    "maybe_wrap",
    "plan_from_conf",
    "reset_plan_cache",
]


class SimulatedCrash(BaseException):
    """A simulated process death at a fault point. BaseException on purpose:
    recovery code that catches ``Exception`` (post-commit checkpointing,
    cleanup) must not be able to "survive" a crash — only the workload
    driver resumes, with a fresh ``DeltaLog``."""

    def __init__(self, point: str):
        super().__init__(f"simulated crash at fault point {point!r}")
        self.point = point


#: Every fault kind the injector knows, keyed to where it can fire.
ALL_KINDS: Tuple[str, ...] = (
    "transient",
    "crash_before_publish",
    "crash_after_publish",
    "torn_checkpoint",
    "stale_last_checkpoint",
    "listing_lag",
    "slow",
)

#: kinds applicable per fault-point family. Read/list points never crash:
#: a reader dying teaches nothing new (no state mutated), while keeping
#: them crash-free keeps the seeded op sequence deterministic under the
#: engine's parallel part decodes.
_POINT_KINDS: Dict[str, Tuple[str, ...]] = {
    "read": ("transient", "slow"),
    "list": ("transient", "listing_lag", "slow"),
    "exists": ("transient",),
    "delete": ("transient",),
    "write.commit": ("transient", "crash_before_publish",
                     "crash_after_publish", "slow"),
    "write.checkpoint": ("transient", "torn_checkpoint", "slow"),
    "write.lastCheckpoint": ("transient", "stale_last_checkpoint"),
    "write.crc": ("transient",),
    "write.other": ("transient", "slow"),
    # engine-level points (fired via :func:`fire`, not through a store op):
    # the group-commit leader's write loop draws once per batch member
    # BEFORE that member's log-entry create — a crash here dies between
    # batch members, leaving a prefix of the batch durable; the async
    # checkpoint writer draws once per build request, pre-build (genuinely
    # TORN builds come from the write.checkpoint store point firing inside
    # the build's part writes — fire() has no partial-write to tear).
    "txn.groupLoop": ("transient", "crash_before_publish", "slow"),
    "checkpoint.asyncBuild": ("transient", "crash_before_publish", "slow"),
    # distributed-execution supervision points (parallel/executor,
    # parallel/leases): item attempts may die transiently, crash the
    # "process" (SimulatedCrash pierces the supervisor — only the workload
    # driver recovers), or stall (the straggler the speculation path
    # rescues); worker spawns and lease writes fail like any other IO;
    # heartbeat loss must cost at most a spurious speculation.
    "dist.itemExec": ("transient", "crash_before_publish", "slow"),
    "dist.workerSpawn": ("transient",),
    "dist.heartbeat": ("transient",),
    "dist.leaseWrite": ("transient", "crash_before_publish", "slow"),
}


class FaultPlan:
    """A reproducible seeded fault schedule.

    Each ``(fault point, target file name)`` pair owns an independent
    ``random.Random(f"{seed}:{point}|{name}")`` stream and its own draw
    index, so the decision for the i-th operation on a given file is a
    PURE FUNCTION of (seed, point, name, i). That makes the fault sequence
    immune to thread interleaving: the engine's pooled IO (parallel
    checkpoint part writes/decodes) may race, but racing threads touch
    different files — and same-file retries replay the same stream — so
    the same seed over the same workload reproduces the identical faults.
    (Plain per-point streams are NOT enough: two threads racing for the
    next stream value would swap which call gets the fault, and the
    workload's reaction to it diverges run over run.)

    ``script`` overrides the seeded draw for targeted tests: an ordered
    list of ``(point_prefix, kind)`` or ``(point_prefix, kind, sub)``
    tuples consumed one at a time — the next store op whose point matches
    the head injects that fault.
    """

    def __init__(
        self,
        seed: int = 0,
        rate: float = 0.0,
        kinds: Sequence[str] = ALL_KINDS,
        max_faults: Optional[int] = None,
        slow_ms: float = 2.0,
        script: Optional[Sequence[Tuple[str, str]]] = None,
    ):
        import random

        unknown = set(kinds) - set(ALL_KINDS)
        if unknown:
            raise ValueError(f"Unknown fault kinds: {sorted(unknown)}")
        self.seed = seed
        self.rate = rate
        self.kinds = tuple(kinds)
        self.max_faults = max_faults
        self.slow_ms = slow_ms
        self.script: List[Tuple[str, str]] = list(script or [])
        self._lock = threading.Lock()
        self._rngs: Dict[str, "random.Random"] = {}
        self._random = random
        #: chronological fault log [(stream key, kind, per-stream index)]
        self.injected: List[Tuple[str, str, int]] = []
        #: per-(point|name) kind sequences — the determinism witness:
        #: identical across runs of the same seeded workload even when
        #: global interleaving of parallel IO differs
        self.per_point: Dict[str, List[str]] = {}

    # -- draw -------------------------------------------------------------

    def _rng(self, key: str):
        rng = self._rngs.get(key)
        if rng is None:
            rng = self._random.Random(f"{self.seed}:{key}")
            self._rngs[key] = rng
        return rng

    def total_injected(self) -> int:
        return len(self.injected)

    def kinds_seen(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for _, kind, _ in self.injected:
            out[kind] = out.get(kind, 0) + 1
        return out

    def draw(self, point: str, name: str = "") -> Optional[Tuple[str, float]]:
        """One decision for one store op at ``point`` targeting file
        ``name``. Returns ``(kind, sub)`` to inject (``sub`` in [0,1): a
        secondary seeded coin, e.g. before/after for ambiguous write
        errors) or None."""
        key = f"{point}|{name}"
        with self._lock:
            if self.script:
                entry = self.script[0]
                prefix, kind = entry[0], entry[1]
                if point.startswith(prefix):
                    self.script.pop(0)
                    return self._record(key, kind,
                                        entry[2] if len(entry) > 2 else 0.0)
                return None
            if self.max_faults is not None and len(self.injected) >= self.max_faults:
                return None
            rng = self._rng(key)
            if rng.random() >= self.rate:
                return None
            applicable = [k for k in _POINT_KINDS[point] if k in self.kinds]
            if not applicable:
                return None
            kind = applicable[rng.randrange(len(applicable))]
            return self._record(key, kind, rng.random())

    def _record(self, key: str, kind: str, sub: float) -> Tuple[str, float]:
        seq = self.per_point.setdefault(key, [])
        self.injected.append((key, kind, len(seq)))
        seq.append(kind)
        from delta_tpu.utils import telemetry

        telemetry.bump_counter("faults.injected")
        return kind, sub


# -- conf plumbing ----------------------------------------------------------

_SPEC_CACHE: Dict[str, FaultPlan] = {}
_SPEC_LOCK = threading.Lock()


def reset_plan_cache() -> None:
    """Forget parsed string-spec plans. A spec string's plan is cached so
    its RNG streams survive crash-resume DeltaLog re-creations — which also
    means a LATER install of the same spec text in this process would
    resume the half-consumed streams. Call this between independent runs
    that reuse a spec string and expect a fresh seeded sequence."""
    with _SPEC_LOCK:
        _SPEC_CACHE.clear()


def plan_from_conf() -> Optional[FaultPlan]:
    """The session's fault plan, or None. A string spec is parsed once and
    cached by its literal text, so plan state (RNG streams, fault log)
    persists across the DeltaLog re-creations a crash-resume loop does —
    see :func:`reset_plan_cache` before reusing a spec for a fresh run."""
    from delta_tpu.utils.config import conf

    v = conf.get("delta.tpu.faults.plan")
    if not v:
        return None
    if isinstance(v, FaultPlan):
        return v
    spec = str(v)
    with _SPEC_LOCK:
        plan = _SPEC_CACHE.get(spec)
        if plan is None:
            plan = _parse_spec(spec)
            _SPEC_CACHE[spec] = plan
        return plan


def _parse_spec(spec: str) -> FaultPlan:
    """``"seed=42,rate=0.05,kinds=transient|slow,maxFaults=100,slowMs=2"``"""
    kw: Dict[str, object] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        key, _, val = part.partition("=")
        key = key.strip()
        val = val.strip()
        if key == "seed":
            kw["seed"] = int(val)
        elif key == "rate":
            kw["rate"] = float(val)
        elif key == "kinds":
            kw["kinds"] = tuple(k for k in val.split("|") if k)
        elif key == "maxFaults":
            kw["max_faults"] = int(val)
        elif key == "slowMs":
            kw["slow_ms"] = float(val)
        else:
            raise ValueError(f"Unknown fault-plan key {key!r} in {spec!r}")
    return FaultPlan(**kw)  # type: ignore[arg-type]


_UNPINNED = object()  # sentinel: fire() resolves the plan from conf


def fire(point: str, name: str = "",
         plan: Any = _UNPINNED) -> None:
    """Engine-level fault point — for code paths that are not a single
    store operation (the group-commit leader loop, the async checkpoint
    builder). Consults the session's active plan directly and raises the
    drawn fault; a no-op when no plan is installed (zero overhead: one
    conf read). Crash kinds raise :class:`SimulatedCrash`; ``transient``
    raises :class:`TransientIOError`; ``slow`` sleeps.

    Long-lived machinery whose threads can outlive the operation that
    spawned them (the sharded executor's worker pool) passes ``plan``
    explicitly — resolved once at job start — so a task that runs late
    draws from ITS job's plan instead of whatever the session conf holds
    by then. ``plan=None`` is an explicit no-op."""
    if plan is _UNPINNED:
        plan = plan_from_conf()
    if plan is None:
        return
    d = plan.draw(point, name)
    if d is None:
        return
    kind, _ = d
    if kind == "slow":
        time.sleep(plan.slow_ms / 1000.0)
        return
    if kind == "transient":
        raise TransientIOError(f"injected transient at {point}")
    raise SimulatedCrash(point)


def maybe_wrap(store: LogStore) -> LogStore:
    """Wrap ``store`` in a FaultInjectingLogStore when a plan is configured;
    otherwise return ``store`` itself (no wrapper, zero overhead)."""
    plan = plan_from_conf()
    if plan is None:
        return store
    return FaultInjectingLogStore(store, plan)


# -- the injecting store ----------------------------------------------------

def _classify_write(path: str) -> str:
    name = path.rsplit("/", 1)[-1]
    if name == filenames.LAST_CHECKPOINT:
        return "write.lastCheckpoint"
    if filenames.is_delta_file(name):
        return "write.commit"
    if filenames.is_checkpoint_file(name):
        return "write.checkpoint"
    if filenames.is_checksum_file(name):
        return "write.crc"
    return "write.other"


class FaultInjectingLogStore(LogStore):
    """Injects ``plan``'s faults around ``base``'s operations."""

    def __init__(self, base: LogStore, plan: FaultPlan):
        self.base = base
        self.plan = plan

    # -- reads ------------------------------------------------------------

    @staticmethod
    def _name(path: str) -> str:
        return path.rsplit("/", 1)[-1]

    def _simple_fault(self, point: str, path: str) -> None:
        d = self.plan.draw(point, self._name(path))
        if d is None:
            return
        kind, _ = d
        if kind == "slow":
            time.sleep(self.plan.slow_ms / 1000.0)
            return
        raise TransientIOError(f"injected {kind} at {point}")

    def read(self, path: str) -> List[str]:
        self._simple_fault("read", path)
        return self.base.read(path)

    def read_iter(self, path: str) -> Iterator[str]:
        self._simple_fault("read", path)
        return self.base.read_iter(path)

    def read_bytes(self, path: str) -> bytes:
        self._simple_fault("read", path)
        return self.base.read_bytes(path)

    def exists(self, path: str) -> bool:
        self._simple_fault("exists", path)
        return self.base.exists(path)

    def delete(self, path: str) -> bool:
        self._simple_fault("delete", path)
        return self.base.delete(path)

    def list_from(self, path: str) -> Iterator[FileStatus]:
        d = self.plan.draw("list", self._name(path))
        entries = list(self.base.list_from(path))
        if d is not None:
            kind, _ = d
            if kind == "transient":
                raise TransientIOError("injected transient at list")
            if kind == "slow":
                time.sleep(self.plan.slow_ms / 1000.0)
            elif kind == "listing_lag" and entries:
                # the newest log file isn't visible yet (eventual listing):
                # drop the lexicographically-last delta/checkpoint entry —
                # readers see a consistent, slightly older prefix
                for i in range(len(entries) - 1, -1, -1):
                    n = entries[i].name
                    if filenames.is_delta_file(n) or filenames.is_checkpoint_file(n):
                        entries.pop(i)
                        break
        return iter(entries)

    # -- writes -----------------------------------------------------------

    def write(self, path: str, lines: Iterable[str], overwrite: bool = False) -> None:
        data = ("".join(line + "\n" for line in lines)).encode("utf-8")
        self.write_bytes(path, data, overwrite=overwrite)

    def write_bytes(self, path: str, data: bytes, overwrite: bool = False) -> None:
        point = _classify_write(path)
        d = self.plan.draw(point, self._name(path))
        if d is None:
            return self.base.write_bytes(path, data, overwrite=overwrite)
        kind, sub = d
        if kind == "slow":
            time.sleep(self.plan.slow_ms / 1000.0)
            return self.base.write_bytes(path, data, overwrite=overwrite)
        if kind == "stale_last_checkpoint":
            return None  # pointer update silently lost; log moves ahead of it
        if kind == "transient":
            if not overwrite and point == "write.commit" and sub < 0.5:
                # lost response: the PUT landed, the writer never heard back.
                # THE ambiguous commit — reconciled via commitInfo.txnId.
                self.base.write_bytes(path, data, overwrite=overwrite)
            raise TransientIOError(f"injected transient at {point}")
        if kind == "crash_before_publish":
            # what a died LocalLogStore.write leaves: staged temp, no publish
            parent, _, name = path.rpartition("/")
            # delta-lint: ignore[crash-tmpfile] -- the orphan IS the fault being
            # injected: it simulates what a died LocalLogStore.write leaves
            orphan = f"{parent}/.{name}.deadbeef{len(self.plan.injected):08x}.tmp"
            try:
                self.base.write_bytes(orphan, data, overwrite=True)
            except Exception:  # noqa: BLE001 — orphan staging is best-effort
                pass
            raise SimulatedCrash(point)
        if kind == "torn_checkpoint":
            # the writer dies before THIS part lands; sibling parts (all
            # attempted — checkpoints.py `_run_all_parts`) may land, so the
            # surviving set is a partial multi-part checkpoint that misses
            # this part, and _last_checkpoint never advances
            raise SimulatedCrash(point)
        if kind == "crash_after_publish":
            self.base.write_bytes(path, data, overwrite=overwrite)
            raise SimulatedCrash(point)
        raise AssertionError(f"unhandled fault kind {kind!r}")

    # -- passthrough ------------------------------------------------------

    def is_partial_write_visible(self, path: str) -> bool:
        return self.base.is_partial_write_visible(path)

    def resolve_path(self, path: str) -> str:
        return self.base.resolve_path(path)

    def mkdirs(self, path: str) -> None:
        self.base.mkdirs(path)

    def __getattr__(self, name):
        return getattr(self.base, name)

    def __repr__(self) -> str:
        return f"FaultInjectingLogStore({self.base!r}, faults={len(self.plan.injected)})"
