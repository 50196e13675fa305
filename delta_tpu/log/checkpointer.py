"""Asynchronous, incremental checkpoint builder.

Synchronous interval checkpointing stalls every
``delta.checkpointInterval``-th committer on an O(table) write: the
snapshot's whole segment (base checkpoint Parquet + log tail) decodes and
re-serializes on the committing writer's thread (``txn/transaction.
_post_commit`` → ``DeltaLog.checkpoint``). Under sustained write traffic
that is the commit path's p99. This module moves the build **off the
critical path** and makes it **incremental**:

* **Async** (``delta.tpu.checkpoint.async``): ``_post_commit`` enqueues a
  checkpoint request; a ``delta-ckpt-async`` daemon thread (the
  ``obs/journal`` writer-daemon pattern) coalesces requests per table
  (newest version wins) and builds them in the background. A failed or
  crashed build loses nothing but the optimization — the log tail stays
  replayable and the next interval re-requests.
* **Incremental** (``delta.tpu.checkpoint.incremental``): checkpoint N is
  built from the **cached reconciled columns** of the last checkpoint M
  plus a decode of ONLY the tail commits M+1..N
  (``log/columnar.extend_segment_columns`` — the columnar twin of the
  state cache's ``apply_tail``), instead of re-reading and re-decoding the
  whole base checkpoint. Any gap (no cached base, missing tail file,
  process restart) falls back to full reconstruction and re-seeds the
  cache; ``checkpoint.incremental.{built,fallback}`` count both paths.
  Dead rows accumulated across incremental rounds are compacted by
  re-decoding the just-written checkpoint once they exceed the live count.

The actual Parquet/pointer writes go through ``DeltaLog.checkpoint`` —
multi-part semantics, ``_last_checkpoint`` publication and expired-log
cleanup are unchanged, and the existing ``write.checkpoint`` /
``write.lastCheckpoint`` fault points cover the IO. The builder itself
draws at the ``checkpoint.asyncBuild`` fault point once per request, so a
torture plan can tear an incremental build deterministically.

Both confs default OFF; with them off this module is never imported on the
commit path.
"""
from __future__ import annotations

import logging
import threading
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

from delta_tpu.protocol import filenames
from delta_tpu.utils.config import conf
from delta_tpu.utils import telemetry

logger = logging.getLogger(__name__)

__all__ = ["request_checkpoint", "build_checkpoint", "flush", "reset",
           "pending_requests", "base_version"]

_LOCK = threading.Lock()
#: data_path -> (delta_log, version): coalesced, newest version wins
_REQUESTS: Dict[str, Tuple[object, int]] = {}
_WAKE = threading.Event()
_WRITER: Optional[threading.Thread] = None
#: serializes builds: a synchronous flush() or direct build_checkpoint()
#: call (harness, tests) never interleaves with the daemon mid-build —
#: re-entrant because _drain holds it across its build_checkpoint calls
_IO_LOCK = threading.RLock()

_BASE_LOCK = threading.Lock()


@dataclass
class _Base:
    """Cached reconciled columns of the last checkpoint built for a table."""

    version: int
    cols: object  # log/columnar.SegmentColumns


#: data_path -> _Base, LRU-bounded by delta.tpu.checkpoint.incremental.maxTables
_BASES: Dict[str, _Base] = {}


def _max_tables() -> int:
    try:
        n = int(conf.get("delta.tpu.checkpoint.incremental.maxTables", 8))
    except (TypeError, ValueError):
        n = 8
    return max(n, 1)


def base_version(data_path: str) -> Optional[int]:
    """The cached incremental base's version for a table (tests/doctor)."""
    with _BASE_LOCK:
        b = _BASES.get(data_path.rstrip("/"))
        return b.version if b is not None else None


def _seed_base(data_path: str, version: int, cols) -> None:
    with _BASE_LOCK:
        _BASES.pop(data_path, None)
        _BASES[data_path] = _Base(version, cols)  # re-insert = most recent
        while len(_BASES) > _max_tables():
            _BASES.pop(next(iter(_BASES)))


def _drop_base(data_path: str) -> None:
    with _BASE_LOCK:
        _BASES.pop(data_path, None)


# ---------------------------------------------------------------------------
# Request queue + daemon
# ---------------------------------------------------------------------------


def request_checkpoint(delta_log, version: int) -> None:
    """Enqueue a background checkpoint of ``delta_log`` at ``version``.
    Requests coalesce per table — only the newest requested version builds.
    Never blocks and never raises into the committing writer."""
    try:
        with _LOCK:
            prev = _REQUESTS.get(delta_log.data_path)
            if prev is None or prev[1] < version:
                _REQUESTS[delta_log.data_path] = (delta_log, version)
        _ensure_writer()
        _WAKE.set()
    except Exception:  # noqa: BLE001 — the checkpoint is an optimization
        logger.debug("async checkpoint request failed", exc_info=True)


def _ensure_writer() -> None:
    global _WRITER
    if _WRITER is not None and _WRITER.is_alive():
        return
    with _LOCK:
        if _WRITER is not None and _WRITER.is_alive():
            return
        _WRITER = threading.Thread(target=_writer_loop, daemon=True,
                                   name="delta-ckpt-async")
        _WRITER.start()


def _writer_loop() -> None:  # pragma: no cover — exercised via flush() too
    while True:
        _WAKE.wait(timeout=2.0)
        _WAKE.clear()
        try:
            _drain(raise_errors=False)
        # delta-lint: ignore[crash-except] -- deliberately narrowed from
        # BaseException: SimulatedCrash now pierces and kills the daemon
        except Exception:  # noqa: BLE001 — the daemon survives IO failures,
            # but a BaseException (SimulatedCrash = process death,
            # KeyboardInterrupt) kills this thread like a real crash would;
            # the next request_checkpoint() revives a fresh writer — the
            # crash-resume shape the torture harness replays
            logger.debug("async checkpoint drain failed", exc_info=True)


def _drain(raise_errors: bool) -> int:
    built = 0
    with _IO_LOCK:
        while True:
            with _LOCK:
                if not _REQUESTS:
                    return built
                data_path = next(iter(_REQUESTS))
                delta_log, version = _REQUESTS.pop(data_path)
            try:
                build_checkpoint(delta_log, version)
                built += 1
            except BaseException as e:
                # a torn build (injected crash, IO failure) loses only the
                # optimization; the base may no longer match what landed on
                # disk, so forget it — the next build reconstructs fully
                _drop_base(data_path)
                if raise_errors or not isinstance(e, Exception):
                    # a SimulatedCrash/KeyboardInterrupt mid-batch must
                    # pierce even on the daemon path: swallowing it here
                    # would let a "dead" writer keep draining the queue
                    raise
                logger.warning("async checkpoint at version %s failed for %s",
                               version, data_path, exc_info=True)


def flush() -> int:
    """Synchronously build every pending request on the CALLING thread
    (tests, the torture harness); returns builds completed.
    Unlike the daemon, failures propagate to the caller."""
    return _drain(raise_errors=True)


def reset() -> None:
    """Drop pending requests and cached bases (tests). On-disk checkpoints
    are untouched."""
    with _LOCK:
        _REQUESTS.clear()
    with _BASE_LOCK:
        _BASES.clear()


def pending_requests() -> Dict[str, int]:
    with _LOCK:
        return {p: v for p, (_dl, v) in _REQUESTS.items()}


# ---------------------------------------------------------------------------
# Builds
# ---------------------------------------------------------------------------


def build_checkpoint(delta_log, version: int):
    """Build and publish the checkpoint at ``version``: incrementally from
    the cached base when ``delta.tpu.checkpoint.incremental`` allows it,
    else by full reconstruction (which seeds the base for next time).
    Returns the :class:`~delta_tpu.log.checkpoints.CheckpointMetaData`.

    Serialized under ``_IO_LOCK``: a direct caller (the torture harness's
    on-thread build, tests) never interleaves part writes or base seeding
    with the daemon building the same table."""
    from delta_tpu.storage import faults as faults_mod

    with _IO_LOCK:
        faults_mod.fire("checkpoint.asyncBuild",
                        filenames.checkpoint_file_single(version))
        incremental = conf.get_bool("delta.tpu.checkpoint.incremental", False)
        if incremental:
            md = _build_incremental(delta_log, version)
            if md is not None:
                telemetry.bump_counter("checkpoint.incremental.built")
                return md
            telemetry.bump_counter("checkpoint.incremental.fallback")
        snap = delta_log.unsafe_volatile_snapshot
        if snap is None or snap.version != version:
            snap = delta_log.get_snapshot_at(version)
        md = delta_log.checkpoint(snap)
        if incremental:
            _seed_base(delta_log.data_path, version,
                       _maybe_compact(delta_log, md, snap, snap._columnar))
        return md


def _facade_snapshot(delta_log, version: int, cols):
    """A Snapshot whose columnar state is pre-populated with ``cols`` — the
    checkpoint writers (columnar AND dataclass paths) read state through
    ``_columnar``/``_alive_mask``/``checkpoint_actions`` only, so this is a
    complete stand-in for a freshly decoded snapshot at ``version``."""
    from delta_tpu.log.snapshot import LogSegment, Snapshot

    seg = LogSegment(delta_log.log_path, version, deltas=[],
                     checkpoint_files=[], checkpoint_version=None,
                     last_commit_timestamp=delta_log.clock())
    snap = Snapshot(delta_log, version, seg)
    snap.__dict__["_columnar"] = cols  # primes the cached_property
    return snap


def _build_incremental(delta_log, version: int):
    """Checkpoint ``version`` = cached base at M + decode of commits
    M+1..version only. None when the base is missing/stale — caller falls
    back to full reconstruction."""
    from delta_tpu.log import columnar

    with _BASE_LOCK:
        base = _BASES.get(delta_log.data_path)
    if base is None or base.version >= version:
        return None
    tail_paths = [f"{delta_log.log_path}/{filenames.delta_file(v)}"
                  for v in range(base.version + 1, version + 1)]
    try:
        tail = columnar.decode_segment(delta_log.store, [], tail_paths)
    except FileNotFoundError:
        return None  # a tail commit is gone (cleanup/corruption): rebuild
    cols = columnar.extend_segment_columns(base.cols, tail)
    snap = _facade_snapshot(delta_log, version, cols)
    md = delta_log.checkpoint(snap)
    _seed_base(delta_log.data_path, version,
               _maybe_compact(delta_log, md, snap, cols))
    return md


def _maybe_compact(delta_log, md, snap, cols):
    """Bound the cached base's garbage: superseded rows accumulate across
    incremental rounds (each removed file keeps its dead add row). Once
    dead rows exceed the live count (floor 4096), re-decode the checkpoint
    just written — off the commit path, on this builder thread — and cache
    the compact form instead."""
    try:
        alive = int(snap._alive_mask.sum()) + len(snap.tombstones)
        if cols.num_rows <= max(4096, 2 * alive):
            return cols
        from delta_tpu.log import columnar
        from delta_tpu.log.checkpoints import CheckpointInstance

        inst = CheckpointInstance(md.version, md.parts)
        return columnar.decode_segment(
            delta_log.store, inst.paths(delta_log.log_path), [])
    except Exception:  # noqa: BLE001 — compaction is hygiene, not correctness
        return cols
