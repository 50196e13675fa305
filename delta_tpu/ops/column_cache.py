"""Device-resident hot-column scan cache + the jitted residual-filter path.

PR 12 made the scan *planner* device-servable; every surviving row group
still decoded on host Arrow and evaluated the residual predicate through
Arrow compute. This module keeps the decode product itself accelerator-side
for the predicate columns: per-(table, file, column) SoA lanes —
dictionary-encoded strings as int32 codes, temporal columns as epoch
days/µs, numerics widened to lane dtypes — live in HBM across queries, and
the residual filter mask is computed in ONE jitted pass per file
(`expr/jaxeval.compile_residual` + `compile_expr`). Only survivor rows are
then fetched / late-materialized on host (`exec/scan.read_files_as_table`'s
``device_masks``), with result identity guaranteed by construction: the
mask is the exact Kleene TRUE set of the residual, and ``scan_to_table``
re-applies the same residual over the survivors.

Cache discipline mirrors `ops/key_cache.KeyCache`: a process-wide singleton
keyed by (log path, file path, column), per-table rewrite epochs
(:meth:`ColumnCache.bump_epoch` — OPTIMIZE/UPDATE/DELETE-rewrite/RESTORE
drop the table's lanes outright; a decode racing a rewrite is served but
never cached), LRU eviction under
``min(delta.tpu.columnCache.maxBytes, hbm_ledger.column_cache_allowance())``
(the process-wide soft HBM budget, `obs/hbm_ledger` component
``columnCache``), and per-table ``columnCache.residentBytes`` residency
gauges. Parquet files are immutable, so a resident lane never goes stale
for the file it decoded — the epoch machinery frees rewritten tables'
memory promptly and guarantees a post-rewrite scan can only see lanes that
re-decode from the new files.

The device-vs-host choice routes through `parallel/link` pricing
(``HOST_RESIDUAL_S_PER_CELL`` / ``DEVICE_RESIDUAL_S_PER_CELL``, both
calibratable) and every decision is audited via `obs/router_audit` under
``op="scan.residual"`` — the same observability contract as the MERGE
router. ``delta.tpu.read.deviceResidual.mode``: ``auto`` prices each scan,
``force`` always engages (the benchmark's ``force`` pins, tests), ``off``
disables.
"""
from __future__ import annotations

import functools
import os
import threading
import time
import urllib.parse
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from delta_tpu.expr import ir, jaxeval
from delta_tpu.expr.jaxeval import NotDeviceCompilable
from delta_tpu.obs import hbm_ledger
from delta_tpu.ops.state_cache import _next_pow2  # shared pad-size bucketing
from delta_tpu.parallel import link
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf
from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64

__all__ = ["ResidentColumn", "ResidentKeep", "ColumnCache",
           "device_residual_masks", "column_cache_enabled"]

#: the column slot of a file's keep mask in the cache's key: no column of a
#: table is named so
KEEP = "\x00keep"


def column_cache_enabled() -> bool:
    return str(conf.get("delta.tpu.read.deviceResidual.mode", "auto")
               ).lower() != "off"


def lane_budget() -> int:
    """Bytes the cache may keep resident: its own bound, under the HBM
    ledger's allowance where a device budget is set."""
    budget = int(conf.get("delta.tpu.columnCache.maxBytes", 1 << 30))
    allowance = hbm_ledger.column_cache_allowance()
    return budget if allowance is None else min(budget, allowance)


def _abs_data_path(data_path: str, file_path: str) -> str:
    if "://" in file_path or os.path.isabs(file_path):
        return urllib.parse.unquote(file_path)
    return os.path.join(data_path,
                        urllib.parse.unquote(file_path).replace("/", os.sep))


def _lane_from_arrow(arr) -> Optional[Tuple[np.ndarray, np.ndarray,
                                            Optional[Dict[str, int]]]]:
    """Decode one Arrow column to its device lane encoding:
    ``(values, valid, dict)`` — strings become int32 dictionary codes with
    the value→code map returned for literal binding, date32 becomes epoch
    days (int32), timestamps epoch µs (int64), numerics widen to
    int64, floats become int64 order keys (`jaxeval.f64_order_key`: a
    TPU's float64 is not IEEE), ``decimal(p, s)`` with ``p <= 18`` becomes
    its unscaled values as int64 (exact; `jaxeval.compile_residual` scales
    the literals to ``s``). Returns None for types with no lane form."""
    import pyarrow as pa
    import pyarrow.compute as pc

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    valid = pc.is_valid(arr).to_numpy(zero_copy_only=False).astype(bool)
    t = arr.type
    if pa.types.is_string(t) or pa.types.is_large_string(t):
        enc = arr.dictionary_encode()
        codes = enc.indices.fill_null(-1).to_numpy(
            zero_copy_only=False).astype(np.int32, copy=False)
        mapping = {v: i for i, v in enumerate(enc.dictionary.to_pylist())}
        return codes, valid, mapping
    if pa.types.is_date(t):
        vals = arr.cast(pa.date32()).cast(pa.int32()).fill_null(0).to_numpy(
            zero_copy_only=False).astype(np.int32, copy=False)
    elif pa.types.is_timestamp(t):
        vals = arr.cast(pa.timestamp("us")).cast(pa.int64()).fill_null(
            0).to_numpy(zero_copy_only=False).astype(np.int64, copy=False)
    elif pa.types.is_boolean(t):
        vals = arr.fill_null(False).to_numpy(
            zero_copy_only=False).astype(bool)
    elif pa.types.is_integer(t):
        vals = arr.cast(pa.int64()).fill_null(0).to_numpy(
            zero_copy_only=False).astype(np.int64, copy=False)
    elif pa.types.is_floating(t):
        vals = jaxeval.f64_order_key(
            arr.cast(pa.float64()).fill_null(0.0).to_numpy(
                zero_copy_only=False))
    elif pa.types.is_decimal128(t) \
            and t.precision <= jaxeval.DECIMAL_LANE_PRECISION:
        # 16 little-endian bytes a value; below 10^18 the low word is the
        # value (two's complement), whatever a NULL's slot holds is zeroed
        words = np.frombuffer(arr.buffers()[1], np.int64)
        vals = np.where(valid, words[2 * arr.offset:
                                     2 * (arr.offset + len(arr)):2], 0)
    else:
        return None
    return vals, valid, None


class ResidentColumn:
    """One decoded (file, column) lane resident in HBM: values + validity
    padded to the shared pow2 buckets (`state_cache._next_pow2`) so files of
    similar size hit the same jit shape-cache entry; pad rows carry
    ``valid=False`` and slice away after the mask download. String lanes
    keep their host-side value→code dictionary for per-scan literal
    binding. ``lo`` / ``hi`` bound the lane's integers (a NULL's slot holds
    0), from the decode: what `ops/column_aggregate` proves an exact int64
    sum from."""

    __slots__ = ("log_path", "file_path", "column", "values", "valid", "n",
                 "dict_codes", "nbytes", "epoch", "last_used", "lo", "hi",
                 "_account", "_lock", "__weakref__")

    def __init__(self, log_path: str, file_path: str, column: str,
                 values: np.ndarray, valid: np.ndarray,
                 dict_codes: Optional[Dict[str, int]], epoch: int):
        self.log_path = log_path
        self.file_path = file_path
        self.column = column
        self.n = int(len(values))
        integers = self.n and values.dtype.kind == "i"
        self.lo = int(values.min()) if integers else 0
        self.hi = int(values.max()) if integers else 0
        cap = _next_pow2(max(self.n, 1), floor=64)
        pv = np.zeros(cap, dtype=values.dtype)
        pv[: self.n] = values
        pm = np.zeros(cap, dtype=bool)
        pm[: self.n] = valid
        self.nbytes = int(pv.nbytes + pm.nbytes)
        self.dict_codes = dict_codes
        self.epoch = epoch
        self.last_used = 0
        self._lock = threading.Lock()
        self._account = hbm_ledger.Account("columnCache")
        with enable_x64():
            self.values = link.to_device(pv)
            self.valid = link.to_device(pm)
        self._account.on(self, self.nbytes)

    @property
    def is_resident(self) -> bool:
        return self.values is not None

    def device_column(self) -> jaxeval.DeviceColumn:
        return jaxeval.DeviceColumn(self.values, self.valid)

    def drop_device(self) -> None:
        with self._lock:
            self.values = None
            self.valid = None
            self._account.off()


class ResidentKeep:
    """One file's deletion vector resident as a row mask beside the file's
    lanes: False where the vector names the row, padded with True to the
    lanes' length (the programs cut the padding by the file's row count).
    An entry of the cache like a lane, under the column slot ``KEEP``:
    counted in its bytes, dropped with the table's epoch, and freed when
    the file's last lane is evicted. ``vector`` is the identity of the
    vector it was built from (a commit that deletes more rows of the file
    writes a new one): `ensure_keep` rebuilds on another."""

    __slots__ = ("log_path", "file_path", "column", "vector", "mask",
                 "deleted", "nbytes", "epoch", "last_used", "_account",
                 "_lock", "__weakref__")

    def __init__(self, log_path: str, file_path: str, vector: Tuple[str, str],
                 deleted_rows: np.ndarray, cap: int, epoch: int):
        self.log_path = log_path
        self.file_path = file_path
        self.column = KEEP
        self.vector = vector
        self.deleted = int(len(deleted_rows))
        keep = np.ones(cap, dtype=bool)
        keep[deleted_rows] = False
        self.nbytes = int(keep.nbytes)
        self.epoch = epoch
        self.last_used = 0
        self._lock = threading.Lock()
        self._account = hbm_ledger.Account("columnCache")
        self.mask = link.to_device(keep)
        self._account.on(self, self.nbytes)

    @property
    def is_resident(self) -> bool:
        return self.mask is not None

    def drop_device(self) -> None:
        with self._lock:
            self.mask = None
            self._account.off()


class ColumnCache:
    """Process-wide registry of resident scan-column lanes, keyed by
    (log path, file path, column). Locking and epoch discipline mirror
    `ops/key_cache.KeyCache`; entries are immutable after construction
    (Parquet files never change), so there are no build locks or version
    advances — only residency and the per-table rewrite epoch."""

    _instance: Optional["ColumnCache"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._entries: Dict[Tuple[str, str, str], ResidentColumn] = {}
        self._lock = threading.RLock()
        self._tick = 0
        # per-table rewrite generation (bump_epoch): lanes decoded under an
        # older epoch are never cached, and a bump drops the table's lanes
        self._epochs: Dict[str, int] = {}
        self._last_resident: set = set()
        self._published_bytes: Dict[str, int] = {}

    @classmethod
    def instance(cls) -> "ColumnCache":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = ColumnCache()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._instance_lock:
            cls._instance = None

    def epoch(self, log_path: str) -> int:
        with self._lock:
            return self._epochs.get(log_path, 0)

    def bump_epoch(self, log_path: str) -> None:
        """File-rewrite invalidation (OPTIMIZE / UPDATE / DELETE-rewrite /
        RESTORE): drop the table's resident lanes outright — the rewritten
        files' lanes are garbage, and the epoch guard keeps any decode that
        raced the rewrite from being cached under the new generation."""
        from delta_tpu.utils.telemetry import bump_counter

        with self._lock:
            self._epochs[log_path] = self._epochs.get(log_path, 0) + 1
            stale = [k for k in self._entries if k[0] == log_path]
            for k in stale:
                self._entries.pop(k).drop_device()
        if stale:
            bump_counter("columnCache.invalidations", len(stale))
            self._publish_residency()

    def invalidate(self, log_path: str) -> None:
        with self._lock:
            for k in [k for k in self._entries if k[0] == log_path]:
                self._entries.pop(k).drop_device()
        self._publish_residency()

    def get(self, log_path: str, file_path: str,
            column: str) -> Optional[ResidentColumn]:
        with self._lock:
            self._tick += 1
            key = (log_path, file_path, column)
            e = self._entries.get(key)
            if e is not None and e.epoch != self._epochs.get(log_path, 0):
                # belt-and-braces: bump_epoch pops the table's entries, but
                # a registration racing the bump could have slipped in
                self._entries.pop(key, None)
                e.drop_device()
                return None
            if e is not None and e.is_resident:
                e.last_used = self._tick
                return e
            if e is not None:
                self._entries.pop(key, None)  # evicted husk
            return None

    def register(self, entry: ResidentColumn) -> bool:
        """Adopt a freshly decoded lane. Refused when the table's epoch
        moved during the decode (a rewrite raced it) — the caller's mask
        stays exact for its snapshot (file contents are immutable), so it
        serves the lane without caching it."""
        with self._lock:
            if entry.epoch != self._epochs.get(entry.log_path, 0):
                return False
            self._tick += 1
            entry.last_used = self._tick
            key = (entry.log_path, entry.file_path, entry.column)
            replaced = self._entries.get(key)
            if entry.column == KEEP and replaced is not None:
                replaced.drop_device()  # the mask of the vector before
            self._entries[key] = entry
        self._evict(keep=key)
        return True

    def resident_bytes(self) -> int:
        with self._lock:
            return sum(e.nbytes for e in self._entries.values()
                       if e.is_resident)

    def _publish_residency(self) -> None:
        """Per-table ``columnCache.residentBytes`` gauges (label: hashed
        table path), same contract as the key cache: mutation paths only,
        unchanged values skip the telemetry lock, a full drop publishes an
        explicit 0."""
        from delta_tpu.obs.fleet import table_label
        from delta_tpu.utils.telemetry import set_gauge

        with self._lock:
            by_table: Dict[str, int] = {t: 0 for t in self._last_resident}
            for (log_path, _f, _c), e in self._entries.items():
                if e.is_resident:
                    table = log_path[: -len("/_delta_log")] \
                        if log_path.endswith("/_delta_log") else log_path
                    by_table[table] = by_table.get(table, 0) + e.nbytes
            self._last_resident = {t for t, b in by_table.items() if b}
            changed = {t: b for t, b in by_table.items()
                       if self._published_bytes.get(t) != b}
            self._published_bytes.update(changed)
            for table, total in changed.items():
                set_gauge("columnCache.residentBytes", total,
                          table=table_label(table))

    def _evict(self, keep=None) -> None:
        from delta_tpu.utils.telemetry import bump_counter

        budget = lane_budget()
        max_entries = int(conf.get("delta.tpu.columnCache.maxEntries", 4096))
        dropped = 0
        lost: set = set()  # (table, file) that lost an entry
        with self._lock:
            resident = [(k, e) for k, e in self._entries.items()
                        if e.is_resident]
            total = sum(e.nbytes for _, e in resident)
            for k, e in sorted(resident, key=lambda kv: kv[1].last_used):
                if total <= budget and len(self._entries) <= max_entries:
                    break
                if k == keep:
                    continue
                self._entries.pop(k, None)
                e.drop_device()
                total -= e.nbytes
                dropped += 1
                lost.add(k[:2])
            # a keep mask goes with the file's last lane
            for table_file in lost:
                if not any(k[:2] == table_file and k[2] != KEEP
                           for k in self._entries):
                    orphan = self._entries.pop(table_file + (KEEP,), None)
                    if orphan is not None and orphan.is_resident:
                        orphan.drop_device()
                        dropped += 1
        if dropped:
            bump_counter("columnCache.evictions", dropped)
        self._publish_residency()


# -- the jitted residual mask kernel -----------------------------------------


@functools.lru_cache(maxsize=128)
def _mask_kernel(expr: ir.Expression):
    """jit-compiled Kleene-TRUE mask for a lowered residual — keyed on the
    (hashable) rewritten expression; pow2-padded lanes keep the XLA shape
    cache warm across similarly sized files."""
    ensure_compilation_cache()
    import jax

    fn = jaxeval.compile_expr(expr)

    def kernel(env):
        out = fn(env)
        return out.values.astype(bool) & out.valid

    return jax.jit(kernel)


def _scalar_column(value: Any) -> jaxeval.DeviceColumn:
    """A per-file scalar binding (partition value / string-literal code) as
    a broadcastable device scalar."""
    import datetime as _dt

    import jax.numpy as jnp

    if value is None:
        return jaxeval.DeviceColumn(jnp.zeros((), jnp.float32),
                                    jnp.zeros((), bool))
    if isinstance(value, bool):
        arr = np.asarray(value)
    elif isinstance(value, _dt.datetime):
        if value.tzinfo is None:
            value = value.replace(tzinfo=_dt.timezone.utc)
        arr = np.asarray(int(value.timestamp() * 1_000_000), np.int64)
    elif isinstance(value, _dt.date):
        arr = np.asarray((value - _dt.date(1970, 1, 1)).days, np.int32)
    elif isinstance(value, int):
        arr = np.asarray(value, np.int64)
    else:  # floats included: compile_residual declines float partitions
        raise NotDeviceCompilable(f"partition value {value!r} has no lane form")
    return jaxeval.DeviceColumn(link.to_device(arr), jnp.ones((), bool))


def _ensure_lanes(cache: "ColumnCache", log_path: str, data_path: str, add,
                  need: List[str], epoch: int,
                  counters: Dict[str, int]) -> Optional[Dict[str, ResidentColumn]]:
    """Resident lanes for one file's predicate columns, decoding misses
    cold (predicate columns ONLY — the projection still decodes lazily for
    survivors on host). Returns None when a column's Arrow type has no lane
    form. Lanes for columns the file predates bind all-invalid (NULL)."""
    out: Dict[str, ResidentColumn] = {}
    missing = []
    for c in need:
        e = cache.get(log_path, add.path, c)
        if e is not None:
            out[c] = e
            counters["hits"] += 1
        else:
            missing.append(c)
            counters["misses"] += 1
    if not missing:
        return out
    with telemetry.record_operation(
            "delta.columnCache.load",
            {"file": add.path, "columns": len(missing)}):
        import pyarrow.parquet as pq

        pf = pq.ParquetFile(_abs_data_path(data_path, add.path),
                            memory_map=True)
        present = {n.lower(): n for n in pf.schema_arrow.names}
        stored = [present[c] for c in missing if c in present]
        tbl = pf.read(columns=stored) if stored else None
        n_rows = pf.metadata.num_rows
        counters["coldBytes"] += sum(
            pf.metadata.row_group(i).total_byte_size
            for i in range(pf.metadata.num_row_groups)) if stored else 0
        for c in missing:
            if c in present:
                lane = _lane_from_arrow(tbl.column(present[c]))
                if lane is None:
                    return None
                vals, valid, codes = lane
            else:
                # schema evolution: the file predates the column → all-NULL
                vals = np.zeros(n_rows, np.int64)
                valid = np.zeros(n_rows, bool)
                codes = None
            entry = ResidentColumn(log_path, add.path, c, vals, valid, codes,
                                   epoch)
            cache.register(entry)  # epoch race → served uncached, still exact
            out[c] = entry
    return out


def ensure_keep(cache: "ColumnCache", log_path: str, data_path: str, add,
                cap: int) -> Tuple[Any, int, bool]:
    """``(mask, rows deleted, whether the cache held it)``: the keep mask of
    a file that has a deletion vector, as long as the file's lanes
    (``cap``), resident; the caller holds the array, so an eviction between
    this and the launch frees nothing in use. Built from the vector once
    (one read, one host array, one upload), then every launch of every
    query reuses it until a commit gives the file another vector. A build
    that raced a rewrite of the table is served and not cached, as a lane
    is."""
    from delta_tpu.protocol.deletion_vectors import (DeletionVectorDescriptor,
                                                     read_deletion_vector)

    dv = DeletionVectorDescriptor.from_dict(add.deletion_vector)
    vector = (dv.storage_type, dv.path_or_inline_dv)
    e = cache.get(log_path, add.path, KEEP)
    mask = None if e is None or e.vector != vector else e.mask
    if mask is not None and mask.shape[0] == cap:
        telemetry.bump_counter("columnCache.keep.hits")
        return mask, e.deleted, True
    telemetry.bump_counter("columnCache.keep.misses")
    e = ResidentKeep(log_path, add.path, vector,
                     read_deletion_vector(dv, data_path), cap,
                     cache.epoch(log_path))
    mask = e.mask
    cache.register(e)
    return mask, e.deleted, False


def device_residual_masks(snapshot, files, predicate) -> Optional[Dict[str, np.ndarray]]:
    """Per-file physical-row survivor masks for ``predicate``, computed on
    device from resident lanes — or None when the predicate doesn't lower,
    the router prices the host faster, or (``mode=auto``) anything on the
    device path fails — the caller's Arrow path is always correct on its
    own. Under ``mode=force`` a device exception propagates.

    The returned mask is the exact Kleene-TRUE row set of the residual for
    each file of THIS snapshot; deletion vectors are NOT applied here (the
    decode composes them downstream via physical positions)."""
    mode = str(conf.get("delta.tpu.read.deviceResidual.mode", "auto")).lower()
    if mode == "off" or predicate is None or not files:
        return None
    from delta_tpu.utils.telemetry import bump_counter

    metadata = snapshot.metadata
    log_path = snapshot.delta_log.log_path
    data_path = snapshot.delta_log.data_path
    try:
        from delta_tpu.expr.synthesis import schema_types

        types = schema_types(metadata)
        plan = jaxeval.compile_residual(predicate, types,
                                        metadata.partition_columns)
    except NotDeviceCompilable:
        bump_counter("scan.device.fallback")
        return None
    if not plan.refs:
        return None  # partition-only residual: file pruning already exact
    from delta_tpu.obs import router_audit, scan_report

    est_rows = sum(max((f.size or 0) // 64, 1024) for f in files)
    ncols = max(len(plan.refs), 1)
    cache = ColumnCache.instance()
    resident_rows = sum(
        e.n for f in files for c in plan.refs
        if (e := cache.get(log_path, f.path, c)) is not None) // ncols
    cold_rows = max(est_rows - resident_rows, 0)
    p = link.profile()
    predicted = {
        "device": link.device_residual_mask_s(cold_rows, resident_rows,
                                              ncols, p),
        "host": link.host_residual_filter_s(est_rows, ncols),
    }
    decision = "device" if (mode == "force"
                            or predicted["device"] < predicted["host"]) \
        else "host"
    if decision == "host":
        bump_counter("scan.device.declined")
        router_audit.record_audit(
            "scan.residual", data_path, "host", predicted,
            predicted["host"], units={"rows": est_rows, "cols": ncols},
            log_path=log_path, calibration_flush=False,
            files=len(files), mode=mode)
        return None
    counters = {"hits": 0, "misses": 0, "coldBytes": 0}
    part_schema = metadata.partition_schema
    masks: Dict[str, np.ndarray] = {}
    t0 = time.perf_counter()
    try:
        with enable_x64():
            kernel = _mask_kernel(plan.expr)
            for add in files:
                lanes = _ensure_lanes(cache, log_path, data_path, add,
                                      sorted(plan.refs), cache.epoch(log_path),
                                      counters)
                if lanes is None:
                    bump_counter("scan.device.fallback")
                    return None
                n = max((e.n for e in lanes.values()), default=0)
                env = {c: e.device_column() for c, e in lanes.items()}
                for ph, col, value in plan.str_binds:
                    codes = lanes[col].dict_codes or {}
                    env[ph] = _scalar_column(
                        int(codes.get(value, jaxeval.STR_CODE_ABSENT)))
                if plan.part_refs:
                    from delta_tpu.expr.partition import typed_partition_row

                    typed = typed_partition_row(add, part_schema)
                    lowered = {k.lower(): v for k, v in typed.items()}
                    for c in plan.part_refs:
                        env[c] = _scalar_column(lowered.get(c))
                with telemetry.record_operation("delta.columnCache.mask",
                                                {"rows": n}):
                    masks[add.path] = link.to_host(kernel(env))[:n]
    except NotDeviceCompilable:
        bump_counter("scan.device.fallback")
        return None
    except Exception as e:  # noqa: BLE001
        # the device path must never fail a scan the Arrow path can serve —
        # except under deviceResidual.mode=force, which pins the device: a
        # kernel the chip refuses must not read as a scan the device served
        if mode == "force":
            raise
        bump_counter("scan.device.fallback")
        telemetry.add_span_data(deviceError=telemetry.exc_text(e))
        return None
    actual_s = time.perf_counter() - t0
    bump_counter("scan.device.engaged")
    # onto the caller's span (`delta.scan.deviceMask` under a scan)
    telemetry.add_span_data(files=len(files), hits=counters["hits"],
                            misses=counters["misses"],
                            coldBytes=counters["coldBytes"])
    if counters["hits"]:
        bump_counter("columnCache.hits", counters["hits"])
    if counters["misses"]:
        bump_counter("columnCache.misses", counters["misses"])
    total_rows = sum(len(m) for m in masks.values())
    samples = []
    if total_rows and counters["misses"] == 0:
        # warm pass: the whole wall time is the kernel+download — a clean
        # sample for the device per-cell constant
        samples.append(("DEVICE_RESIDUAL_S_PER_CELL", total_rows * ncols,
                        actual_s))
    router_audit.record_audit(
        "scan.residual", data_path, "device", predicted, actual_s,
        units={"rows": total_rows, "cols": ncols},
        samples=samples, log_path=log_path, calibration_flush=False,
        files=len(files), cacheHits=counters["hits"],
        cacheMisses=counters["misses"], mode=mode)
    rep = scan_report.current_report()
    if rep is not None:
        rep.device_residual = "device"
    return masks
