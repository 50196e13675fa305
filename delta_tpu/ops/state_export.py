"""Export table state as fixed-width columns for device computation.

The reference keeps table state as a Spark ``Dataset[SingleAction]``
(``Snapshot.scala:88-111``); scan planning filters it with Catalyst
expressions. Here the host turns AddFile metadata into SoA numpy columns —
paths and partition strings dictionary-encoded (int32 codes + host-side
dictionaries), sizes/timestamps/stats as int64/float64 lanes — which ship to
HBM for the pruning and replay kernels (``ops/pruning.py``,
``ops/replay_kernel.py``). Variable-length bytes never reach the device.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from delta_tpu.protocol.actions import Action, AddFile, Metadata, RemoveFile
from delta_tpu.utils.arrow import one_chunk as _one_chunk
from delta_tpu.schema.types import (
    ByteType,
    DataType,
    DateType,
    DoubleType,
    FloatType,
    IntegerType,
    LongType,
    ShortType,
    StructType,
    TimestampType,
)

__all__ = [
    "FileStateArrays",
    "files_to_arrays",
    "arrays_from_columns",
    "stats_json_table",
    "stats_table",
    "ReplayArrays",
    "actions_to_arrays",
]

_NUMERIC = (ByteType, ShortType, IntegerType, LongType, FloatType, DoubleType,
            DateType, TimestampType)


def _stat_to_lane(v: Any, dt: DataType) -> Optional[float]:
    """Normalize a JSON stats value to a comparable float64 lane value.

    Integers beyond 2^53 don't fit a float64 lane exactly — treating them as
    missing keeps pruning conservative (NULL keeps the file) instead of
    silently pruning on a rounded bound."""
    if v is None:
        return None
    if isinstance(v, int) and abs(v) > 2**53:
        return None
    try:
        if isinstance(dt, DateType) and isinstance(v, str):
            import datetime as _dt

            return float((_dt.date.fromisoformat(v[:10]) - _dt.date(1970, 1, 1)).days)
        if isinstance(dt, TimestampType) and isinstance(v, str):
            import datetime as _dt

            s = v.replace(" ", "T")
            if s.endswith("Z"):
                s = s[:-1] + "+00:00"
            d = _dt.datetime.fromisoformat(s)
            # tz-naive stats are wall-clock UTC; offset-carrying ones are
            # converted to the same instant (matches the Arrow json reader)
            if d.tzinfo is None:
                d = d.replace(tzinfo=_dt.timezone.utc)
            return float(d.timestamp() * 1e6)
        return float(v)
    except (ValueError, TypeError):
        return None


@dataclass
class FileStateArrays:
    """Snapshot AddFile metadata as device-shippable columns.

    ``paths`` stays on host (the dictionary); everything else is numpy and can
    be placed on device. Row i across all arrays describes ``paths[i]``.
    """

    paths: List[str]
    size: np.ndarray  # int64
    modification_time: np.ndarray  # int64
    num_records: np.ndarray  # int64, -1 = unknown
    partition_codes: Dict[str, np.ndarray]  # int32 codes, -1 = null
    partition_dicts: Dict[str, List[str]]  # code -> raw partition string
    stats_min: Dict[str, np.ndarray]  # float64, NaN = missing
    stats_max: Dict[str, np.ndarray]
    stats_null_count: Dict[str, np.ndarray]  # int64, -1 = missing

    @property
    def num_files(self) -> int:
        return len(self.paths)

    def device_env(self):
        """Bind columns as :class:`delta_tpu.expr.jaxeval.DeviceColumn`s using
        the flat names the skipping rewrite emits, lower-cased (``min.c`` /
        ``max.c`` / ``nullCount.c`` / ``numRecords`` / partition columns as
        codes). The float64 min/max lanes bind as exact int64 order keys
        (`jaxeval.f64_order_key`: a TPU's float64 is not IEEE) — the
        encoding `ops/pruning._compiled_skipping` lowers compares to."""
        from delta_tpu.expr.jaxeval import DeviceColumn, f64_order_key

        env = {"numRecords": DeviceColumn.of(self.num_records, self.num_records >= 0)}
        env["size"] = DeviceColumn.of(self.size)
        # partition codes are intentionally NOT bound under the column name:
        # a predicate literal compares against the VALUE, not the dictionary
        # code — binding codes here made `year = 2021` prune wrongly. Kernels
        # that want code-space comparison bind `partition_code.<c>` explicitly.
        for c, codes in self.partition_codes.items():
            env[f"partition_code.{c}"] = DeviceColumn.of(codes, codes >= 0)
        for c, mn in self.stats_min.items():
            env[f"min.{c}"] = DeviceColumn.of(f64_order_key(mn), ~np.isnan(mn))
        for c, mx in self.stats_max.items():
            env[f"max.{c}"] = DeviceColumn.of(f64_order_key(mx), ~np.isnan(mx))
        for c, nc in self.stats_null_count.items():
            env[f"nullCount.{c}"] = DeviceColumn.of(nc, nc >= 0)
        return {name.lower(): col for name, col in env.items()}


def files_to_arrays(
    files: Sequence[AddFile],
    metadata: Metadata,
    stats_columns: Optional[Sequence[str]] = None,
) -> FileStateArrays:
    """Columnarize AddFiles. ``stats_columns`` defaults to every numeric leaf
    of the data schema (the first ``dataSkippingNumIndexedCols`` columns —
    `DeltaConfig.scala:383` semantics are applied by the caller)."""
    schema: StructType = metadata.schema
    part_cols = list(metadata.partition_columns)
    if stats_columns is None:
        stats_columns = [
            f.name
            for f in schema.fields
            if f.name not in part_cols and isinstance(f.data_type, _NUMERIC)
        ]
    col_types: Dict[str, DataType] = {f.name: f.data_type for f in schema.fields}

    n = len(files)
    paths = [f.path for f in files]
    size = np.fromiter((f.size or 0 for f in files), np.int64, n)
    mtime = np.fromiter((f.modification_time or 0 for f in files), np.int64, n)

    part_codes: Dict[str, np.ndarray] = {}
    part_dicts: Dict[str, List[str]] = {}
    for c in part_cols:
        codes = np.empty(n, np.int32)
        mapping: Dict[str, int] = {}
        dictionary: List[str] = []
        for i, f in enumerate(files):
            v = (f.partition_values or {}).get(c)
            if v is None:
                codes[i] = -1
                continue
            code = mapping.get(v)
            if code is None:
                code = mapping[v] = len(dictionary)
                dictionary.append(v)
            codes[i] = code
        part_codes[c] = codes
        part_dicts[c] = dictionary

    num_records = np.full(n, -1, np.int64)
    smin = {c: np.full(n, np.nan) for c in stats_columns}
    smax = {c: np.full(n, np.nan) for c in stats_columns}
    snull = {c: np.full(n, -1, np.int64) for c in stats_columns}
    for i, f in enumerate(files):
        st = f.stats_dict()
        if not st:
            continue
        nr = st.get("numRecords")
        if nr is not None:
            num_records[i] = int(nr)
        mins = st.get("minValues") or {}
        maxs = st.get("maxValues") or {}
        nulls = st.get("nullCount") or {}
        for c in stats_columns:
            dt = col_types.get(c, DoubleType())
            v = _stat_to_lane(mins.get(c), dt)
            if v is not None:
                smin[c][i] = v
            v = _stat_to_lane(maxs.get(c), dt)
            if v is not None:
                smax[c][i] = v
            if nulls.get(c) is not None:
                snull[c][i] = int(nulls[c])

    return FileStateArrays(
        paths=paths,
        size=size,
        modification_time=mtime,
        num_records=num_records,
        partition_codes=part_codes,
        partition_dicts=part_dicts,
        stats_min=smin,
        stats_max=smax,
        stats_null_count=snull,
    )


def _temporal_to_lane(arr: pa.Array, dt: DataType) -> Optional[np.ndarray]:
    """Vectorized string→lane conversion for date/timestamp stats columns.
    Returns float64 with NaN for unparseable/missing, or None when the whole
    column can't be converted (caller treats as missing — conservative)."""
    import pyarrow.compute as pc

    def _to_ts_us(a: pa.Array) -> pa.Array:
        if pa.types.is_timestamp(a.type):
            # the json reader already normalized zone designators to UTC
            return a.cast(pa.timestamp("us")) if a.type.tz is None else (
                a.cast(pa.timestamp("us", tz="UTC")).cast(pa.timestamp("us")))
        s = a.cast(pa.string())
        try:
            return pc.cast(s, pa.timestamp("us"))  # tz-naive = wall-clock UTC
        except Exception:
            z = pc.replace_substring_regex(s, r"Z$", "+00:00")
            aware = pc.cast(z, pa.timestamp("us", tz="UTC"))
            return aware.cast(pa.timestamp("us"))

    try:
        if isinstance(dt, DateType):
            if pa.types.is_timestamp(arr.type) or pa.types.is_date(arr.type):
                days = arr.cast(pa.date32()).cast(pa.int32())
            else:
                days = arr.cast(pa.string()).cast(pa.date32()).cast(pa.int32())
            out = days.to_numpy(zero_copy_only=False).astype(np.float64)
        elif isinstance(dt, TimestampType):
            ts = _to_ts_us(arr)
            out = ts.cast(pa.int64()).to_numpy(zero_copy_only=False).astype(np.float64)
        else:
            return None
    except Exception:
        return None
    nulls = pc.is_null(arr).to_numpy(zero_copy_only=False)
    out[nulls] = np.nan
    return out


def _numeric_to_lane(arr: pa.Array) -> Optional[np.ndarray]:
    """Numeric stats column → float64 lane; int64 magnitudes beyond 2^53 are
    masked to NaN (same conservative rule as :func:`_stat_to_lane`)."""
    if not pa.types.is_integer(arr.type) and not pa.types.is_floating(arr.type):
        return None
    nulls = np.asarray(arr.is_null())
    if pa.types.is_integer(arr.type):
        ints = arr.cast(pa.int64()).to_numpy(zero_copy_only=False)
        out = ints.astype(np.float64)
        out[np.abs(ints) > 2**53] = np.nan
    else:
        out = arr.cast(pa.float64()).to_numpy(zero_copy_only=False).astype(np.float64)
    out[nulls] = np.nan
    return out


def string_prefix_lane_value(s: str) -> float:
    """First-6-bytes big-endian integer of a string's UTF-8 form, as an
    EXACT float64 (48 bits < 2^53). Monotone non-strict w.r.t. byte order:
    s1 <= s2 implies prefix(s1) <= prefix(s2), so range pruning over
    prefix lanes keeps a superset (never drops a match)."""
    b = s.encode("utf-8")[:6]
    v = 0
    for i, byte in enumerate(b):
        v += byte << (8 * (5 - i))
    return float(v)


def _string_prefix_lanes(arr) -> Optional[np.ndarray]:
    """Vectorized 6-byte prefix values for a pyarrow string array
    (null/non-string -> NaN). Pure-numpy over the Arrow buffers — no
    per-string Python objects."""
    import pyarrow.compute as pc

    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    if not pa.types.is_string(arr.type):
        return None
    valid = np.asarray(pc.is_valid(arr))
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], np.int32,
                            count=len(arr) + 1, offset=arr.offset * 4)
    data = np.frombuffer(bufs[2], np.uint8) if bufs[2] is not None else \
        np.empty(0, np.uint8)
    starts = offsets[:-1].astype(np.int64)
    lens = (offsets[1:] - offsets[:-1]).astype(np.int64)
    idx = starts[:, None] + np.arange(6)[None, :]
    mask = np.arange(6)[None, :] < np.minimum(lens, 6)[:, None]
    safe = np.clip(idx, 0, max(len(data) - 1, 0))
    b = np.where(mask, data[safe] if len(data) else 0, 0)
    weights = (256.0 ** np.arange(5, -1, -1))
    out = (b * weights[None, :]).sum(axis=1)
    out[~valid] = np.nan
    return out


def stats_json_table(st: pa.Array, explicit_schema: Optional[pa.Schema] = None):
    """One C++ ndjson parse of a per-file stats JSON string column.

    Returns ``(kind, parsed, idx)``: ``idx`` are the input row positions
    whose stats were non-blank and ``parsed`` is the Arrow table aligned
    with them (``kind == "ok"``). ``kind == "empty"`` means no stats at
    all; ``"newline"`` means a pretty-printed stats string would desync
    the ndjson rows (callers take a per-row path); ``"malformed"`` means
    the batch parse failed (callers treat every stat as missing — pruning
    stays conservative).

    ``explicit_schema`` pins the parsed column types (extra JSON fields are
    ignored). Callers that PERSIST the parsed values (the struct-stats
    checkpoint writer) must pass one: without it the Arrow JSON reader
    type-infers, and a *string* column whose values look like ISO dates
    ('2021-01-01') comes back as timestamp[s] — rendering it back to text
    would store a different literal than the table holds.

    The newline-join runs entirely in C++ (a ListArray wrapping slices of
    the column, then ``binary_join``) — a ``to_pylist`` + ``"\\n".join``
    here round-trips every string through Python objects and dominates the
    cold cache build. Joins run in <=1 GiB slices: one giant join would
    hit Arrow's 2 GiB int32 offset capacity on ~10M-file tables.
    """
    import pyarrow.compute as pc
    import pyarrow.json as pajson

    st = _one_chunk(st)
    blank = pc.if_else(pc.equal(pc.utf8_trim_whitespace(st.fill_null("")), ""), None, st)
    if bool(pc.any(pc.match_substring(blank.fill_null(""), "\n")).as_py() or False):
        return "newline", None, None
    valid = np.asarray(pc.is_valid(blank))
    idx = np.nonzero(valid)[0]
    compact = blank.drop_null()
    if isinstance(compact, pa.ChunkedArray):
        compact = compact.combine_chunks()
    if len(compact) == 0:
        return "empty", None, idx
    try:
        parts = []
        total = len(compact)
        start = 0
        budget = 1 << 30
        offs = np.frombuffer(compact.buffers()[1], np.int32,
                             count=total + 1, offset=compact.offset * 4)
        while start < total:
            end = start + 1
            base = offs[start]
            while end < total and offs[end + 1] - base <= budget:
                end += 1
            sl = compact.slice(start, end - start)
            sl = pa.concat_arrays([sl])  # re-materialize exact offsets
            lst = pa.ListArray.from_arrays(
                pa.array([0, len(sl)], pa.int32()), sl.cast(pa.string()))
            raw = pc.binary_join(lst, "\n").cast(pa.binary())[0].as_buffer()
            parse_opts = (pajson.ParseOptions(
                explicit_schema=explicit_schema,
                unexpected_field_behavior="ignore",
            ) if explicit_schema is not None else None)
            parts.append(pajson.read_json(
                pa.BufferReader(raw),
                read_options=pajson.ReadOptions(use_threads=True,
                                                block_size=8 << 20),
                parse_options=parse_opts,
            ))
            start = end
        parsed = (parts[0] if len(parts) == 1
                  else pa.concat_tables(parts, promote_options="permissive"))
    except Exception:
        return "malformed", None, None
    if parsed.num_rows != len(idx):
        return "malformed", None, None
    return "ok", parsed, idx


def arrays_from_columns(
    cols,
    rows_mask: np.ndarray,
    metadata: Metadata,
    stats_columns: Optional[Sequence[str]] = None,
    sort_by_path: bool = False,
    string_prefix_cols: Sequence[str] = (),
) -> Optional[FileStateArrays]:
    """Vectorized :class:`FileStateArrays` straight from a columnar segment
    (``delta_tpu.log.columnar.SegmentColumns``) — no AddFile dataclasses.

    Stat lanes prefer the checkpoint's typed ``stats_parsed`` struct
    columns (zero JSON: float64 lanes build directly from typed Arrow
    leaves); rows or columns the struct doesn't cover fall back to one C++
    ndjson pass over the raw stats strings (``pyarrow.json``), replacing a
    Python loop over ``stats_dict()`` calls — at 1M files this is the
    difference between a cache build in seconds vs minutes. Partition
    values come vectorized from the checkpoint map columns (or the tail's
    JSON lines). Returns None for shapes neither path can carry, and
    callers fall back to :func:`files_to_arrays`.
    """
    import pyarrow.compute as pc

    rows = np.nonzero(rows_mask)[0] if rows_mask.dtype == bool else np.asarray(rows_mask)
    part_cols = list(metadata.partition_columns)
    part_codes: Dict[str, np.ndarray] = {}
    part_dicts: Dict[str, List[str]] = {}
    if part_cols:
        # dictionary-code partition values straight from the columnar batches
        # (checkpoint map columns / tail JSON lines) — the dynamic-key map
        # never materializes dataclasses
        strings = cols.partition_strings(rows, part_cols)
        if strings is None:
            return None
        for c in part_cols:
            enc = strings[c].dictionary_encode()
            if isinstance(enc, pa.ChunkedArray):
                enc = enc.combine_chunks()
            codes = enc.indices.fill_null(-1).to_numpy(
                zero_copy_only=False).astype(np.int32, copy=False)
            part_codes[c] = codes
            part_dicts[c] = enc.dictionary.to_pylist()
    paths = cols.paths_for(rows)
    size = cols.size[rows].copy()
    mtime = cols.modification_time[rows].copy()
    if sort_by_path:
        order = pc.sort_indices(pa.array(paths)).to_numpy(zero_copy_only=False)
        rows, size, mtime = rows[order], size[order], mtime[order]
        paths = [paths[i] for i in order]
        for c in part_cols:
            part_codes[c] = part_codes[c][order]

    schema: StructType = metadata.schema
    if stats_columns is None:
        stats_columns = [
            f.name for f in schema.fields
            if f.name not in set(part_cols) and isinstance(f.data_type, _NUMERIC)
        ]
    prefix_set = {c for c in string_prefix_cols if c not in set(part_cols)}
    stats_columns = list(stats_columns) + [
        c for c in sorted(prefix_set) if c not in set(stats_columns)
    ]
    col_types: Dict[str, DataType] = {f.name: f.data_type for f in schema.fields}

    n = len(rows)
    num_records = np.full(n, -1, np.int64)
    smin = {c: np.full(n, np.nan) for c in stats_columns}
    smax = {c: np.full(n, np.nan) for c in stats_columns}
    snull = {c: np.full(n, -1, np.int64) for c in stats_columns}
    out = FileStateArrays(
        paths=paths, size=size, modification_time=mtime, num_records=num_records,
        partition_codes=part_codes, partition_dicts=part_dicts,
        stats_min=smin, stats_max=smax, stats_null_count=snull,
    )
    if n == 0:
        return out

    import time as _time

    from delta_tpu.utils.telemetry import bump_counter

    _t0 = _time.perf_counter()

    def _lane_us():
        # stats-lane build time in µs (telemetry: the "parse time" component
        # of a cold state-cache build, isolated from the shared path/size
        # extraction)
        bump_counter("stateExport.statsLanes.us",
                     int((_time.perf_counter() - _t0) * 1e6))

    # -- typed struct-stats fast path (zero JSON) --------------------------
    # Checkpoints written with `stats_parsed` (struct columns typed from the
    # table schema) surface it through the columnar segment; the lanes then
    # build from typed Arrow leaves with no JSON parse at all. Rows the
    # struct misses (JSON commit tails, old checkpoint parts) fall back to
    # the batched ndjson parse below, restricted to just those rows.
    struct_rows: Optional[np.ndarray] = None  # bool mask: struct-covered rows
    sp = cols.stats_parsed
    if sp is not None:
        sp = sp.take(pa.array(rows, pa.int64()))
        sp = _one_chunk(sp)
        struct_rows = _struct_stat_lanes(
            sp, stats_columns, prefix_set, col_types,
            num_records, smin, smax, snull)
    if struct_rows is not None and (cols.stats is None
                                    or bool(struct_rows.all())):
        # every row struct-served: never materialize the JSON string column
        bump_counter("stateExport.statsLanes.struct")
        _lane_us()
        return out

    st = None
    if cols.stats is not None:
        st = _one_chunk(cols.stats.take(pa.array(rows, pa.int64())))
    if struct_rows is not None:
        json_rows = np.asarray(pc.is_valid(st)) & ~struct_rows
        if not json_rows.any():
            bump_counter("stateExport.statsLanes.struct")
            _lane_us()
            return out
        # mask the struct-covered rows out of the JSON pass
        st = pc.if_else(pa.array(json_rows), st, pa.scalar(None, pa.string()))
        bump_counter("stateExport.statsLanes.mixed")
    if st is None:
        return out

    kind, parsed, idx = stats_json_table(st)
    if kind == "newline":
        # pretty-printed stats would desync the ndjson rows — bail to the
        # dataclass path, which parses per row
        return None
    if kind != "ok":
        _lane_us()
        return out  # no/malformed stats → all-missing (keeps every file)
    if struct_rows is None:
        bump_counter("stateExport.statsLanes.json")

    def _scatter_f(dst: np.ndarray, lane: Optional[np.ndarray]):
        if lane is not None:
            dst[idx] = lane

    names = parsed.column_names
    if "numRecords" in names:
        nr = parsed.column("numRecords").combine_chunks()
        lane = _numeric_to_lane(nr)
        if lane is not None:
            vals = np.where(np.isnan(lane), -1, lane).astype(np.int64)
            num_records[idx] = vals
    for struct_name, dest in (("minValues", smin), ("maxValues", smax)):
        if struct_name not in names:
            continue
        col = parsed.column(struct_name).combine_chunks()
        t = col.type
        if not pa.types.is_struct(t):
            continue
        fields = {t.field(i).name for i in range(t.num_fields)}
        for c in stats_columns:
            if c not in fields:
                continue
            leaf = pc.struct_field(col, c)
            if c in prefix_set:
                lane = _string_prefix_lanes(leaf)
            else:
                lane = _numeric_to_lane(leaf)
                if lane is None:
                    lane = _temporal_to_lane(leaf, col_types.get(c, DoubleType()))
            _scatter_f(dest[c], lane)
    if "nullCount" in names:
        col = parsed.column("nullCount").combine_chunks()
        t = col.type
        if pa.types.is_struct(t):
            fields = {t.field(i).name for i in range(t.num_fields)}
            for c in stats_columns:
                if c not in fields:
                    continue
                lane = _numeric_to_lane(pc.struct_field(col, c))
                if lane is not None:
                    snull[c][idx] = np.where(np.isnan(lane), -1, lane).astype(np.int64)
    _lane_us()
    return out




def _struct_fieldset(t: pa.DataType, name: str) -> set:
    if not pa.types.is_struct(t):
        return set()
    for i in range(t.num_fields):
        f = t.field(i)
        if f.name == name:
            if pa.types.is_struct(f.type):
                return {f.type.field(j).name for j in range(f.type.num_fields)}
            return set()
    return set()


def _struct_stat_lanes(sp, stats_columns, prefix_set, col_types,
                       num_records, smin, smax, snull) -> Optional[np.ndarray]:
    """Scatter stat lanes from a ``stats_parsed`` struct column (aligned
    with the output rows). Returns the bool mask of rows the struct served,
    or None when it cannot serve this request — struct absent/all-null, or
    a requested column missing from its min/max fields (the JSON path then
    computes everything, so no column is half-served)."""
    import pyarrow.compute as pc

    if sp is None or not pa.types.is_struct(sp.type):
        return None
    minf = _struct_fieldset(sp.type, "minValues")
    maxf = _struct_fieldset(sp.type, "maxValues")
    if not set(stats_columns) <= (minf & maxf):
        return None
    sp_valid = np.asarray(pc.is_valid(sp))
    if not sp_valid.any():
        return None
    idx = np.nonzero(sp_valid)[0]
    spc = sp if len(idx) == len(sp) else sp.take(pa.array(idx, pa.int64()))
    top = {sp.type.field(i).name for i in range(sp.type.num_fields)}
    if "numRecords" in top:
        lane = _numeric_to_lane(_one_chunk(pc.struct_field(spc, "numRecords")))
        if lane is not None:
            num_records[idx] = np.where(np.isnan(lane), -1, lane).astype(np.int64)
    for struct_name, dest in (("minValues", smin), ("maxValues", smax)):
        col = _one_chunk(pc.struct_field(spc, struct_name))
        for c in stats_columns:
            leaf = _one_chunk(pc.struct_field(col, c))
            if c in prefix_set:
                lane = _string_prefix_lanes(leaf)
            else:
                lane = _numeric_to_lane(leaf)
                if lane is None:
                    lane = _temporal_to_lane(leaf, col_types.get(c, DoubleType()))
            if lane is not None:
                dest[c][idx] = lane
    ncf = _struct_fieldset(sp.type, "nullCount")
    if ncf:
        col = _one_chunk(pc.struct_field(spc, "nullCount"))
        for c in stats_columns:
            if c not in ncf:
                continue
            lane = _numeric_to_lane(_one_chunk(pc.struct_field(col, c)))
            if lane is not None:
                snull[c][idx] = np.where(np.isnan(lane), -1, lane).astype(np.int64)
    return sp_valid


def stats_table(files: Sequence[AddFile], metadata: Metadata,
                stats_columns: Optional[Sequence[str]] = None) -> pa.Table:
    """Host (Arrow) view of per-file stats for the vectorized skipping path —
    includes string columns the device path can't carry."""
    from delta_tpu.expr.partition import typed_partition_row

    schema: StructType = metadata.schema
    part_cols = set(metadata.partition_columns)
    part_schema = metadata.partition_schema
    if stats_columns is None:
        stats_columns = [f.name for f in schema.fields if f.name not in part_cols]
    rows: List[Dict[str, Any]] = []
    for f in files:
        st = f.stats_dict() or {}
        row: Dict[str, Any] = {"numRecords": st.get("numRecords")}
        mins = st.get("minValues") or {}
        maxs = st.get("maxValues") or {}
        nulls = st.get("nullCount") or {}
        for c in stats_columns:
            row[f"min.{c}"] = mins.get(c)
            row[f"max.{c}"] = maxs.get(c)
            row[f"nullCount.{c}"] = nulls.get(c)
        # typed partition values: constant per file, bound so mixed
        # partition/data predicates evaluate the partition leg exactly
        row.update(typed_partition_row(f, part_schema))
        rows.append(row)
    return pa.Table.from_pylist(rows) if rows else pa.table({"numRecords": pa.nulls(0, pa.int64())})


# -- raw action-stream export for the replay kernel -----------------------


@dataclass
class ReplayArrays:
    """A log segment's Add/Remove stream as device columns, in commit order.

    ``seq`` is the global action order (commit version major, position within
    the commit minor) — the sort key that makes last-writer-wins a segmented
    max (`actions/InMemoryLogReplay.scala:43-65` semantics).
    """

    paths: List[str]  # dictionary: path_id -> path
    path_id: np.ndarray  # int32, one per action row
    seq: np.ndarray  # int64
    is_add: np.ndarray  # bool
    size: np.ndarray  # int64 (0 for removes without size)
    deletion_timestamp: np.ndarray  # int64, only for removes (0 otherwise)
    row_action: List[Action] = field(default_factory=list)  # aligned originals

    @property
    def num_rows(self) -> int:
        return len(self.path_id)


def actions_to_arrays(versioned_actions: Sequence[Tuple[int, Sequence[Action]]]) -> ReplayArrays:
    """Flatten ``[(version, actions), ...]`` into :class:`ReplayArrays`,
    keeping only file actions (Metadata/Protocol/txns replay on host)."""
    mapping: Dict[str, int] = {}
    dictionary: List[str] = []
    path_id: List[int] = []
    seq: List[int] = []
    is_add: List[bool] = []
    size: List[int] = []
    del_ts: List[int] = []
    originals: List[Action] = []
    for version, actions in versioned_actions:
        for pos, a in enumerate(actions):
            if isinstance(a, AddFile):
                add = True
                sz = a.size or 0
                dts = 0
            elif isinstance(a, RemoveFile):
                add = False
                sz = a.size or 0
                dts = a.delete_timestamp
            else:
                continue
            code = mapping.get(a.path)
            if code is None:
                code = mapping[a.path] = len(dictionary)
                dictionary.append(a.path)
            path_id.append(code)
            # 31 bits of intra-commit position (2B actions/commit), 32 of
            # version; overflow raises rather than silently sharing a seq
            # (ties would make the replay sort's last-writer-wins arbitrary)
            if pos >= 1 << 31:
                raise ValueError(
                    f"commit {version} has {pos + 1}+ file actions; "
                    "more than 2^31 per commit is unsupported"
                )
            if version >= 1 << 32:
                raise ValueError(
                    f"version {version} exceeds 2^32; seq encoding unsupported"
                )
            seq.append((version << 31) | pos)
            is_add.append(add)
            size.append(sz)
            del_ts.append(dts)
            originals.append(a)
    return ReplayArrays(
        paths=dictionary,
        path_id=np.asarray(path_id, np.int32),
        seq=np.asarray(seq, np.int64),
        is_add=np.asarray(is_add, bool),
        size=np.asarray(size, np.int64),
        deletion_timestamp=np.asarray(del_ts, np.int64),
        row_action=originals,
    )
