"""Parquet read/write executor (host data plane, Arrow C++ underneath).

The role Spark's `ParquetFileFormat` + `FileFormatWriter` play in the
reference (`files/TransactionalWrite.scala:182-192`, `DeltaFileFormat.scala`)
— encode/decode Parquet, collect per-file column stats — lands on Arrow's
native Parquet module here. Stats collection follows the protocol's
per-column ``minValues``/``maxValues``/``nullCount`` + ``numRecords`` schema
(`PROTOCOL.md:441-480`), truncated to the first
``dataSkippingNumIndexedCols`` leaf columns (`DeltaConfig.scala:383`).
"""
from __future__ import annotations

import datetime as _dt
import decimal as _decimal
import json
import math
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq

__all__ = [
    "write_parquet_file",
    "read_parquet_files",
    "collect_stats",
    "stats_json",
    "json_stat_value",
]


def json_stat_value(v: Any, round_up: bool = False) -> Any:
    """Encode one Python min/max value for the protocol's JSON stats —
    shared by the decode path (:func:`collect_stats`) and the footer path
    (`exec.rowgroups.stats_from_footer`), so both emit identical bounds."""
    if isinstance(v, _dt.datetime):
        if round_up and v.microsecond % 1000:
            # maxValues truncated to ms must round UP or data skipping would
            # prune files containing sub-millisecond maxima
            v = v + _dt.timedelta(microseconds=1000 - v.microsecond % 1000)
        return v.strftime("%Y-%m-%dT%H:%M:%S.%f")[:-3] + "Z"
    if isinstance(v, _dt.date):
        return v.isoformat()
    if isinstance(v, float) and (math.isnan(v) or math.isinf(v)):
        return None
    if isinstance(v, bytes):
        return None  # binary stats not representable in JSON stats
    if isinstance(v, _decimal.Decimal):
        # JSON can't carry exact decimals as numbers; a float conversion can
        # shift the bound inward (wrongly pruning matching files) and an
        # outward nudge breaks the column's scale for the V2 stats_parsed
        # struct — absent bounds are the only always-safe encoding
        return None
    return v


def _stat_value(scalar: pa.Scalar, round_up: bool = False) -> Any:
    return json_stat_value(scalar.as_py(), round_up)


def has_stat_bounds(t: pa.DataType) -> bool:
    """Whether a column of Arrow type ``t`` gets ``minValues``/``maxValues``
    at all: one rule for the decode path and the footer path."""
    return (
        pa.types.is_integer(t)
        or pa.types.is_floating(t)
        or pa.types.is_string(t)
        or pa.types.is_date(t)
        or pa.types.is_timestamp(t)
        or pa.types.is_boolean(t)
        or pa.types.is_decimal(t)
    )


def collect_stats(table: pa.Table, num_indexed_cols: int = 32) -> Dict[str, Any]:
    """Per-file stats over the first ``num_indexed_cols`` leaf columns."""
    mins: Dict[str, Any] = {}
    maxs: Dict[str, Any] = {}
    nulls: Dict[str, Any] = {}
    for name in table.column_names[: num_indexed_cols if num_indexed_cols >= 0 else None]:
        col = table.column(name)
        nulls[name] = col.null_count
        if not has_stat_bounds(col.type) or col.null_count == len(col):
            continue
        try:
            mn = _stat_value(pc.min(col))
            mx = _stat_value(pc.max(col), round_up=True)
        except pa.ArrowNotImplementedError:
            continue
        if mn is not None:
            mins[name] = mn
        if mx is not None:
            maxs[name] = mx
    return {
        "numRecords": table.num_rows,
        "minValues": mins,
        "maxValues": maxs,
        "nullCount": nulls,
    }


def stats_json(table: pa.Table, num_indexed_cols: int = 32) -> str:
    return json.dumps(collect_stats(table, num_indexed_cols))


def _compresses_well(col: pa.ChunkedArray, sample_bytes: int = 65536) -> bool:
    """Cheap entropy probe: snappy-compress the first ~64KB of the column's
    raw buffers; ratio < 0.9 means compression earns its keep. High-entropy
    numerics (random keys, hashes) fail this and store uncompressed — snappy
    on incompressible int64 pages costs 4x encode / 14x decode for ~10%."""
    try:
        chunk = col.chunk(0) if col.num_chunks else None
        if chunk is None or len(chunk) == 0:
            return True
        # sample the DATA buffer (last) — the validity bitmap compresses to
        # nothing and would misjudge every nullable high-entropy column
        bufs = [b for b in chunk.buffers() if b is not None]
        if not bufs:
            return True
        data = bufs[-1]
        raw = bytes(data.slice(0, min(sample_bytes, data.size)))  # zero-copy slice
        if len(raw) < 1024:
            return True
        return len(pa.compress(raw, codec="snappy", asbytes=True)) < 0.9 * len(raw)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, IndexError):
        return True


def write_parquet_file(
    table: pa.Table, abs_path: str, compression: Optional[str] = None
) -> Tuple[int, int, "pq.FileMetaData"]:
    """Write one Parquet file; returns (size_bytes, mtime_ms, footer).

    ``footer`` is the ``FileMetaData`` the encoder made for the file it just
    wrote (nothing is read back): every column's min, max and null count for
    every row group are in it, so `exec.rowgroups.stats_from_footer` gives
    the file's protocol statistics without a second pass over the rows.

    One file encodes on one host core (pyarrow hands Python no threaded
    column encode); `write_files` spreads several files over a pool.
    Encoding policy (measured on store_sales-shaped data, on that one core):

    - dictionary pages only for string/binary columns — dictionary-encoding
      high-cardinality numerics bloats files and makes reads 4-5x slower;
    - BYTE_STREAM_SPLIT for float columns (faster encode, much faster
      decode, compresses as well as plain+snappy). Gate with
      ``delta.tpu.write.byteStreamSplit=false`` for parquet-mr < 1.12
      readers (Spark <= 3.1);
    - per-column compression: snappy only where it earns its keep (strings,
      BYTE_STREAM_SPLIT float streams); high-entropy integer columns store
      uncompressed — snappy on random int64 pages costs 4x on encode and
      14x (!) on decode for a ~10% size win;
    - decimals as the integers Spark's writer stores them
      (``spark.sql.parquet.writeLegacyFormat=false``): INT32 up to precision
      9, INT64 up to 18, ``FIXED_LEN_BYTE_ARRAY`` above. A byte-array decimal
      decodes one value at a time; a ``decimal(7,2)`` is 4 bytes either way.
      Files written before as byte arrays read back the same.

    ``delta.tpu.write.compression`` overrides: "auto" (policy above) or a
    codec name applied to every column."""
    from delta_tpu.utils.config import conf

    os.makedirs(os.path.dirname(abs_path), exist_ok=True)
    dict_cols = [
        f.name for f in table.schema
        if pa.types.is_string(f.type) or pa.types.is_large_string(f.type)
        or pa.types.is_binary(f.type)
    ]
    kwargs: Dict[str, Any] = {"use_dictionary": dict_cols or False}
    float_cols = [f.name for f in table.schema if pa.types.is_floating(f.type)]
    if float_cols and bool(conf.get("delta.tpu.write.byteStreamSplit", True)):
        kwargs["use_byte_stream_split"] = float_cols
    if compression is None:
        compression = str(conf.get("delta.tpu.write.compression", "auto"))
    if compression == "auto":
        codec: Any = {
            f.name: (
                "snappy"
                if f.name in dict_cols or f.name in float_cols
                or _compresses_well(table.column(f.name))
                else "none"
            )
            for f in table.schema
        }
    else:
        codec = compression
    # defragment before encode: heavily chunked tables (hash-join output,
    # many-block concats) encode one page set per chunk otherwise
    if table.num_rows and table.column(0).num_chunks > 8:
        table = table.combine_chunks()
    # bounded row groups are the skipping granule of the read path's second
    # pruning tier (exec/rowgroups): Arrow's 1Mi-row default would leave
    # most engine-written files as ONE group, with nothing to skip
    rg_rows = int(conf.get("delta.tpu.write.rowGroupRows", 131_072))
    if rg_rows > 0:
        kwargs["row_group_size"] = rg_rows
    footer: List[pq.FileMetaData] = []
    pq.write_table(table, abs_path, compression=codec,
                   store_decimal_as_integer=True,
                   metadata_collector=footer, **kwargs)
    st = os.stat(abs_path)
    from delta_tpu.utils.telemetry import bump_counter

    bump_counter("parquet.files.written")
    bump_counter("parquet.bytes.written", st.st_size)
    bump_counter("parquet.rows.written", table.num_rows)
    return st.st_size, int(st.st_mtime * 1000), footer[0]


def read_parquet_files(
    abs_paths: Sequence[str],
    columns: Optional[Sequence[str]] = None,
    schema: Optional[pa.Schema] = None,
) -> List[pa.Table]:
    """Read data files; one table per file (callers attach partition values
    before concatenation). Files decode in parallel on a thread pool —
    Arrow's Parquet reader drops the GIL, the same host fan-out
    ``write_files``/``read_files_as_table`` already use."""

    def read_one(p: str) -> pa.Table:
        return pq.read_table(
            p, columns=list(columns) if columns else None, memory_map=True,
        )

    if len(abs_paths) <= 1:
        return [read_one(p) for p in abs_paths]
    from concurrent.futures import ThreadPoolExecutor

    from delta_tpu.utils import telemetry

    workers = min(len(abs_paths), os.cpu_count() or 4)
    # propagate the caller's span context into the pool: any span or event
    # a decode emits parents under the calling operation instead of
    # starting an orphan trace root in the worker thread
    with ThreadPoolExecutor(
        max_workers=workers, thread_name_prefix="delta-parquet-read"
    ) as pool:
        return list(pool.map(telemetry.propagated(read_one), abs_paths))
