"""Transactional write path: Arrow batch → partitioned Parquet → AddFiles.

Equivalent of `files/TransactionalWrite.scala:43-207` +
`files/DelayedCommitProtocol.scala:41-164`: normalize the batch to the table
schema, enforce constraints (vectorized, `schema/constraints.py`), split by
partition values, write `part-<n>-<uuid>.c000.snappy.parquet` files directly
into partition directories (no rename — the commit *is* the transaction log
entry), and return `AddFile` actions carrying protocol-format stats.

Like the reference's committer, files become visible only via the commit;
orphaned files from failed writes are invisible to readers and reaped by
VACUUM.
"""
from __future__ import annotations

import json
import os
import urllib.parse
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.exec import parquet as pq_exec
from delta_tpu.exec.rowgroups import stats_from_footer
from delta_tpu.expr.vectorized import arrow_type_for
from delta_tpu.protocol.actions import AddFile, Metadata
from delta_tpu.schema import constraints as constraints_mod
from delta_tpu.schema.types import StructType
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import DeltaConfigs
from delta_tpu.utils.errors import SchemaMismatchError

__all__ = ["normalize_data", "write_files", "escape_partition_value", "partition_path"]

# Hive-style partition-path escaping (util/PartitionUtils.scala vendored copy
# of Spark's ExternalCatalogUtils): these characters are %-encoded in dir names.
_ESCAPE = set('\\"#%\'*/:=?\x7f[]^ \t\n\x0b\x0c\r{}')
HIVE_DEFAULT_PARTITION = "__HIVE_DEFAULT_PARTITION__"


def escape_partition_value(v: Optional[str]) -> str:
    if v is None or v == "":
        return HIVE_DEFAULT_PARTITION
    return "".join(f"%{ord(c):02X}" if c in _ESCAPE or ord(c) < 0x20 else c for c in v)


def unescape_partition_value(s: str) -> Optional[str]:
    if s == HIVE_DEFAULT_PARTITION:
        return None
    return urllib.parse.unquote(s)


def partition_path(partition_values: Dict[str, Optional[str]], partition_columns: Sequence[str]) -> str:
    return "/".join(
        f"{c}={escape_partition_value(partition_values.get(c))}" for c in partition_columns
    )


def _resolve(table: pa.Table, name: str) -> Optional[str]:
    if name in table.column_names:
        return name
    low = name.lower()
    for c in table.column_names:
        if c.lower() == low:
            return c
    return None


def normalize_data(table: pa.Table, schema: StructType) -> pa.Table:
    """Reorder/case-normalize/cast the batch to the table schema
    (`TransactionalWrite.scala:79-115` normalizeData)."""
    cols = []
    fields = []
    for f in schema.fields:
        src = _resolve(table, f.name)
        target_type = arrow_type_for(f.data_type)
        if src is None:
            # missing column → nulls (schema enforcement happens upstream)
            cols.append(pa.nulls(table.num_rows, target_type))
        else:
            col = table.column(src)
            if col.type != target_type:
                try:
                    col = pc.cast(col, target_type)
                except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
                    raise SchemaMismatchError(
                        f"Cannot cast column {f.name} from {col.type} to {target_type}: {e}"
                    )
            cols.append(col)
        fields.append(pa.field(f.name, target_type, f.nullable))
    extra = [
        c for c in table.column_names
        if all(c.lower() != f.name.lower() for f in schema.fields)
    ]
    if extra:
        raise SchemaMismatchError(
            f"Data columns {extra} not present in table schema "
            f"{[f.name for f in schema.fields]} (enable mergeSchema to add them)"
        )
    return pa.table(cols, schema=pa.schema(fields))


def _split_by_partition(
    table: pa.Table, part_cols: Sequence[str]
) -> List[Tuple[Dict[str, Optional[str]], pa.Table]]:
    """One sort + linear run-boundary scan instead of one full-table mask per
    partition value (O(n log n) vs O(groups × rows))."""
    import numpy as np

    t = table.sort_by([(c, "ascending") for c in part_cols])
    n = t.num_rows
    if n == 0:
        return []
    change = np.zeros(n, bool)
    change[0] = True
    for c in part_cols:
        col = pa.chunked_array(t.column(c)).combine_chunks()
        prev, cur = col.slice(0, n - 1), col.slice(1)
        neq = pc.fill_null(pc.not_equal(cur, prev), False)
        # null↔value transitions are boundaries; null↔null is not
        null_b = pc.xor(pc.is_null(cur), pc.is_null(prev))
        m = pc.or_(neq, null_b)
        if pa.types.is_floating(col.type):
            # NaN != NaN would split every NaN row into its own group
            both_nan = pc.and_(
                pc.fill_null(pc.is_nan(cur), False),
                pc.fill_null(pc.is_nan(prev), False),
            )
            m = pc.and_(m, pc.invert(both_nan))
        change[1:] |= np.asarray(m)
    starts = np.flatnonzero(change)
    bounds = np.append(starts, n)
    out: List[Tuple[Dict[str, Optional[str]], pa.Table]] = []
    for i, s in enumerate(starts):
        chunk = t.slice(int(s), int(bounds[i + 1] - s))
        pv = {c: _partition_value_str(chunk.column(c)[0]) for c in part_cols}
        out.append((pv, chunk))
    return out


def _partition_value_str(scalar: pa.Scalar) -> Optional[str]:
    v = scalar.as_py()
    if v is None:
        return None
    if isinstance(v, bool):
        return "true" if v else "false"
    return str(v)


def write_files(
    data_path: str,
    table: pa.Table,
    metadata: Metadata,
    data_change: bool = True,
    target_file_rows: Optional[int] = None,
    constraints: Optional[List[constraints_mod.Constraint]] = None,
) -> List[AddFile]:
    """Write a normalized batch as partitioned Parquet; return AddFiles.

    Files encode in parallel on a thread pool (Arrow's Parquet writer drops
    the GIL) — the host fan-out the reference gets from `FileFormatWriter`
    parallel tasks (`files/TransactionalWrite.scala:182-192`). Batches larger
    than ``delta.tpu.write.targetFileRows`` split into multiple files so the
    encode parallelizes and later scans decode in parallel.

    Three spans tile the call under whatever span the caller holds:
    ``delta.write.prepare`` (everything before the first byte is encoded),
    then for each file, on the thread that writes it, ``delta.write.encode``
    and ``delta.write.stats``. A file's statistics come from the footer its
    encoder just made (`rowgroups.stats_from_footer`: the encoder has
    computed every column's min, max and null count already); where the
    footer declines (a nested column, bounds withheld or not the rows' own)
    they come from a pass over the file's rows (`parquet.stats_json`), and
    the string is the same either way. ``source`` on the stats span says
    which (``footer`` or ``decode``); ``write.stats.footer`` and
    ``write.stats.decoded`` count the files."""
    with telemetry.record_operation(
            "delta.write.prepare",
            {"rows": table.num_rows, "columns": table.num_columns,
             "chunksIn": table.column(0).num_chunks if table.num_columns else 0}
    ) as pev:
        jobs, num_indexed = _plan_files(table, metadata, target_file_rows,
                                        constraints)
        pev.data["files"] = len(jobs)

    def write_one(job) -> AddFile:
        pv, rel, file_data = job
        abs_path = os.path.join(data_path, rel.replace("/", os.sep))
        with telemetry.record_operation(
                "delta.write.encode", {"rows": file_data.num_rows}) as eev:
            size, mtime, footer = pq_exec.write_parquet_file(file_data, abs_path)
            eev.data["bytes"] = size
        with telemetry.record_operation(
                "delta.write.stats", {"columns": file_data.num_columns}) as sev:
            from_footer = stats_from_footer(footer, num_indexed)
            if from_footer is not None:
                stats = json.dumps(from_footer)
                sev.data["source"] = "footer"
                telemetry.bump_counter("write.stats.footer")
            else:
                stats = pq_exec.stats_json(file_data, num_indexed)
                sev.data["source"] = "decode"
                telemetry.bump_counter("write.stats.decoded")
        return AddFile(
            # AddFile.path is URI-encoded per the protocol (the hive-
            # escaped dir's '%' becomes '%25'); readers unquote once.
            # safe set = URI path chars java Path.toUri leaves bare.
            path=urllib.parse.quote(rel, safe="/:@!$&'()*+,;=-._~"),
            partition_values=pv,
            size=size,
            modification_time=mtime,
            data_change=data_change,
            stats=stats,
        )

    if len(jobs) <= 1:
        return [write_one(j) for j in jobs]
    from concurrent.futures import ThreadPoolExecutor

    workers = min(len(jobs), os.cpu_count() or 4)
    # span-context propagation: a file's encode and stats spans parent under
    # the enclosing command span instead of orphan worker roots
    with ThreadPoolExecutor(max_workers=workers,
                            thread_name_prefix="delta-parquet-write") as pool:
        return list(pool.map(telemetry.propagated(write_one), jobs))


def _plan_files(
    table: pa.Table,
    metadata: Metadata,
    target_file_rows: Optional[int],
    constraints: Optional[List[constraints_mod.Constraint]],
) -> Tuple[List[Tuple[Dict[str, Optional[str]], str, pa.Table]], int]:
    """`write_files` up to the encoder: the batch checked, normalized to the
    table's schema and split into ``(partition values, relative path, file
    table)`` jobs; and how many leading columns get statistics."""
    from delta_tpu.utils.config import conf

    schema: StructType = metadata.schema
    part_cols = list(metadata.partition_columns)
    # ambiguous (case-insensitively duplicated) batch columns would silently
    # drop data during cast/resolution — reject at ANY nesting level, and
    # before generated-column computation whose lookups would KeyError on
    # them (`SchemaUtils.checkColumnNameDuplication`)
    from delta_tpu.schema.arrow_interop import schema_from_arrow
    from delta_tpu.schema.schema_utils import check_column_name_duplication

    check_column_name_duplication(
        schema_from_arrow(table.schema), "in the data to save"
    )
    # generated columns: compute the missing, verify the provided — must see
    # the batch before normalize_data turns missing columns into nulls
    from delta_tpu.schema import generated as generated_mod

    table = generated_mod.compute_on_write(table, schema)
    table = normalize_data(table, schema)
    # Defragment heavily-chunked inputs (join/filter outputs arrive as
    # hundreds of small chunks): one contiguous copy is cheap next to the
    # per-chunk costs the Parquet encoder pays on fragmented columns.
    if table.num_columns and table.column(0).num_chunks > 4:
        table = table.combine_chunks()
    # char/varchar write semantics: pad char(n) to width, enforce length
    # bounds (CharVarcharUtils.scala write-side behavior)
    from delta_tpu.schema import char_varchar

    table = char_varchar.apply_write_semantics(table, metadata)
    if constraints is None:
        constraints = constraints_mod.from_metadata(metadata)
    constraints_mod.enforce(constraints, table)
    num_indexed = DeltaConfigs.DATA_SKIPPING_NUM_INDEXED_COLS.from_metadata(metadata)
    if target_file_rows is None:
        target_file_rows = int(conf.get("delta.tpu.write.targetFileRows", 4_000_000))

    data_cols = [f.name for f in schema.fields if f.name not in part_cols]

    groups: List[Tuple[Dict[str, Optional[str]], pa.Table]] = []
    if part_cols:
        groups = _split_by_partition(table, part_cols)
    else:
        groups.append(({}, table))

    # plan all (partition values, relative path, file table) jobs up front,
    # then encode on a thread pool
    jobs: List[Tuple[Dict[str, Optional[str]], str, pa.Table]] = []
    for pv, part_table in groups:
        if part_table.num_rows == 0:
            continue
        chunks: List[pa.Table] = []
        if target_file_rows and part_table.num_rows > target_file_rows:
            for start in range(0, part_table.num_rows, target_file_rows):
                chunks.append(part_table.slice(start, target_file_rows))
        else:
            chunks.append(part_table)
        prefix = partition_path(pv, part_cols)
        for idx, chunk in enumerate(chunks):
            file_data = chunk.select(data_cols) if part_cols else chunk
            name = f"part-{idx:05d}-{uuid.uuid4()}.c000.snappy.parquet"
            rel = f"{prefix}/{name}" if prefix else name
            jobs.append((pv, rel, file_data))

    return jobs, num_indexed
