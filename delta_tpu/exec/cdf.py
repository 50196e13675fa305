"""Change Data Feed: per-commit row-level change capture.

The reference at 0.9 carries the ``cdc`` action in its protocol
(``actions/actions.scala:328-341``) but blocks writing it
(``actions.scala:151-156``); modern Delta ships the full feature. This
module implements it end to end:

* **Write side** — DML on tables with ``delta.enableChangeDataFeed=true``
  stages change rows (``_change_type`` ∈ insert / delete /
  update_preimage / update_postimage) that commit as Parquet files under
  ``_change_data/`` logged with ``AddCDCFile`` actions (``dataChange=false``
  so they never affect table state replay).
* **Read side** — :func:`read_changes` returns the changes between two
  versions with ``_change_type`` / ``_commit_version`` /
  ``_commit_timestamp`` columns. Commits without CDC files are
  reconstructed from their file actions: dataChange adds → inserts,
  dataChange removes of dropped files → deletes (read through the
  tombstone's deletion vector), and deletion-vector re-adds → deletes of
  the newly-marked positions (old-DV/new-DV diff).
"""
from __future__ import annotations

import os
import uuid
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from delta_tpu.protocol.actions import AddCDCFile, AddFile, RemoveFile
from delta_tpu.utils import errors

__all__ = [
    "CHANGE_TYPE_COL",
    "COMMIT_VERSION_COL",
    "COMMIT_TIMESTAMP_COL",
    "CDC_DIR",
    "write_change_data",
    "read_changes",
]

CHANGE_TYPE_COL = "_change_type"
COMMIT_VERSION_COL = "_commit_version"
COMMIT_TIMESTAMP_COL = "_commit_timestamp"
CDC_DIR = "_change_data"


def cdf_enabled(metadata) -> bool:
    from delta_tpu.utils.config import DeltaConfigs

    return bool(DeltaConfigs.CHANGE_DATA_FEED.from_metadata(metadata))


def write_change_data(
    data_path: str,
    blocks: Sequence[Tuple[str, pa.Table]],
    metadata,
) -> List[AddCDCFile]:
    """Write change blocks (``(change_type, rows)``) as one CDC Parquet file.

    Rows are stored with every table column (partition columns included —
    unlike data files, CDC files are self-contained) plus ``_change_type``.
    """
    from delta_tpu.exec.parquet import write_parquet_file

    target_cols = [f.name for f in metadata.schema.fields]
    parts: List[pa.Table] = []
    for change_type, rows in blocks:
        if rows is None or rows.num_rows == 0:
            continue
        t = rows.select([c for c in target_cols if c in rows.column_names])
        t = t.append_column(
            CHANGE_TYPE_COL, pa.array([change_type] * t.num_rows, pa.string())
        )
        parts.append(t)
    if not parts:
        return []
    out = pa.concat_tables(parts, promote_options="permissive")
    rel = f"{CDC_DIR}/cdc-{uuid.uuid4()}.c000.snappy.parquet"
    abs_path = os.path.join(data_path, CDC_DIR, os.path.basename(rel))
    size, _mtime, _footer = write_parquet_file(out, abs_path)
    return [AddCDCFile(path=rel, partition_values={}, size=size)]


def _read_file_rows(
    data_path: str, add_like, metadata, dv_dict=None
) -> pa.Table:
    """Read a data file's rows as they were live under ``dv_dict``."""
    from delta_tpu.exec.scan import read_files_as_table

    add = AddFile(
        path=add_like.path,
        partition_values=dict(add_like.partition_values or {}),
        size=add_like.size or 0,
        deletion_vector=dv_dict,
    )
    [t] = read_files_as_table(data_path, [add], metadata, per_file=True)
    return t


def _dv_positions(data_path: str, dv_dict) -> np.ndarray:
    from delta_tpu.protocol import deletion_vectors as dv_mod

    if not dv_dict:
        return np.array([], np.uint32)
    return dv_mod.read_deletion_vector(
        dv_mod.DeletionVectorDescriptor.from_dict(dv_dict), data_path
    )


def read_changes(
    delta_log,
    starting_version: int,
    ending_version: Optional[int] = None,
) -> pa.Table:
    """The table's change feed for versions [starting, ending] (inclusive)."""
    snapshot = delta_log.update()
    if ending_version is None:
        ending_version = snapshot.version
    if starting_version > snapshot.version:
        raise errors.cdf_start_after_latest(starting_version, snapshot.version)
    if starting_version > ending_version:
        raise errors.cdf_start_after_end(starting_version, ending_version)
    # data-loss guard: silently skipping retention-cleaned commits would
    # hide their deletes/updates from the consumer
    earliest = delta_log.history.get_earliest_delta_file()
    if starting_version < earliest:
        raise errors.cdf_start_unavailable(starting_version, earliest)
    metadata = snapshot.metadata
    target_cols = [f.name for f in metadata.schema.fields]
    commits = {
        c.version: c.timestamp
        for c in delta_log.history.get_commits(starting_version, ending_version)
    }

    out_parts: List[pa.Table] = []

    def emit(rows: pa.Table, change_type: Optional[str], version: int):
        if rows.num_rows == 0:
            return
        keep = [c for c in rows.column_names
                if c in target_cols or c == CHANGE_TYPE_COL]
        t = rows.select(keep)
        if change_type is not None:
            t = t.append_column(
                CHANGE_TYPE_COL, pa.array([change_type] * t.num_rows, pa.string())
            )
        t = t.append_column(
            COMMIT_VERSION_COL, pa.array([version] * t.num_rows, pa.int64())
        )
        t = t.append_column(
            COMMIT_TIMESTAMP_COL,
            pa.array([commits.get(version, 0)] * t.num_rows, pa.int64()),
        )
        out_parts.append(t)

    for version, actions in delta_log.get_changes(starting_version):
        if version > ending_version:
            break
        cdc_files = [a for a in actions if isinstance(a, AddCDCFile)]
        if cdc_files:
            from delta_tpu.exec.parquet import read_parquet_files

            abs_paths = [
                os.path.join(delta_log.data_path, c.path.replace("/", os.sep))
                for c in cdc_files
            ]
            for t in read_parquet_files(abs_paths):
                emit(t, None, version)
            continue
        # reconstruction: no CDC files in this commit
        adds: Dict[str, AddFile] = {
            a.path: a for a in actions
            if isinstance(a, AddFile) and a.data_change
        }
        removes: Dict[str, RemoveFile] = {
            a.path: a for a in actions
            if isinstance(a, RemoveFile) and a.data_change
        }
        for path, add in adds.items():
            rm = removes.get(path)
            if rm is not None:
                # deletion-vector re-add: the change is the newly-marked rows
                from delta_tpu.commands.dml_common import POSITION_COL
                from delta_tpu.exec.scan import read_files_as_table

                old = _dv_positions(delta_log.data_path, rm.deletion_vector)
                new = _dv_positions(delta_log.data_path, add.deletion_vector)
                newly = np.setdiff1d(new, old)
                if newly.size == 0:
                    continue
                bare = AddFile(path=add.path,
                               partition_values=dict(add.partition_values or {}),
                               size=add.size)
                # the newly-marked positions are known before any decode:
                # read only the row groups containing them (positions stay
                # physical, so the isin selection below is unchanged)
                [t] = read_files_as_table(
                    delta_log.data_path, [bare], metadata, per_file=True,
                    position_column=POSITION_COL,
                    positions_of_interest=[newly],
                )
                sel = np.isin(
                    t.column(POSITION_COL).to_numpy(zero_copy_only=False), newly
                )
                emit(t.filter(pa.array(sel)), "delete", version)
            else:
                emit(
                    _read_file_rows(delta_log.data_path, add, metadata,
                                    dv_dict=add.deletion_vector),
                    "insert", version,
                )
        for path, rm in removes.items():
            if path in adds:
                continue  # handled as DV diff above
            rows = _read_file_rows(
                delta_log.data_path, rm, metadata, dv_dict=rm.deletion_vector
            )
            emit(rows, "delete", version)

    if not out_parts:
        schema = pa.schema(
            [pa.field(CHANGE_TYPE_COL, pa.string()),
             pa.field(COMMIT_VERSION_COL, pa.int64()),
             pa.field(COMMIT_TIMESTAMP_COL, pa.int64())]
        )
        return schema.empty_table()
    return pa.concat_tables(out_parts, promote_options="permissive")
