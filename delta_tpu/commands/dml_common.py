"""Shared DML machinery: candidate selection and file rewrites.

The reference's `commands/DeltaCommand.scala:48-219` equivalent — resolve the
files a predicate may touch (partition pruning + stats skipping), read them,
and rewrite survivors — but columnar: per-file row masks come from one
vectorized predicate evaluation instead of `input_file_name()` joins.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import pyarrow as pa

from delta_tpu.exec.scan import read_files_as_table
from delta_tpu.expr import ir
from delta_tpu.expr.vectorized import boolean_mask
from delta_tpu.ops import pruning
from delta_tpu.protocol.actions import AddFile

__all__ = [
    "TouchedFile",
    "candidate_files",
    "read_candidates",
    "Timer",
    "POSITION_COL",
    "dv_enabled",
    "dv_mark_deleted",
    "dv_mark_from_mask",
]

# physical-row-position column attached to scans when deletion vectors are on
POSITION_COL = "__pos__"


class Timer:
    """Phase timer for operation metrics (scanTimeMs / rewriteTimeMs)."""

    def __init__(self):
        self.t0 = time.perf_counter()

    def lap_ms(self) -> int:
        now = time.perf_counter()
        ms = int((now - self.t0) * 1000)
        self.t0 = now
        return ms


@dataclass
class TouchedFile:
    add: AddFile
    table: pa.Table  # full rows of the file (with partition columns)
    mask: pa.ChunkedArray  # True = row matches the predicate


def candidate_files(txn, predicate: Optional[ir.Expression]) -> List[AddFile]:
    """Files the predicate may touch; registers the read set on the txn.

    Conjuncts are split so a mixed predicate (``part='a' AND data>5``)
    records the partition leg as the transaction's read predicate — keeping
    the OCC read set partition-scoped instead of whole-table — while stats
    skipping still applies the data leg."""
    if predicate is None:
        return txn.filter_files()
    conjuncts = ir.split_conjuncts(predicate)
    matched = txn.filter_files(conjuncts)
    scan = pruning.files_for_scan(txn.snapshot, [predicate])
    kept_paths = {f.path for f in scan.files}
    return [f for f in matched if f.path in kept_paths]


def read_candidates(
    data_path: str,
    files: Sequence[AddFile],
    metadata,
    predicate: Optional[ir.Expression],
    with_positions: bool = False,
    prune_row_groups: bool = False,
) -> List[TouchedFile]:
    """Read each candidate (parallel decode) and compute its match mask.

    ``prune_row_groups=True`` pushes the predicate into the decode so row
    groups that definitely contain no matches never leave disk
    (`exec/rowgroups`). Only safe when the caller never rewrites untouched
    rows — i.e. deletion-vector DML, which consumes ONLY mask-True rows
    (their physical positions stay correct under skipping). The rewrite
    path must read files whole: rows in pruned groups are exactly the
    non-matching rows it must copy forward."""
    out: List[TouchedFile] = []
    tables = read_files_as_table(
        data_path, files, metadata, per_file=True,
        position_column=POSITION_COL if with_positions else None,
        predicate=predicate if prune_row_groups else None,
    )
    for add, t in zip(files, tables):
        if predicate is None:
            mask = pa.chunked_array([pa.array([True] * t.num_rows)])
        else:
            mask = boolean_mask(predicate, t)
        out.append(TouchedFile(add=add, table=t, mask=mask))
    return out


def dv_enabled(metadata) -> bool:
    from delta_tpu.utils.config import DeltaConfigs, conf

    if not bool(conf.get("delta.tpu.deletionVectors.enabled", True)):
        return False  # session kill switch (forces the rewrite path)
    return bool(DeltaConfigs.ENABLE_DELETION_VECTORS.from_metadata(metadata))


def dv_mark_from_mask(data_path: str, add: AddFile, table: pa.Table, mask):
    """DV-mark the rows of ``table`` (a :class:`TouchedFile` read with
    positions) selected by ``mask``; see :func:`dv_mark_deleted`."""
    import pyarrow.compute as pc

    positions = pc.filter(table.column(POSITION_COL), mask).to_numpy(
        zero_copy_only=False
    )
    return dv_mark_deleted(data_path, add, positions)


def dv_mark_deleted(data_path: str, add: AddFile, matched_positions):
    """Mark physical row positions deleted via a deletion vector.

    Returns ``(remove, new_add)``: a tombstone for the old file entry and a
    re-add of the same path carrying the union of the old DV and
    ``matched_positions``. ``new_add`` is None when every live row is gone —
    the file is then simply removed. Replay handles the re-add by path
    last-wins (`actions/InMemoryLogReplay.scala:43-65` semantics unchanged).
    """
    import numpy as np
    from dataclasses import replace as _replace

    from delta_tpu.protocol import deletion_vectors as dv_mod

    matched_positions = np.asarray(matched_positions, dtype=np.uint32)
    old_rows = None
    if add.deletion_vector is not None:
        old_rows = dv_mod.read_deletion_vector(
            dv_mod.DeletionVectorDescriptor.from_dict(add.deletion_vector),
            data_path,
        )
        all_rows = np.union1d(old_rows, matched_positions)
    else:
        all_rows = np.unique(matched_positions)
    live = add.num_logical_records
    if live is not None and len(all_rows) >= live:
        return add.remove(), None
    desc = dv_mod.write_deletion_vector(all_rows, data_path)
    return add.remove(), _replace(add, deletion_vector=desc.to_dict(), data_change=True)
