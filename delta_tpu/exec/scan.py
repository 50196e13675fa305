"""Scan executor: snapshot + predicate → Arrow table.

The read side of the engine, replacing Spark's `FileSourceScanExec` over the
`TahoeFileIndex` (`files/TahoeFileIndex.scala:58-81`, SURVEY §3.2): prune the
file list on device (`ops/pruning.files_for_scan` — partition + min/max
skipping), decode the surviving Parquet with Arrow, materialize partition
columns from `partitionValues` (data files don't store them), and apply the
residual predicate with the vectorized evaluator.
"""
from __future__ import annotations

import os
import urllib.parse
from typing import Optional, Sequence, Union

import pyarrow as pa

from delta_tpu.expr import ir
from delta_tpu.expr.parser import parse_predicate
from delta_tpu.expr.partition import typed_partition_row
from delta_tpu.expr.vectorized import arrow_type_for, filter_table
from delta_tpu.ops import pruning
from delta_tpu.protocol.actions import AddFile
from delta_tpu.schema.types import StructType
from delta_tpu.utils.config import conf

__all__ = ["scan_files", "read_files_as_table", "scan_to_table", "plan_scans", "QueryPlan"]


def _abs_data_path(data_path: str, file_path: str) -> str:
    if "://" in file_path or os.path.isabs(file_path):
        return urllib.parse.unquote(file_path)
    return os.path.join(data_path, urllib.parse.unquote(file_path).replace("/", os.sep))


def _byte_array_decimals(schema, columns) -> int:
    """The decimal leaves under the top-level ``columns`` that a footer's
    schema stores as ``FIXED_LEN_BYTE_ARRAY``, the kind Arrow decodes one
    value at a time."""
    wanted = set(columns)
    n = 0
    for j in range(len(schema)):
        c = schema.column(j)
        if c.physical_type == "FIXED_LEN_BYTE_ARRAY" \
                and c.converted_type == "DECIMAL" \
                and c.path.split(".")[0] in wanted:
            n += 1
    return n


def read_files_as_table(
    data_path: str,
    files: Sequence[AddFile],
    metadata,
    columns: Optional[Sequence[str]] = None,
    per_file: bool = False,
    position_column: Optional[str] = None,
    distribute: bool = False,
    predicate=None,
    positions_of_interest: Optional[Sequence] = None,
    late_materialize: bool = True,
    file_ready=None,
    device_masks=None,
):
    """Decode AddFiles to one Arrow table, materializing partition columns.

    Files decode in parallel on a thread pool (Arrow's Parquet reader drops
    the GIL) — the host fan-out the reference gets from Spark executors
    (`files/TahoeFileIndex.scala:58-81`). ``per_file=True`` returns the list
    of per-file tables (same order as ``files``) instead of one concat.
    ``distribute=True`` restricts the decode to THIS host's deterministic
    slice of the file list (`parallel/distributed.host_partition`) — the
    multi-host scan shape where each process consumes its partition; on a
    single host it is the identity.

    ``predicate`` (an `expr/ir` expression) turns on the second pruning
    tier (`exec/rowgroups`): row groups whose footer stats definitely
    cannot match skip decode entirely, and of the survivors, predicate
    columns decode FIRST — remaining projected columns decode only for
    row groups with at least one possibly-matching row (late
    materialization). Rows within surviving row groups are NOT filtered;
    callers apply the residual predicate exactly as before, so the result
    is identical to a full decode. Callers must only pass ``predicate``
    when rows outside it are never needed (scans re-filter; DML may pass
    it only when it doesn't rewrite untouched rows — deletion-vector
    mode). ``positions_of_interest`` (per-file physical row positions,
    aligned with ``files``; entries may be None) additionally restricts
    decode to row groups containing those positions — the CDF DV-diff
    shape. Both are gated by ``delta.tpu.read.rowGroupSkipping``.

    Rows marked in a file's deletion vector are dropped. When
    ``position_column`` is given, each row carries its PHYSICAL position in
    the file as written (int64) — DML needs physical positions to extend a
    file's deletion vector; positions stay physical under row-group
    skipping (offset by the row counts of skipped groups).

    ``file_ready(index, add, table)`` is invoked from the decode pool as
    each file's table completes (decode-completion order, not list order) —
    the hook the MERGE fused pipeline uses to stream key lanes onto the
    device while the remaining files still decode. The callback must not
    raise; an exception from it fails the whole read.

    ``device_masks`` ({add.path → bool ndarray over the file's physical
    rows}, from `ops/column_cache.device_residual_masks`) switches masked
    files to the device residual path: row groups whose mask slice is
    all-False skip decode, surviving groups decode in one read with NO
    host predicate evaluation, and — unlike the contract above — rows
    within surviving groups ARE filtered to the mask. Only callers that
    re-apply the residual over the result may pass it (``scan_to_table``
    does); a file whose mask doesn't line up with its footer falls back to
    the host path.

    One span, ``delta.scan.read``, holds the call wherever there is a file
    to read: the read's set-up (the projection's schema, the row-group
    planner's rewrites of ``predicate``) as well as the decode pool.
    """
    from delta_tpu.obs import scan_report as scan_report_mod
    from delta_tpu.utils import telemetry

    args = (data_path, files, metadata, columns, per_file, position_column,
            distribute, predicate, positions_of_interest, late_materialize,
            file_ready, device_masks)
    if not files:
        return _read_files(None, *args)
    with telemetry.record_operation(
        "delta.scan.read", {"numFiles": len(files)}
    ) as rev:
        out = _read_files(rev, *args)
    scan_report_mod.record_phase("read", rev)
    return out


def _read_files(rev, data_path, files, metadata, columns, per_file,
                position_column, distribute, predicate, positions_of_interest,
                late_materialize, file_ready, device_masks):
    """`read_files_as_table` inside its span ``rev`` (None where no file
    is asked for)."""
    from delta_tpu.utils import telemetry

    if distribute:
        if positions_of_interest is not None:
            raise ValueError(
                "positions_of_interest cannot be combined with distribute"
            )
        from delta_tpu.parallel.distributed import host_partition

        # byte-weighted LPT: the strided count-based split hands one host
        # the hot shard's bytes on a zipf-skewed file list; sizes are on
        # every AddFile, so the balanced assignment is free and RPC-less
        files = list(files)
        files = host_partition(files, sizes=[f.size or 0 for f in files])
        if rev is not None:
            rev.data["numFiles"] = len(files)
    total_bytes = sum(f.size or 0 for f in files)
    telemetry.bump_counter("scan.files.read", len(files))
    telemetry.bump_counter("scan.bytes.read", total_bytes)
    from delta_tpu.obs import scan_report as scan_report_mod

    scan_report_mod.contribute(bytes_read=total_bytes)
    schema: StructType = metadata.schema
    part_cols = list(metadata.partition_columns)
    part_schema = metadata.partition_schema
    out_names = columns if columns is not None else [f.name for f in schema.fields]
    data_cols = [c for c in out_names if c not in part_cols]

    arrow_fields = [
        pa.field(f.name, arrow_type_for(f.data_type), f.nullable)
        for f in schema.fields
        if f.name in out_names
    ]
    empty = pa.schema(arrow_fields).empty_table()
    if not files:
        return [] if per_file else empty

    import pyarrow.parquet as pq

    rg_skipping = conf.get_bool("delta.tpu.read.rowGroupSkipping", True)
    pred_refs = (
        frozenset(r.lower() for r in ir.references(predicate))
        if predicate is not None
        else frozenset()
    )
    if predicate is not None:
        from delta_tpu.expr.synthesis import schema_types

        # arms predicate synthesis in the row-group planner (the shared
        # skipping rewrite needs declared column types to gate its rules)
        pred_types = schema_types(metadata)
    else:
        pred_types = None
    pred_rewrites = None
    pred_leaves = None
    pcols_lower = frozenset(c.lower() for c in part_cols)
    if pred_types is not None:
        from delta_tpu.exec.rowgroups import stats_leaves
        from delta_tpu.ops.pruning import conjunct_rewrites

        # scan-constant: computed ONCE here, not per file in the decode pool,
        # with the leaves whose row-group statistics the rewrite reads
        pred_rewrites = conjunct_rewrites([predicate], pcols_lower,
                                          pred_types)
        pred_leaves = stats_leaves(
            ir.and_all([r.rewritten for r in pred_rewrites]))
    pos_hints = list(positions_of_interest) if positions_of_interest else None
    # per-file (rgTotal, rgPruned, rgLateSkipped, bytesSkippedPlanned,
    # bytesLateSkipped, planFired, rgDeviceSkipped, bytesDeviceSkipped,
    # bytesDeviceSurvivor) — summed into counters/span attributes after the
    # pool drains
    rg_stats: List[tuple] = []

    def _dummy(n: int) -> pa.Table:
        # no stored columns requested (partition-only projection, or all
        # requested columns post-date this file): carry just the row
        # count — the dummy column is dropped by the final select
        return pa.table({"__dummy": pa.nulls(n)})

    def _mask_table(t1: pa.Table, add: AddFile) -> pa.Table:
        """Attach everything the predicate may reference beyond the decoded
        predicate columns: typed partition constants and nulls for columns
        this file predates — mirroring the final table the residual filter
        sees, so the late-materialization verdict can never diverge."""
        mt = t1
        for f in schema.fields:
            if f.name.lower() not in pred_refs:
                continue
            if f.name in mt.column_names or f.name in part_cols:
                continue
            at = arrow_type_for(f.data_type)
            mt = mt.append_column(pa.field(f.name, at, True), pa.nulls(mt.num_rows, at))
        if part_cols:
            typed = typed_partition_row(add, part_schema)
            for c in part_cols:
                if c.lower() not in pred_refs or c in mt.column_names:
                    continue
                f = part_schema[c]
                at = arrow_type_for(f.data_type)
                v = typed.get(c)
                arr = (
                    pa.nulls(mt.num_rows, at)
                    if v is None
                    else pa.array([v] * mt.num_rows, type=at)
                )
                mt = mt.append_column(pa.field(c, at, f.nullable), arr)
        return mt

    def _read_stage(stage, meta, idx, cols) -> None:
        """Open the ``delta.scan.decode.rowGroups`` stage with what it
        reads: the groups, their bytes as the footer states them (every
        column, uncompressed) and, where the span is recorded,
        ``flbaDecimals``: how many of the decoded columns are decimals
        stored as fixed-length byte arrays (precision above 18, or a file
        written before decimals were stored as integers)."""
        ev = stage("delta.scan.decode.rowGroups",
                   {"groups": len(idx),
                    "bytes": sum(meta.row_group(i).total_byte_size
                                 for i in idx)})
        if ev.span_id:
            ev.data["flbaDecimals"] = _byte_array_decimals(meta.schema, cols)

    # the inside of a decode's ``open`` stage, as its child spans:
    # ``.open.plan`` (`_plan_groups`), ``.open.survivors`` (the row groups'
    # offsets, and on the device route the pass over the mask's slices),
    # ``.open.file`` (`_open_file`)

    def _plan_groups(abs_path, add, pos_hint):
        """The file's footer and the row groups worth reading by its
        statistics and the position hint: ``(meta | None, keep_idx,
        skipped_bytes, plan_fired)``. Where the span is recorded it also
        carries ``statsColumns``: the leaves whose statistics the planner
        read (0 where it read none)."""
        from delta_tpu.exec import rowgroups

        with telemetry.record_operation("delta.scan.decode.open.plan") as ev:
            try:
                meta, cached = rowgroups.FooterCache.instance().lookup(abs_path)
            except Exception:
                return None, [], 0, []
            n_rg = meta.num_row_groups
            keep_idx = list(range(n_rg))
            skipped_bytes = 0
            plan_fired: list = []
            stats_columns = 0
            if predicate is not None and n_rg > 1:
                part_row = (
                    typed_partition_row(add, part_schema) if part_cols else None
                )
                plan = rowgroups.plan_row_groups(
                    meta, predicate, part_row, pcols_lower, pred_types,
                    rewrites=pred_rewrites, leaves=pred_leaves,
                )
                keep_idx, skipped_bytes = plan.keep, plan.skipped_bytes
                plan_fired = plan.fired
                stats_columns = plan.stats_columns
            if pos_hint is not None and n_rg:
                wanted = rowgroups.row_groups_for_positions(meta, pos_hint)
                for i in keep_idx:
                    if i not in wanted:
                        skipped_bytes += meta.row_group(i).total_byte_size
                keep_idx = [i for i in keep_idx if i in wanted]
            ev.data.update(rowGroups=n_rg, kept=len(keep_idx),
                           footerCached=cached)
            if ev.span_id:
                ev.data["statsColumns"] = stats_columns
            return meta, keep_idx, skipped_bytes, plan_fired

    def _open_file(abs_path, meta):
        """``(ParquetFile, the projected columns this file has)``: files
        written before a schema evolution lack the newer columns, and the
        read fills them with nulls. ``meta`` is the footer where the planner
        fetched one.
        memory_map: decoded columns reference page-cache pages instead of
        round-tripping file bytes through the Arrow memory pool — on
        single-core hosts the pool churn costs more than the decode."""
        with telemetry.record_operation("delta.scan.decode.open.file"):
            pf = pq.ParquetFile(abs_path, memory_map=True, metadata=meta)
            present = set(pf.schema_arrow.names)
            return pf, [c for c in data_cols if c in present]

    def _decode_pruned(abs_path, meta, keep_idx, add, need_positions, stage):
        """Decode only ``keep_idx`` row groups (late-materializing around
        the predicate columns); returns (table, physical_positions | None,
        late_skipped_groups, late_skipped_bytes)."""
        import numpy as np

        from delta_tpu.exec import rowgroups

        with telemetry.record_operation("delta.scan.decode.open.survivors",
                                        {"survivors": len(keep_idx)}):
            offsets = rowgroups.row_group_offsets(meta)
        late_skipped = 0
        late_bytes = 0
        if not keep_idx:
            t = _dummy(0)
            pos = np.empty(0, dtype=np.int64) if need_positions else None
            return t, pos, 0, 0
        pf, file_cols = _open_file(abs_path, meta)
        present = set(pf.schema_arrow.names)
        if not file_cols:
            t = _dummy(int(sum(meta.row_group(i).num_rows for i in keep_idx)))
        else:
            pred_cols = [c for c in file_cols if c.lower() in pred_refs]
            rest_cols = [c for c in file_cols if c not in pred_cols]
            # a predicate column STORED in the file but outside the
            # projection would mask as all-null and late-skip groups that
            # genuinely match — late materialization needs every stored
            # predicate column in the decode set
            refs_covered = not (
                pred_refs
                & {c.lower() for c in present}
                - {c.lower() for c in file_cols}
            )
            t = None
            # one stage for the whole read: under late materialization that
            # is the predicate columns, their mask, then the rest
            _read_stage(stage, meta, keep_idx, file_cols)
            if late_materialize and refs_covered \
                    and predicate is not None and pred_cols and rest_cols:
                t1 = pf.read_row_groups(keep_idx, columns=pred_cols)
                try:
                    from delta_tpu.expr.vectorized import boolean_mask

                    mask = boolean_mask(
                        predicate, _mask_table(t1, add)
                    ).to_numpy(zero_copy_only=False)
                except Exception:
                    mask = None  # unevaluable here: keep every group
                if mask is not None:
                    survivors, slices = [], []
                    start = 0
                    for i in keep_idx:
                        n_i = meta.row_group(i).num_rows
                        if mask[start:start + n_i].any():
                            survivors.append(i)
                            slices.append((start, n_i))
                        else:
                            late_skipped += 1
                            rg = meta.row_group(i)
                            by_name = {
                                rg.column(j).path_in_schema: j
                                for j in range(rg.num_columns)
                            }
                            late_bytes += sum(
                                rg.column(by_name[c]).total_uncompressed_size
                                for c in rest_cols
                                if c in by_name
                            )
                        start += n_i
                    if late_skipped:
                        t1 = (
                            pa.concat_tables([t1.slice(s, n) for s, n in slices])
                            if slices
                            else t1.slice(0, 0)
                        )
                        keep_idx = survivors
                if keep_idx and rest_cols:
                    t2 = pf.read_row_groups(keep_idx, columns=rest_cols)
                    cols = {c: t1.column(c) for c in t1.column_names}
                    cols.update({c: t2.column(c) for c in t2.column_names})
                    t = pa.table([cols[c] for c in file_cols], names=file_cols)
                elif keep_idx:
                    t = t1
                else:
                    t = pf.schema_arrow.empty_table().select(file_cols)
            if t is None:
                t = pf.read_row_groups(keep_idx, columns=file_cols)
        stage("delta.scan.decode.assemble")
        pos = None
        if need_positions:
            pos = (
                np.concatenate(
                    [np.arange(offsets[i], offsets[i + 1]) for i in keep_idx]
                ).astype(np.int64)
                if keep_idx
                else np.empty(0, dtype=np.int64)
            )
        return t, pos, late_skipped, late_bytes

    def _decode_device_masked(abs_path, meta, keep_idx, add, need_positions,
                              dev_mask, stage):
        """The device residual path's survivor fetch: drop row groups whose
        device mask slice is all-False, decode the survivors' projected
        columns in ONE read (no host predicate evaluation), and filter rows
        to the mask. The caller re-applies the residual over the result
        (``scan_to_table``), so an over-keep can never leak; an under-keep
        cannot happen because the mask is the exact Kleene-TRUE set of the
        same predicate. Returns None when the mask doesn't line up with the
        footer (→ host path), else (table, positions | None,
        (device_skipped_groups, device_skipped_bytes, survivor_bytes))."""
        import numpy as np

        from delta_tpu.exec import rowgroups

        with telemetry.record_operation(
                "delta.scan.decode.open.survivors") as ev:
            offsets = rowgroups.row_group_offsets(meta)
            if len(dev_mask) != offsets[-1]:
                return None
            survivors = []
            dev_skipped = dev_bytes = surv_bytes = 0
            for i in keep_idx:
                if dev_mask[offsets[i]:offsets[i + 1]].any():
                    survivors.append(i)
                    surv_bytes += meta.row_group(i).total_byte_size
                else:
                    dev_skipped += 1
                    dev_bytes += meta.row_group(i).total_byte_size
            ev.data["survivors"] = len(survivors)
        pf, file_cols = _open_file(abs_path, meta)
        if not survivors:
            t = (pf.schema_arrow.empty_table().select(file_cols)
                 if file_cols else _dummy(0))
            pos = np.empty(0, dtype=np.int64) if need_positions else None
            return t, pos, (dev_skipped, dev_bytes, 0)
        _read_stage(stage, meta, survivors, file_cols)
        if file_cols:
            t = pf.read_row_groups(survivors, columns=file_cols)
        else:
            t = _dummy(int(sum(meta.row_group(i).num_rows
                               for i in survivors)))
        stage("delta.scan.decode.assemble")
        keep = np.concatenate(
            [dev_mask[offsets[i]:offsets[i + 1]] for i in survivors])
        t = t.filter(pa.array(keep))
        pos = None
        if need_positions:
            phys = np.concatenate(
                [np.arange(offsets[i], offsets[i + 1]) for i in survivors])
            pos = phys[keep].astype(np.int64)
        return t, pos, (dev_skipped, dev_bytes, surv_bytes)

    def read_one(job, stage) -> pa.Table:
        """``stage`` opens the next of the decode's three spans, each ending
        where the next begins: ``open`` (footer, row-group selection, the
        ``ParquetFile``), ``rowGroups`` (the read), ``assemble`` (mask and
        deletion-vector filter, casts, partition and position columns)."""
        fidx, add, pos_hint = job
        abs_path = _abs_data_path(data_path, add.path)
        import numpy as np

        stage("delta.scan.decode.open")

        need_positions = (
            add.deletion_vector is not None or position_column is not None
        )
        t = None
        positions = None
        meta = None
        if rg_skipping and (predicate is not None or pos_hint is not None):
            meta, keep_idx, skipped_bytes, plan_fired = _plan_groups(
                abs_path, add, pos_hint)
        if meta is not None and meta.num_row_groups > 0:
            n_rg = meta.num_row_groups
            pruned = n_rg - len(keep_idx)
            late_capable = (
                late_materialize and predicate is not None
                and keep_idx and pred_refs and n_rg > 1
            )
            dev_mask = device_masks.get(add.path) if device_masks else None
            if dev_mask is not None:
                res = _decode_device_masked(
                    abs_path, meta, keep_idx, add, need_positions, dev_mask,
                    stage,
                )
                if res is not None:
                    t, positions, dstats = res
                    rg_stats.append(
                        (n_rg, pruned, 0, skipped_bytes, 0, plan_fired)
                        + dstats
                    )
            if t is None and (pruned or late_capable):
                t, positions, late_n, late_bytes = _decode_pruned(
                    abs_path, meta, keep_idx, add, need_positions, stage
                )
                rg_stats.append(
                    (n_rg, pruned, late_n, skipped_bytes, late_bytes,
                     plan_fired, 0, 0, 0)
                )
            elif t is None:
                rg_stats.append((n_rg, 0, 0, 0, 0, (), 0, 0, 0))
        if t is None:
            # full decode — the seed path; reuse the already-parsed footer
            # when the planner fetched one
            pf, file_cols = _open_file(abs_path, meta)
            _read_stage(stage, pf.metadata,
                        range(pf.metadata.num_row_groups), file_cols)
            if file_cols:
                t = pf.read(columns=file_cols)
            else:
                t = _dummy(pf.metadata.num_rows)

        stage("delta.scan.decode.assemble")
        if add.deletion_vector is not None:
            from delta_tpu.protocol.deletion_vectors import (
                DeletionVectorDescriptor,
                read_deletion_vector,
            )

            dv_rows = read_deletion_vector(
                DeletionVectorDescriptor.from_dict(add.deletion_vector), data_path
            )
            if positions is None:
                keep = np.ones(t.num_rows, dtype=bool)
                keep[dv_rows] = False
                positions = np.flatnonzero(keep)
            else:
                # pruned decode: positions are physical but sparse — map
                # the DV through membership, not direct indexing
                keep = ~np.isin(positions, dv_rows)
                positions = positions[keep]
            t = t.filter(pa.array(keep))
        elif position_column is not None and positions is None:
            positions = np.arange(t.num_rows, dtype=np.int64)
        for f in schema.fields:
            if f.name in data_cols and f.name not in t.column_names:
                at = arrow_type_for(f.data_type)
                t = t.append_column(pa.field(f.name, at, True), pa.nulls(t.num_rows, at))
        if part_cols:
            typed = typed_partition_row(add, part_schema)
            for c in part_cols:
                if c not in out_names:
                    continue
                f = part_schema[c]
                at = arrow_type_for(f.data_type)
                v = typed.get(c)
                arr = (
                    pa.nulls(t.num_rows, at)
                    if v is None
                    else pa.array([v] * t.num_rows, type=at)
                )
                t = t.append_column(pa.field(c, at, f.nullable), arr)
        # column order = requested order
        t = t.select([c for c in out_names if c in t.column_names])
        # Cast columns up to the declared table type: files written before an
        # ALTER ... CHANGE COLUMN widen carry the old narrower type.
        declared = {f.name: arrow_type_for(f.data_type) for f in schema.fields}
        for i, name in enumerate(t.column_names):
            want = declared.get(name)
            col = t.column(i)
            if want is not None and col.type != want:
                t = t.set_column(i, pa.field(name, want, True), col.cast(want))
        if position_column is not None:
            t = t.append_column(
                position_column, pa.array(positions, pa.int64())
            )
        if file_ready is not None:
            file_ready(fidx, add, t)
        return t

    if pos_hints is not None and len(pos_hints) != len(files):
        raise ValueError(
            f"positions_of_interest has {len(pos_hints)} entries "
            f"for {len(files)} files"
        )
    jobs = [(i, add, hint) for i, (add, hint) in enumerate(
        zip(files, pos_hints if pos_hints else [None] * len(files)))]
    def decode_one(job):
        # one span per file decode: with the span context propagated into
        # the pool workers these parent under `delta.scan.read` (and the
        # enclosing command span) on each worker's own trace lane — the
        # decode half of the decode/compute overlap, visible in
        # export_chrome_trace instead of orphaned
        with telemetry.record_operation(
            "delta.scan.decode", {"file": job[1].path}
        ), telemetry.span_stages() as stage:
            return read_one(job, stage)

    if len(jobs) == 1:
        pieces = [decode_one(jobs[0])]
    else:
        from concurrent.futures import ThreadPoolExecutor

        workers = min(len(jobs), os.cpu_count() or 4)
        with ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="delta-scan-decode"
        ) as pool:
            pieces = list(pool.map(telemetry.propagated(decode_one), jobs))
    if rg_stats:
        rg_total = sum(s[0] for s in rg_stats)
        rg_pruned = sum(s[1] for s in rg_stats)
        rg_late = sum(s[2] for s in rg_stats)
        planned_bytes = sum(s[3] for s in rg_stats)
        rg_device = sum(s[6] for s in rg_stats)
        device_bytes = sum(s[7] for s in rg_stats)
        device_survivor = sum(s[8] for s in rg_stats)
        bytes_skipped = (planned_bytes + sum(s[4] for s in rg_stats)
                         + device_bytes)
        telemetry.bump_counter("scan.rowgroups.total", rg_total)
        if rg_pruned:
            telemetry.bump_counter("scan.rowgroups.pruned", rg_pruned)
        if rg_late:
            telemetry.bump_counter("scan.rowgroups.lateSkipped", rg_late)
        if rg_device:
            telemetry.bump_counter("scan.rowgroups.deviceSkipped",
                                   rg_device)
        if bytes_skipped:
            telemetry.bump_counter("scan.bytes.skipped", bytes_skipped)
        if device_bytes:
            telemetry.bump_counter("scan.bytes.deviceSkipped",
                                   device_bytes)
        if device_survivor:
            # survivor-group bytes the device path sent to host decode —
            # the host-decoded remainder of masked files, counted apart
            # from plain host reads so the counters split the two
            telemetry.bump_counter("scan.bytes.deviceSurvivor",
                                   device_survivor)
        rev.data.update(
            rowGroupsTotal=rg_total, rowGroupsPruned=rg_pruned,
            rowGroupsLateSkipped=rg_late, bytesSkipped=bytes_skipped,
            rowGroupsDeviceSkipped=rg_device,
        )
        # the in-flight per-query ScanReport (obs/scan_report) gets the
        # SAME sums that fed the counters — report/counter parity by
        # construction
        from delta_tpu.obs import scan_report as scan_report_mod

        scan_report_mod.contribute(
            row_groups_total=rg_total, row_groups_pruned=rg_pruned,
            row_groups_late_skipped=rg_late, bytes_skipped=bytes_skipped,
            bytes_skipped_planned=planned_bytes,
            row_groups_device_skipped=rg_device,
            bytes_device_skipped=device_bytes,
            bytes_device_survivor=device_survivor,
        )
        # fired-rewrite attribution: each synthesized conjunct that
        # excluded a row group records ONCE per scan (the per-file
        # planner reports per file; the report layer dedupes against
        # the file tier too)
        seen_fired = set()
        for s in rg_stats:
            for fe in s[5]:
                key = (fe["family"], fe["conjunct"])
                if key in seen_fired:
                    continue
                seen_fired.add(key)
                scan_report_mod.record_rewrite_fired(
                    fe["family"], fe["conjunct"], fe["rewrite"])
    out = pieces if per_file else pa.concat_tables(
        pieces, promote_options="permissive")
    return out


def scan_files(snapshot, filters: Sequence[Union[str, ir.Expression]] = ()) -> pruning.DeltaScan:
    exprs = [parse_predicate(f) if isinstance(f, str) else f for f in filters]
    return pruning.files_for_scan(snapshot, exprs)


from dataclasses import dataclass
from typing import List


@dataclass
class QueryPlan:
    """One query's pruned file list from :func:`plan_scans`. ``overflow``
    marks a query whose match set exceeded K (``paths`` holds the first K;
    ``count`` stays exact); ``via`` records which engine produced it
    ('device', 'host-resident', or 'scan' for the per-query fallback)."""

    paths: List[str]
    count: int
    overflow: bool = False
    via: str = "scan"


def plan_scans(
    snapshot,
    queries: Sequence[Sequence[Union[str, ir.Expression]]],
    k: int = 256,
) -> List[QueryPlan]:
    """Plan a *batch* of queries against one snapshot — the serving shape of
    a query router / BI dashboard (N concurrent point lookups) or MERGE's
    per-partition file probing.

    With the table's scan lanes HBM-resident (`ops/state_cache`), the whole
    batch is ONE device dispatch and one (N, K) download; the link cost model
    (`parallel/link`) decides device vs the host float64 mirrors per batch.
    Queries whose predicates don't lower to per-column ranges (ORs, null
    tests, strings) fall back to :func:`scan_files` individually."""
    import numpy as np

    from delta_tpu.ops.state_cache import DeviceStateCache, extract_range_union
    from delta_tpu.utils.telemetry import bump_counter

    parsed = [
        [parse_predicate(f) if isinstance(f, str) else f for f in q]
        for q in queries
    ]
    out: List[Optional[QueryPlan]] = [None] * len(queries)
    entry = DeviceStateCache.instance().get(snapshot)
    range_ix, term_lists = [], []
    if entry is not None:
        from delta_tpu.expr.synthesis import schema_types

        pcols = frozenset(c.lower() for c in snapshot.metadata.partition_columns)
        types = schema_types(snapshot.metadata)
        for i, exprs in enumerate(parsed):
            if not exprs:
                continue
            rewritten = pruning.skipping_predicate(ir.and_all(list(exprs)),
                                                   pcols, types)
            terms = extract_range_union(rewritten, entry.columns,
                                        entry.part_info,
                                        str_lanes=entry.str_lanes)
            if terms:
                range_ix.append(i)
                term_lists.append(terms)
            else:
                bump_counter("stateCache.plan.fallback.lowering")
    else:
        bump_counter("stateCache.plan.fallback.noentry", len(queries))
    if term_lists:
        # OR queries lower to several boxes; their row sets union after the
        # plan, so THEIR boxes ask for complete row sets — but only theirs:
        # per-range k keeps the single-term queries sharing the dispatch on
        # small plans instead of dragging the whole batch to num_rows
        flat, flat_ks = [], []
        full_k = max(entry.num_rows, 1)
        for terms in term_lists:
            flat.extend(terms)
            flat_ks.extend([k if len(terms) == 1 else full_k] * len(terms))
        plans = entry.plan_ranges(
            flat, k=flat_ks, expected_version=snapshot.version
        )
        if plans is not None:  # None: entry advanced past our snapshot
            bump_counter("stateCache.plan.resident", len(term_lists))
            pos = 0
            for i, terms in zip(range_ix, term_lists):
                chunk = plans[pos:pos + len(terms)]
                pos += len(terms)
                if len(chunk) == 1:
                    rows, count = chunk[0].rows, chunk[0].count
                else:
                    rows = np.unique(np.concatenate([p.rows for p in chunk]))
                    count = len(rows)
                over = count > k or chunk[0].overflow
                out[i] = QueryPlan(
                    paths=[entry.paths[r] for r in rows[:k]],
                    count=count, overflow=over, via=chunk[0].via,
                )
        else:
            bump_counter("stateCache.plan.fallback.version", len(term_lists))
    for i, exprs in enumerate(parsed):
        if out[i] is None:
            scan = pruning.files_for_scan(snapshot, exprs)
            out[i] = QueryPlan(
                paths=[f.path for f in scan.files], count=len(scan.files)
            )
    return out  # type: ignore[return-value]


def scan_to_table(
    snapshot,
    filters: Sequence[Union[str, ir.Expression]] = (),
    columns: Optional[Sequence[str]] = None,
    distribute: bool = False,
) -> pa.Table:
    """Full read path: prune → decode (projection ∪ filter columns) →
    residual filter → project. ``distribute=True``: this host decodes only
    its partition of the pruned file list (multi-host scan).

    Each call records a per-query :class:`delta_tpu.obs.scan_report.ScanReport`
    (files/row-groups considered vs pruned, bytes, phase durations),
    retrievable via ``obs.last_scan_report()`` and attached to the
    ``delta.scan`` span — skipped entirely under a telemetry blackout."""
    from delta_tpu.obs import scan_report as scan_report_mod
    from delta_tpu.utils import telemetry

    track = conf.get_bool("delta.tpu.telemetry.enabled", True)
    token = (scan_report_mod.start_report(snapshot.delta_log.data_path,
                                          snapshot.version)
             if track else None)
    scan_ok = False
    try:
        # the phases are child spans that tile this one: `.planning`
        # (ops/pruning), `.deviceMask`, `.read` (read_files_as_table),
        # `.filter`, `.report`; the first four write the report's phaseMs
        with telemetry.record_operation(
            "delta.scan", path=snapshot.delta_log.data_path
        ) as sev:
            exprs = [parse_predicate(f) if isinstance(f, str) else f for f in filters]
            scan = pruning.files_for_scan(snapshot, exprs)
            data_path = snapshot.delta_log.data_path
            residual = scan.partition_filters + scan.data_filters
            read_cols = columns
            if columns is not None and residual:
                # read filter-referenced columns too; project back after filtering
                needed = set(columns)
                for e in residual:
                    needed.update(ir.references(e))
                read_cols = [c for c in [f.name for f in snapshot.metadata.schema.fields]
                             if c in needed]
            # third tier, when the router prices it: the device residual
            # path (ops/column_cache) computes per-file survivor masks from
            # HBM-resident lanes in one jitted pass; None = host path
            device_masks = None
            if residual and scan.files:
                from delta_tpu.ops import column_cache

                if column_cache.column_cache_enabled():
                    with telemetry.record_operation(
                            "delta.scan.deviceMask") as mev:
                        device_masks = column_cache.device_residual_masks(
                            snapshot, scan.files, ir.and_all(residual))
                    scan_report_mod.record_phase("mask", mev)
            # the residual predicate rides into the decode: footer row-group
            # stats prune inside each file (second tier), and the residual
            # filter below re-applies the exact semantics over the survivors
            table = read_files_as_table(data_path, scan.files, snapshot.metadata,
                                        read_cols, distribute=distribute,
                                        predicate=(ir.and_all(residual)
                                                   if residual else None),
                                        device_masks=device_masks)
            with telemetry.record_operation("delta.scan.filter") as fev:
                if residual and table.num_rows:
                    table = filter_table(table, ir.and_all(residual))
                if columns is not None and read_cols != list(columns):
                    table = table.select([c for c in columns if c in table.column_names])
            scan_report_mod.record_phase("filter", fev)
            sev.data.update(
                filesScanned=len(scan.files), rowsOut=table.num_rows,
                bytesScanned=scan.scanned.bytes_compressed,
            )
            rep = scan_report_mod.current_report() if token is not None else None
            if rep is not None:
                # what the operations plane costs a scan: the report and
                # the journal record, as a span of their own
                with telemetry.record_operation("delta.scan.report"):
                    rep.predicate = (ir.and_all(residual).sql()
                                     if residual else None)
                    rep.columns = list(columns) if columns is not None else None
                    rep.files_total = scan.total.files or 0
                    rep.files_after_partition = scan.partition.files or 0
                    rep.files_scanned = len(scan.files)
                    rep.rows_out = table.num_rows
                    rep_dict = rep.to_dict()
                    sev.data["scanReport"] = rep_dict
                    # workload journal: the same report dict plus the
                    # normalized predicate fingerprint (computed on the
                    # journal writer thread) persists to
                    # <table>/_delta_log/_journal so the layout advisor can
                    # aggregate across processes (buffered; inert when the
                    # journal or telemetry is disabled)
                    from delta_tpu.obs import journal as journal_mod

                    from delta_tpu.expr.synthesis import schema_types

                    # resolve the synthesis conf NOW: the fingerprint is
                    # computed deferred on the journal writer thread, and
                    # the process conf may sit in a different window by
                    # flush time (types=None = synthesis was off)
                    fp_types = (
                        schema_types(snapshot.metadata)
                        if conf.get_bool(
                            "delta.tpu.read.predicateSynthesis", True)
                        else None)
                    journal_mod.record_scan(
                        snapshot.delta_log.log_path, report_dict=rep_dict,
                        predicate=(ir.and_all(residual) if residual else None),
                        partition_cols=snapshot.metadata.partition_columns,
                        types=fp_types,
                    )
            scan_ok = True
            return table
    finally:
        if token is not None:
            scan_report_mod.finish_report(token, completed=scan_ok)
