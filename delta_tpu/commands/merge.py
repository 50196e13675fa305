"""MERGE INTO — columnar three-phase upsert.

The reference (`commands/MergeIntoCommand.scala:201-771`) runs MERGE as:
(1) findTouchedFiles — inner join source×target to locate files with matches
    plus multi-match detection (`:310-389`);
(2) writeAllChanges — re-read only touched files, outer join, then a
    row-at-a-time clause interpreter (`JoinedRowProcessor :681-753`);
(3) commit removes ++ adds.

This engine keeps the phase structure but replaces the row interpreter with
columnar blocks: matched pairs / unmatched target rows / unmatched source
rows are materialized separately, and every clause becomes a vectorized mask
+ projection over its block. The join itself has two executors:

- **device** — 1-2 integer equi-keys, no residual conjuncts (the TPC-DS
  upsert shape), three variants by residency (PR 6 fused pipeline):
  *resident* (the table's key lane is HBM-resident in `ops/key_cache` —
  ships only source keys), *device-cold* (per-file key decode streams onto
  a pre-sized slab while the remaining files decode, then registers the
  slab so the next merge cache-hits), and *device-upload* (multichip mesh:
  target sharded, source all-gathered, per-shard sort-merge —
  `ops/join_kernel.py`). The probe kernel computes match masks AND the
  matched pairing on device; the host maps O(matched) pairs onto the
  decode. Toggle: ``delta.tpu.merge.devicePath.enabled``; routing is
  link-priced per residency case (`parallel/link.py`), and every decision
  emits a ``delta.merge.router`` event + ``merge.device.*`` counters.
- **host fallback** (Arrow hash join — the C++ kernel) for string /
  multi-key / non-equi conditions.

Multi-clause ordering, clause conditions, multi-match errors, the insert-only
fast path (`:397-450`) and `MergeStats` (`:79-174`) follow the reference.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple, Union

import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.commands import operations as ops
from delta_tpu.commands import dml_common as dv_common
from delta_tpu.commands.dml_common import POSITION_COL, Timer, candidate_files
from delta_tpu.exec import cdf as cdf_exec
from delta_tpu.exec import write as write_exec
from delta_tpu.exec.scan import read_files_as_table
from delta_tpu.expr import ir
from delta_tpu.expr.parser import parse_expression, parse_predicate
from delta_tpu.expr.vectorized import boolean_mask, evaluate
from delta_tpu.protocol.actions import Action, AddFile
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf
from delta_tpu.utils.errors import DeltaAnalysisError, DeltaUnsupportedOperationError
from delta_tpu.utils import errors as errors_mod

__all__ = ["MergeIntoCommand", "MergeClause"]

def _coerce_join_keys(t_vals, s_vals):
    """Lossless join-key coercion: never run a narrowing or precision-losing
    cast (wrapped/rounded keys fabricate matches).

    int vs int → wider int; float vs float → float64; int vs float → keep
    int64 and map the float side through an integrality check (non-integral
    or out-of-range floats become NULL, and NULL keys never join)."""
    a, b = t_vals.type, s_vals.type
    if a == b:
        return t_vals, s_vals
    if pa.types.is_integer(a) and pa.types.is_integer(b):
        common = a if a.bit_width >= b.bit_width else b
        return pc.cast(t_vals, common), pc.cast(s_vals, common)
    if pa.types.is_floating(a) and pa.types.is_floating(b):
        return pc.cast(t_vals, pa.float64()), pc.cast(s_vals, pa.float64())

    def float_to_int64(vals):
        f = pc.cast(vals, pa.float64())
        # any integral float64 in [-2^63, 2^63) casts to int64 exactly (it
        # IS a representable integer); non-integral / out-of-range can't
        # equal any int64 key, so they become NULL (null keys never join)
        integral = pc.and_(
            pc.equal(pc.floor(f), f),
            pc.and_(pc.greater_equal(f, pa.scalar(-(2.0**63))),
                    pc.less(f, pa.scalar(2.0**63))),
        )
        return pc.cast(
            pc.if_else(pc.fill_null(integral, False), f, pa.scalar(None, pa.float64())),
            pa.int64(),
        )

    if pa.types.is_integer(a) and pa.types.is_floating(b):
        return pc.cast(t_vals, pa.int64()), float_to_int64(s_vals)
    if pa.types.is_floating(a) and pa.types.is_integer(b):
        return float_to_int64(t_vals), pc.cast(s_vals, pa.int64())
    if pa.types.is_string(a) or pa.types.is_string(b):
        return pc.cast(t_vals, pa.string()), pc.cast(s_vals, pa.string())
    return t_vals, s_vals


_SRC = "__s__"  # prefix for source columns in the combined pair table
_TID = "__t_row__"
_SID = "__s_row__"
_FID = "__t_file__"
# the two stages that tile `delta.dml.merge.join` on a device route
_JOIN_WAIT = "delta.dml.merge.join.wait"
_JOIN_PAIRS = "delta.dml.merge.join.pairs"


class _StaleResidentSlab(RuntimeError):
    """Internal control flow: on the pairs-only route the resident slab
    named a row the snapshot does not hold live (a pair outside the
    candidate files, or a matched row its file's deletion vector already
    covers). The entry has been invalidated; `MergeIntoCommand.run` runs the
    body once more, which then rebuilds the slab from the files."""


def _rows_from_stats(candidates) -> Optional[int]:
    """Total numRecords over the candidate files, None when any file lacks
    stats (routing then falls back to the post-decode estimate)."""
    total = 0
    for add in candidates:
        n = add.num_logical_records
        if n is None:
            return None
        total += int(n)
    return total


@dataclass
class MergeClause:
    """One WHEN clause (`catalyst/plans/logical/deltaMerge.scala:161-221`)."""

    kind: str  # "update" | "delete" | "insert"
    condition: Optional[ir.Expression] = None
    # None = updateAll/insertAll (star); else target column -> expression
    assignments: Optional[Dict[str, ir.Expression]] = None

    @property
    def is_star(self) -> bool:
        return self.assignments is None and self.kind in ("update", "insert")


def _parse_opt(e: Optional[Union[str, ir.Expression]], pred=True):
    if e is None or isinstance(e, ir.Expression):
        return e
    return parse_predicate(e) if pred else parse_expression(e)


class MergeIntoCommand:
    def __init__(
        self,
        delta_log,
        source: Any,
        condition: Union[str, ir.Expression],
        matched_clauses: Sequence[MergeClause] = (),
        not_matched_clauses: Sequence[MergeClause] = (),
        source_alias: Optional[str] = None,
        target_alias: Optional[str] = None,
    ):
        from delta_tpu.commands.write import coerce_to_table

        self.delta_log = delta_log
        self.source = coerce_to_table(source)
        self.condition = _parse_opt(condition)

        def _norm(c: MergeClause) -> MergeClause:
            return MergeClause(
                kind=c.kind,
                condition=_parse_opt(c.condition),
                assignments=None if c.assignments is None else {
                    col: (parse_expression(e) if isinstance(e, str) else e)
                    for col, e in c.assignments.items()
                },
            )

        self.matched_clauses = [_norm(c) for c in matched_clauses]
        self.not_matched_clauses = [_norm(c) for c in not_matched_clauses]
        self.source_alias = source_alias
        self.target_alias = target_alias
        self.metrics: Dict[str, int] = {}
        # wall-clock per phase (decode/key/join/apply/write ms), filled by
        # the phase spans (`_phase`); the router audit carries a copy
        self.phase_ms: Dict[str, float] = {}
        # the vectors' phase ends on a worker thread (`_write_vectors`)
        self._phase_lock = threading.Lock()
        # set by _join when the device kernel ran: JoinResult with exact
        # per-target match counts and per-source matched flags
        self._device_join = None
        self._validate_clauses()

    def _validate_clauses(self) -> None:
        for c in self.matched_clauses:
            if c.kind not in ("update", "delete"):
                raise errors_mod.invalid_merge_clause(c.kind, matched=True)
        for c in self.not_matched_clauses:
            if c.kind != "insert":
                raise errors_mod.invalid_merge_clause(c.kind, matched=False)
        for c in self.matched_clauses:
            if c.kind == "delete" and c.assignments:
                raise DeltaAnalysisError(
                    "DELETE clauses cannot carry SET assignments"
                )
        # only the last clause of each group may lack a condition
        for group in (self.matched_clauses, self.not_matched_clauses):
            for c in group[:-1]:
                if c.condition is None:
                    raise DeltaAnalysisError(
                        "When there are more than one MATCHED/NOT MATCHED clauses, "
                        "only the last can omit its condition"
                    )
        # duplicate assignment targets within one clause (case-insensitive)
        for group in (self.matched_clauses, self.not_matched_clauses):
            for c in group:
                if not c.assignments:
                    continue
                seen = set()
                for col in c.assignments:
                    low = col.split(".")[-1].lower()
                    if low in seen:
                        raise errors_mod.merge_conflicting_set_columns(col)
                    seen.add(low)

    def _analyze_clauses(self, target_cols, source_cols) -> None:
        """Post-schema-resolution clause validation: every clause condition
        and assignment must resolve, insert conditions see only the source,
        and assignment targets must be real target columns."""
        t_low = {c.lower() for c in target_cols}
        for clause in self.matched_clauses:
            if clause.condition is not None:
                self._resolve(clause.condition, target_cols, source_cols)
            if clause.assignments:
                for col, e in clause.assignments.items():
                    name = col.split(".")[-1]
                    if name.lower() not in t_low:
                        raise errors_mod.merge_unresolvable_column(
                            col, target_cols, [])
                    self._resolve(e, target_cols, source_cols)
        for clause in self.not_matched_clauses:
            if clause.condition is not None:
                # NOT MATCHED: there is no target row to reference
                self._resolve(clause.condition, [], source_cols)
            if clause.assignments:
                for col, e in clause.assignments.items():
                    name = col.split(".")[-1]
                    if name.lower() not in t_low:
                        raise errors_mod.merge_unresolvable_column(
                            col, target_cols, [])
                    self._resolve(e, [], source_cols)

    def _migrate_schema(self, txn):
        """MERGE schema evolution (`deltaMerge.scala:224-424`,
        `PreprocessTableMerge.scala:65-71`): when
        ``delta.tpu.schema.autoMerge.enabled`` is on and the merge has a
        star clause (updateAll/insertAll), the target schema widens to
        ``mergeSchemas(target, source)`` — new source columns append, and
        existing columns keep the target's name case/position with types
        implicitly widened. Returns the (possibly evolved) txn metadata."""
        from dataclasses import replace

        from delta_tpu.schema import schema_utils
        from delta_tpu.schema.arrow_interop import schema_from_arrow

        metadata = txn.metadata
        auto = bool(conf.get("delta.tpu.schema.autoMerge.enabled", False))
        has_star = any(
            c.is_star for c in list(self.matched_clauses) + list(self.not_matched_clauses)
        )
        if not (auto and has_star):
            return metadata
        from delta_tpu.schema import generated as generated_mod

        src_schema = schema_from_arrow(self.source.schema)
        merged = schema_utils.merge_schemas(
            metadata.schema, src_schema, allow_implicit_conversions=True,
            fixed_type_columns=generated_mod.fixed_type_columns(metadata.schema),
        )
        if merged.to_json() != metadata.schema.to_json():
            txn.update_metadata(replace(metadata, schema_string=merged.to_json()))
            metadata = txn.metadata
        return metadata

    # -- name resolution --------------------------------------------------

    def _resolve(self, e: ir.Expression, target_cols: Sequence[str],
                 source_cols: Sequence[str]) -> ir.Expression:
        """Rewrite alias-qualified/unqualified refs onto the combined pair
        table: target columns keep their names, source columns get _SRC."""
        t_low = {c.lower(): c for c in target_cols}
        s_low = {c.lower(): c for c in source_cols}
        t_alias = (self.target_alias or "").lower()
        s_alias = (self.source_alias or "").lower()

        def rewrite(node: ir.Expression) -> Optional[ir.Expression]:
            if not isinstance(node, ir.Column):
                return None
            name = node.name
            low = name.lower()
            if "." in low and low not in t_low and low not in s_low:
                qual, _, col = low.partition(".")
                if qual == s_alias and col in s_low:
                    return ir.Column(_SRC + s_low[col])
                if qual == t_alias and col in t_low:
                    return ir.Column(t_low[col])
                # an unknown qualifier must NOT fall back to bare resolution:
                # 't.id = s.id' without aliases would resolve both sides to
                # the target and turn the condition into a tautology
                raise errors_mod.merge_unresolvable_qualifier(
                    name, qual, self.target_alias, self.source_alias)
            if low in t_low:
                return ir.Column(t_low[low])
            if low in s_low:
                return ir.Column(_SRC + s_low[low])
            raise errors_mod.merge_unresolvable_column(name, target_cols, source_cols)

        return e.transform(rewrite)

    def _split_equi_keys(
        self, cond: ir.Expression
    ) -> Tuple[List[Tuple[ir.Expression, ir.Expression]], List[ir.Expression]]:
        """Split the (resolved) join condition into target=source equi pairs
        + residual conjuncts."""
        pairs: List[Tuple[ir.Expression, ir.Expression]] = []
        residual: List[ir.Expression] = []
        for c in ir.split_conjuncts(cond):
            if isinstance(c, ir.Eq):
                sides = [c.left, c.right]
                refs = [set(ir.references(s)) for s in sides]
                t_side = s_side = None
                for side, r in zip(sides, refs):
                    if r and all(x.startswith(_SRC) for x in r):
                        s_side = side
                    elif r and not any(x.startswith(_SRC) for x in r):
                        t_side = side
                if t_side is not None and s_side is not None:
                    pairs.append((t_side, s_side))
                    continue
            residual.append(c)
        return pairs, residual

    # -- main -------------------------------------------------------------

    def run(self) -> int:
        # the statement's shape on its root span, so that a trace tells an
        # insert-only MERGE from a keyed delete from an upsert
        kinds = [c.kind for c in self.matched_clauses + self.not_matched_clauses]
        if not self.matched_clauses:
            telemetry.bump_counter("merge.clause.insertOnly")
        if "delete" in kinds:
            telemetry.bump_counter("merge.clause.delete")
        with telemetry.record_operation(
                "delta.dml.merge",
                {"clauses": ",".join(kinds), "sourceRows": self.source.num_rows},
                path=self.delta_log.data_path) as ev:
            try:
                version = self.delta_log.with_new_transaction(self._body)
            except _StaleResidentSlab:
                # nothing was committed and the entry is gone: this time the
                # join decodes the files and the slab is built anew
                version = self.delta_log.with_new_transaction(self._body)
            ev.data.update(
                inserted=self.metrics.get("numTargetRowsInserted", 0),
                updated=self.metrics.get("numTargetRowsUpdated", 0),
                deleted=self.metrics.get("numTargetRowsDeleted", 0))
            return version

    @property
    def _pairs_only(self) -> bool:
        """The resident probe's pairs were the join: no target row was
        decoded (`_pairs_from_probe`)."""
        return self._router.get("route") == "pairs-only"

    @contextlib.contextmanager
    def _phase(self, op_type: str, key: str,
               data: Optional[Dict[str, Any]] = None) -> Iterator[Any]:
        """One phase of the MERGE: a child span of ``delta.dml.merge`` whose
        own duration is ``phase_ms[key]``, so a phase has one clock reading,
        a start and a parent. The phases tile `_body` and `_join`: they do
        not overlap on the calling thread, and only a few lines lie between
        them; the vectors' phase alone may run on a worker thread, beside
        the write's (`_write_vectors`). A phase that opens twice in one MERGE
        (the pairs-only route that declines and decodes late) adds up. Under
        a telemetry blackout the span is the no-op event and the phase is
        timed here."""
        t0 = time.perf_counter_ns()
        with telemetry.record_operation(op_type, data) as ev:
            yield ev
        ms = (ev.duration_us / 1000.0 if ev.duration_us is not None
              else (time.perf_counter_ns() - t0) / 1e6)
        with self._phase_lock:
            self.phase_ms[key] = self.phase_ms.get(key, 0.0) + ms

    def _body(self, txn) -> int:
        # self-calibrating cost model: install any persisted constant
        # overrides BEFORE routing, so a fresh process routes with what the
        # last one learned (no-op unless router.calibration.enabled)
        import numpy as np

        from delta_tpu.obs import calibration

        calibration.apply_state(self.delta_log.log_path)
        # reset per-execution state: a re-run that takes the host or empty
        # path must not consume a previous run's device-join flags
        self._device_join = None
        self._resident_candidate = None
        # (target rows, source rows) the join actually saw — the router
        # audit's workload sizes (obs/router_audit); slab rows when a
        # device probe ran (the probe's real n is the slab, not the
        # possibly-pruned decode)
        self._audit_units = None
        self._audit_eligible = False
        self._audit_slab_rows = None
        # 'resident' (HBM cache hit) | 'device-cold' (fused slab build) |
        # 'device-upload' (mesh all-gather kernel) | 'host'
        self._join_path = "host"
        self._router: Dict[str, Any] = {}
        self._cdf_blocks = []
        self._use_cdf = cdf_exec.cdf_enabled(txn.metadata)
        with self._phase_lock:
            self.phase_ms.clear()
        timer = Timer()
        with self._phase("delta.dml.merge.analyze", "analyze_ms"):
            metadata = self._migrate_schema(txn)
            target_cols = [f.name for f in metadata.schema.fields]
            source_cols = list(self.source.column_names)
            # static star-coverage analysis (the reference resolves stars at
            # analysis time, `deltaMerge.scala:322-328` — the error must not
            # depend on whether any row fires the clause)
            for clause in self.matched_clauses:
                if clause.is_star:
                    self._check_star_coverage(target_cols, source_cols, "UPDATE", metadata)
                    break
            for clause in self.not_matched_clauses:
                if clause.is_star:
                    self._check_star_coverage(target_cols, source_cols, "INSERT", metadata)
                    break
            # read-side char padding on the merge condition and clause
            # conditions (literals vs char(n) target columns). Only refs that
            # resolve to the TARGET pad: a source column sharing a name with a
            # target char column (s.status = 'x') must keep its literal as-is.
            from delta_tpu.schema.char_varchar import pad_char_literals

            tq = frozenset({self.target_alias.lower()} if self.target_alias
                           else ())
            self.condition = pad_char_literals(self.condition, metadata, tq)
            self.matched_clauses = [
                MergeClause(c.kind, pad_char_literals(c.condition, metadata, tq)
                            if c.condition is not None else None, c.assignments)
                for c in self.matched_clauses
            ]
            # static clause analysis (the reference rejects these shapes at
            # analysis time regardless of which rows fire,
            # `deltaMerge.scala:161-221` resolution errors)
            self._analyze_clauses(target_cols, source_cols)
            cond = self._resolve(self.condition, target_cols, source_cols)
            equi, residual = self._split_equi_keys(cond)

            # source with prefixed names + row ids
            src = self.source.rename_columns([_SRC + c for c in source_cols])
            src = src.append_column(_SID, pa.array(range(src.num_rows), pa.int64()))

            # phase 1: candidates by target-only conjuncts, then the join
            target_only = [
                c for c in ir.split_conjuncts(cond)
                if not any(r.startswith(_SRC) for r in ir.references(c))
            ]
            candidates = candidate_files(txn, ir.and_all(target_only) if target_only else None)
        insert_only = not self.matched_clauses
        # the join narrows the candidates itself (the distributed
        # findTouchedFiles probe), unless the resident pairs-only route
        # serves it: file ids in the pairs index the list it returns
        matched_pairs, tgt_tables, candidates = self._join(
            txn, candidates, src, equi, residual, metadata,
            prune_pred=ir.and_all(target_only) if target_only else None,
        )
        self._emit_router()
        scan_ms = timer.lap_ms()

        # the three stages tile the span: which of them an apply's time is
        with self._phase("delta.dml.merge.apply", "apply_ms"), \
                telemetry.span_stages() as stage:
            if not insert_only:
                # insert-only merges can't modify target rows, so duplicate
                # matches are harmless (reference fast path, `:397-450`)
                stage("delta.dml.merge.apply.multiMatch",
                      {"pairs": matched_pairs.num_rows})
                self._check_multi_match(matched_pairs)

            removes: List[Action] = []
            dv_adds: List[Action] = []
            out_blocks: List[pa.Table] = []
            n_copied = n_updated = n_deleted = 0
            use_dv = not insert_only and dv_common.dv_enabled(metadata)

            if not insert_only:
                # matched block → per-clause masks
                sev = stage("delta.dml.merge.apply.matched",
                            {"pairs": matched_pairs.num_rows})
                upd, n_updated, n_deleted, n_pair_copied, claimed_tbl, fired_fids = (
                    self._apply_matched(
                        matched_pairs, target_cols, metadata, dv_mode=use_dv
                    )
                )
                n_copied += n_pair_copied
                if upd is not None:
                    out_blocks.append(upd)
                if not use_dv:
                    for fid in sorted(fired_fids):
                        removes.append(candidates[fid].remove())
                    # unmatched target rows inside touched files → copy. _TID is
                    # the global row index over the candidate concat, so one
                    # boolean scatter replaces a per-file hash-set probe
                    total_rows = sum(t.num_rows for t in tgt_tables.values())
                    claimed = np.zeros(total_rows, bool)
                    claimed[matched_pairs.column(_TID).to_numpy(zero_copy_only=False)] = True
                    row_start = 0
                    starts = {}
                    for fid in sorted(tgt_tables):
                        starts[fid] = row_start
                        row_start += tgt_tables[fid].num_rows
                    for fid in sorted(fired_fids):
                        t = tgt_tables[fid]
                        keep = ~claimed[starts[fid]: starts[fid] + t.num_rows]
                        if not keep.all():
                            copied = t.filter(pa.array(keep)).select(target_cols)
                        else:
                            copied = t.select(target_cols)
                        n_copied += copied.num_rows
                        if copied.num_rows:
                            out_blocks.append(copied)
                sev.data.update(updated=n_updated, deleted=n_deleted,
                                copied=n_copied)

            # not-matched source rows → insert clauses
            sev = stage("delta.dml.merge.apply.notMatched")
            inserts, n_inserted = self._apply_not_matched(
                matched_pairs, src, target_cols, source_cols, metadata
            )
            sev.data["inserted"] = n_inserted
            if inserts is not None and inserts.num_rows:
                out_blocks.append(inserts)
                if self._use_cdf:
                    self._cdf_blocks.append(("insert", inserts))

        # claimed rows are marked deleted via per-file deletion vectors;
        # everything else stays live in place — the file rewrite (and its
        # copy block above) disappears entirely. A file's vector and the
        # new data file need nothing of each other: where the statement made
        # both, the vectors are written beside the data file, not before it
        marked: List[Tuple[Action, Optional[Action]]] = []
        pool = beside = None
        if use_dv and self._writes_beside(claimed_tbl, out_blocks):
            from concurrent.futures import ThreadPoolExecutor

            telemetry.bump_counter("merge.dv.overlapped")
            # one thread holds the phase's span, the others run its jobs
            pool = ThreadPoolExecutor(
                max_workers=1 + min(len(candidates), os.cpu_count() or 4),
                thread_name_prefix="delta-merge-dv")
            beside = pool.submit(
                telemetry.propagated(self._write_vectors),
                candidates, claimed_tbl, pool)
        elif use_dv:
            marked = self._write_vectors(candidates, claimed_tbl)

        written: List[Action] = []
        cdc_actions: List[Action] = []
        try:
            with self._phase("delta.dml.merge.write", "write_ms"):
                out = None
                # what comes before the shared writer, whose own stages
                # (`delta.write.prepare`, `.encode`, `.stats`) tile the rest
                with telemetry.record_operation(
                        "delta.dml.merge.write.concat",
                        {"blocks": len(out_blocks), "rows": 0}) as cev:
                    if self._cdf_blocks:
                        cdc_actions = list(cdf_exec.write_change_data(
                            self.delta_log.data_path, self._cdf_blocks, metadata
                        ))
                    if out_blocks:
                        out = pa.concat_tables(out_blocks, promote_options="permissive")
                        if out.column_names != target_cols:
                            out = out.select(target_cols)
                        cev.data["rows"] = out.num_rows
                if out is not None and out.num_rows:
                    written = list(
                        write_exec.write_files(
                            self.delta_log.data_path, out, metadata, data_change=True
                        )
                    )
        finally:
            # every file and vector is on disk before the commit is built;
            # a vector job's error is the statement's, whatever the write did
            if beside is not None:
                try:
                    marked = beside.result()
                except BaseException:
                    self._remove_files(written + cdc_actions)
                    raise
                finally:
                    pool.shutdown()
        for rm, re_add in marked:  # by file id, as the jobs were made
            removes.append(rm)
            if re_add is not None:
                dv_adds.append(re_add)
        adds: List[Action] = dv_adds + written
        rewrite_ms = timer.lap_ms()

        self.metrics.update(
            numSourceRows=self.source.num_rows,
            numTargetRowsCopied=n_copied,
            numTargetRowsUpdated=n_updated,
            numTargetRowsDeleted=n_deleted,
            numTargetRowsInserted=n_inserted,
            numTargetFilesRemoved=len(removes),
            numTargetFilesAdded=len(adds),
            scanTimeMs=scan_ms,
            rewriteTimeMs=rewrite_ms,
        )
        txn.report_metrics(**self.metrics)
        def _clause_info(c: MergeClause) -> Dict[str, Any]:
            info: Dict[str, Any] = {"actionType": c.kind}
            if c.condition is not None:
                info["predicate"] = c.condition.sql()
            return info

        op = ops.Merge(
            predicate=self.condition.sql(),
            updates=[_clause_info(c) for c in self.matched_clauses if c.kind == "update"],
            deletes=[_clause_info(c) for c in self.matched_clauses if c.kind == "delete"],
            inserts=[_clause_info(c) for c in self.not_matched_clauses],
        )
        version = txn.commit(removes + adds + cdc_actions, op)
        with self._phase("delta.dml.merge.residentKeys", "resident_ms"):
            self._maybe_build_resident_keys()
        return version

    # -- distributed touched-files probe ----------------------------------

    def _probe_touched_files(self, candidates, src, equi, metadata):
        """findTouchedFiles-style pre-probe on the sharded executor
        (reference `MergeIntoCommand.scala` findTouchedFiles — phase 1 of
        the two-phase merge): read ONLY the equi-key columns of each
        candidate file as byte-weighted work items and keep the files whose
        keys intersect the source keys.

        Soundness: per-key-column ``is_in`` is a conservative superset of
        exact tuple membership, so a touched file is never dropped;
        untouched files contribute no matched pairs and are never
        rewritten, and this MERGE has no NOT-MATCHED-BY-SOURCE clauses, so
        restricting the candidate set is result-identical by construction.
        Null target keys never equal a source key, so dropping all-miss
        files stays exact under SQL join semantics.
        """
        from delta_tpu.utils.config import conf

        if not conf.get_bool("delta.tpu.distributed.merge.probe.enabled", True):
            return candidates
        min_files = conf.get_int("delta.tpu.distributed.merge.probe.minFiles", 8)
        if len(candidates) < max(min_files, 2):
            return candidates
        import pyarrow.compute as pc

        cols = sorted({r.lower() for t_e, _ in equi for r in ir.references(t_e)})
        svals = [(t_e, evaluate(s_e, src)) for t_e, s_e in equi]

        def _touched(f) -> bool:
            tbl = read_files_as_table(
                self.delta_log.data_path, [f], metadata, columns=cols)
            if tbl.num_rows == 0:
                return False
            for t_e, sv in svals:
                tv, sv2 = _coerce_join_keys(evaluate(t_e, tbl), sv)
                if isinstance(tv, pa.ChunkedArray):
                    tv = tv.combine_chunks()
                if isinstance(sv2, pa.ChunkedArray):
                    sv2 = sv2.combine_chunks()
                if not pc.any(pc.is_in(tv, value_set=sv2)).as_py():
                    return False
            return True

        from delta_tpu.parallel.executor import run_sharded

        telemetry.bump_counter("dist.merge.filesProbed", len(candidates))
        with self._phase(
            "delta.dist.mergeProbe", "probe_ms", {"candidates": len(candidates)}
        ) as probe_ev:
            try:
                report = run_sharded(
                    candidates, _touched,
                    sizes=[f.size or 0 for f in candidates],
                    label="merge-probe", on_failure="quarantine")
            except Exception:  # noqa: BLE001 — probe machinery failure:
                # the probe is an OPTIMIZATION — fall back to the full
                # conservative candidate set rather than failing the MERGE
                telemetry.bump_counter("dist.degraded.probe")
                probe_ev.data["degraded"] = True
                probe_ev.data["touched"] = len(candidates)
                return candidates
            # a quarantined probe item is a file whose keys we could not
            # read — soundness demands it stays IN the candidate set (the
            # probe may only drop files proven all-miss, hit is False)
            touched = [f for f, hit in zip(candidates, report.results)
                       if hit is not False]
            if report.quarantined:
                telemetry.bump_counter("dist.degraded.probe")
            probe_ev.data["touched"] = len(touched)
        return touched

    # -- join -------------------------------------------------------------

    def _join(self, txn, candidates: List[AddFile], src: pa.Table, equi, residual,
              metadata, prune_pred: Optional[ir.Expression] = None,
              ) -> Tuple[pa.Table, Dict[int, pa.Table], List[AddFile]]:
        """Inner-join source×candidate-target. Returns (pair table with
        target cols bare + source cols prefixed + ids, per-file target
        tables with row ids, the candidates as the join narrowed them: the
        pairs' file ids index that list).

        Resident pairs-only route: when the key cache holds the table's
        slab and nothing of a target row is needed but which row it is
        (`_pairs_only_shape`), the probe's pairs are the join: no
        touched-files pre-probe, no decode of the target, see
        `_pairs_from_probe`.

        Device path otherwise: the join-key columns decode first (a cheap
        projected Parquet read), the membership kernel launches
        asynchronously, and the full-column decode of the candidates runs on
        the host *while the device probes* — the kernel's wall-clock hides
        under the decode.

        ``prune_pred`` (the target-only conjuncts of the merge condition)
        enables row-group skipping inside candidate files: a pruned group
        can hold no join matches (the conjuncts are implied by the full
        condition). Applied only when unmatched target rows are never
        written back — DV mode (positions stay physical) or insert-only
        merges (target rows feed the join and nothing else)."""
        import numpy as np

        # what the statement needs of the target: from the statement alone
        target_cols = [f.name for f in metadata.schema.fields]
        insert_only = not self.matched_clauses
        key_need = {r.lower() for t_e, _ in equi for r in ir.references(t_e)}
        # insert-only merges never rewrite target rows: read only the columns
        # the join condition touches (the reference's left-anti fast path
        # reads the full target; we push the projection into the Parquet scan)
        read_cols: Optional[List[str]] = None
        if insert_only:
            need = key_need | {
                r.lower()
                for c in residual
                for r in ir.references(c)
                if not r.startswith(_SRC)
            }
            cols = [c for c in target_cols if c.lower() in need]
            read_cols = cols or None
        else:
            read_cols = self._referenced_target_columns(
                metadata, target_cols, [c for c in src.column_names
                                        if c.startswith(_SRC)],
                key_need, residual,
            )
        # DV-mode matched clauses mark physical rows deleted — every scan
        # that can end up as the phase-2 tables must carry positions
        pos_col = (
            POSITION_COL
            if (not insert_only and dv_common.dv_enabled(metadata))
            else None
        )
        mode = str(conf.get("delta.tpu.merge.devicePath.mode", "auto"))
        shape_eligible = (
            bool(conf.get("delta.tpu.merge.devicePath.enabled", True))
            and mode != "off"
            and 1 <= len(equi) <= 2
            and not residual
            and src.num_rows > 0
        )

        # the resident route is decided first: when the slab is cached and
        # the pairs alone will do, the probe launches on every file of the
        # snapshot and the touched-files pre-probe (whose answer is a
        # by-product of that probe) is not run
        resident = None
        resident_tried = False
        whole_table = True
        pairs_shape = bool(
            shape_eligible and candidates
            and self._pairs_only_shape(equi, read_cols, key_need, pos_col,
                                       insert_only))
        if pairs_shape and self._resident_entry_cached(txn, equi):
            resident_tried = True
            with self._phase("delta.dml.merge.keyDecode", "key_decode_ms"):
                resident = self._launch_resident_probe(
                    txn, candidates, src, equi, target_cols, key_need,
                    pos_col, insert_only,
                )
        if resident is not None:
            telemetry.bump_counter("merge.resident.pairsOnly")
            self._audit_eligible = True
            with self._phase("delta.dml.merge.rowDecode", "decode_ms"):
                # nothing of the target is read
                n_target = _rows_from_stats(candidates)
                self._audit_units = (
                    resident[0].num_rows if n_target is None else n_target,
                    src.num_rows)
            with self._phase("delta.dml.merge.join", "join_ms",
                             {"route": "pairs-only"}):
                joined = self._pairs_from_probe(
                    resident[1], candidates, src, equi, read_cols or [],
                    metadata, insert_only)
            if joined is not None:
                return joined, {}, candidates
            # a designed decline: decode late and take the path below, the
            # probe still in hand
            telemetry.bump_counter("merge.resident.pairsOnly.declined")
        elif equi and not (pairs_shape
                           and self._builds_table_slab(candidates, src, mode)):
            # distributed findTouchedFiles probe: restrict the candidates to
            # files whose equi keys intersect the source BEFORE the join
            # decodes full rows (conf-gated; result-identical — see the method)
            n_all = len(candidates)
            candidates = self._probe_touched_files(candidates, src, equi, metadata)
            # a slab built over fewer files than the table holds must not
            # be registered as the table's
            whole_table = len(candidates) == n_all

        # routing, the key-column decode, and the slab advance / upload launch
        with self._phase("delta.dml.merge.keyDecode", "key_decode_ms"):
            base_eligible = bool(shape_eligible and candidates)
            device_eligible = base_eligible
            # audit: whether a device route even existed for this condition
            # shape — a structurally host-only merge is audited without a
            # device alternative (no hindsight miss against a route that
            # could not have run)
            self._audit_eligible = base_eligible
            if (device_eligible and mode == "auto"
                    and self._cold_estimate_declines(candidates, src)):
                device_eligible = False
                telemetry.bump_counter("merge.device.declined")
                self._router.update(reason="cold-estimate")

            # row-group skipping is only safe when unmatched target rows never
            # need writing back: DV mode (matched rows mark by physical
            # position) or insert-only (target rows exist only to probe)
            if pos_col is None and not insert_only:
                prune_pred = None
            pending = None
            via = None
            key_pieces: Optional[List[pa.Table]] = None
            key_pieces_have_pos = False
            if base_eligible:
                # resident-operand path first: the target key lane already lives
                # in HBM (ops/key_cache), so the probe ships only source keys —
                # different economics from the cold upload path, hence evaluated
                # before (and independent of) the upload-cost gate above. Not
                # asked twice: what the pairs-only route was refused, or
                # launched and then declined, stands
                if not resident_tried:
                    resident = self._launch_resident_probe(
                        txn, candidates, src, equi, target_cols, key_need,
                        pos_col, insert_only,
                    )
                if resident is not None:
                    via = "resident"
            if resident is None and device_eligible:
                if not self._prefers_mesh():
                    # fused cold pipeline: per-file key decode streams into a
                    # pre-sized HBM slab (upload overlaps decode), then the
                    # resident probe joins + pairs on device — and the
                    # slab registers in the KeyCache so the NEXT merge against
                    # this table skips the upload entirely
                    resident, key_pieces = self._launch_slab_pipeline(
                        txn, candidates, src, equi, target_cols, key_need,
                        pos_col, insert_only, metadata, register=whole_table,
                    )
                    if resident is not None:
                        via = "device-cold"
                    key_pieces_have_pos = key_pieces is not None
                if resident is None and key_pieces is None:
                    # multichip mesh (all-gather sort-merge kernel, opt-in via
                    # devicePath.preferMesh), or the slab pipeline bailed before
                    # decoding: decode the key projection and launch the upload
                    # join
                    key_cols = [c for c in target_cols if c.lower() in key_need]
                    key_pieces = read_files_as_table(
                        self.delta_log.data_path, candidates, metadata,
                        columns=key_cols or None, per_file=True,
                        position_column=pos_col, predicate=prune_pred,
                        # the key read and the full read below must stay
                        # row-aligned (the device probe's indices map onto the
                        # full decode) — stats-pruning is deterministic across
                        # both, but late materialization's verdict depends on
                        # the decoded columns
                        late_materialize=False,
                    )
                if resident is None:
                    key_tab = pa.concat_tables(key_pieces,
                                               promote_options="permissive")
                    if key_tab.num_rows:
                        pending = self._launch_device_join(key_tab, src, equi)
                        if pending is not None:
                            via = "device-upload"
                        else:
                            self._router.setdefault("reason", "upload-declined")

        with self._phase("delta.dml.merge.rowDecode", "decode_ms"):

            # full-column decode (overlaps the in-flight device probe); when the
            # key projection already covers every needed column, reuse it (the
            # slab pipeline's pieces carry an extra position column — harmless,
            # every write-side consumer projects to target_cols)
            if key_pieces is not None and read_cols is not None and set(
                c.lower() for c in read_cols
            ) <= key_need and (not key_pieces_have_pos or pos_col is not None
                               or insert_only):
                raw_pieces = key_pieces
            else:
                raw_pieces = read_files_as_table(
                    self.delta_log.data_path, candidates, metadata,
                    columns=read_cols, per_file=True, position_column=pos_col,
                    predicate=prune_pred, late_materialize=False,
                )
            tgt_tables: Dict[int, pa.Table] = {}
            pieces: List[pa.Table] = []
            row_base = 0
            for fid, t in enumerate(raw_pieces):
                t = t.append_column(
                    _TID,
                    pa.array(np.arange(row_base, row_base + t.num_rows, dtype=np.int64)),
                )
                t = t.append_column(
                    _FID, pa.array(np.full(t.num_rows, fid, dtype=np.int64))
                )
                row_base += t.num_rows
                tgt_tables[fid] = t
                pieces.append(t)
            if not pieces:
                empty = pa.schema(
                    [pa.field(_TID, pa.int64()), pa.field(_FID, pa.int64())]
                ).empty_table()
                target = empty
            else:
                target = pa.concat_tables(pieces, promote_options="permissive")
            self._audit_units = (target.num_rows, src.num_rows)

        def empty_pairs() -> pa.Table:
            # empty pair table with the full combined (target + source) schema
            combined = target.slice(0, 0)
            for name in src.column_names:
                combined = combined.append_column(
                    name, pa.nulls(0, src.column(name).type)
                )
            return combined

        if target.num_rows == 0 or src.num_rows == 0:
            return empty_pairs(), tgt_tables, candidates

        # the wait for the device probe, or the host join; then the pair take
        with self._phase("delta.dml.merge.join", "join_ms",
                         {"route": "host"}) as join_ev:
            if resident is not None or pending is not None:
                # two stages tile the span on a device route: the wait for
                # the device's answer, then the host's pair mapping and take
                with telemetry.span_stages() as stage:
                    stage(_JOIN_WAIT)
                    if pending is None:
                        pending = self._finalize_resident(
                            resident, candidates, tgt_tables, target, src,
                            equi, pos_col, insert_only,
                            got_pairs=lambda: stage(_JOIN_PAIRS),
                        )
                    res = pending.result()
                    stage(_JOIN_PAIRS)
                    if res is None:
                        self._router.setdefault(
                            "reason", "device-finalize-fallback")
                    else:
                        self._device_join = res
                        self._join_path = via
                        self._router["route"] = join_ev.data["route"] = "decode"
                        # insert-only never consumes the pair rows (the
                        # not-matched block comes from s_matched): skip
                        # materializing them
                        if insert_only:
                            joined = empty_pairs()
                        else:
                            matched = np.flatnonzero(res.t_matched)
                            joined = target.take(pa.array(matched, pa.int64()))
                            s_taken = src.take(
                                pa.array(res.t_first_s[matched], pa.int64())
                            )
                            for name in s_taken.column_names:
                                joined = joined.append_column(
                                    name, s_taken.column(name))
                        return joined, tgt_tables, candidates

            if equi:
                # Join INDEX tables (keys + row positions), then take the full
                # rows: Arrow's hash join refuses nested (struct/list/map)
                # non-key payload columns, and carrying 2 int columns through
                # the join beats carrying every column anyway.
                key_cols = []
                for t_e, s_e in equi:
                    t_vals = evaluate(t_e, target)
                    s_vals = evaluate(s_e, src)
                    key_cols.append(_coerce_join_keys(t_vals, s_vals))
                t_idx_cols = {"__trow__": pa.array(np.arange(target.num_rows), pa.int64())}
                s_idx_cols = {"__srow__": pa.array(np.arange(src.num_rows), pa.int64())}
                tkeys, skeys = [], []
                for i, (t_vals, s_vals) in enumerate(key_cols):
                    k = f"__k{i}__"
                    t_idx_cols[k] = t_vals
                    s_idx_cols[k] = s_vals
                    tkeys.append(k)
                    skeys.append(k)
                pairs_idx = pa.table(t_idx_cols).join(
                    pa.table(s_idx_cols), keys=tkeys, right_keys=skeys,
                    join_type="inner", use_threads=False,
                )
                t_take = pairs_idx.column("__trow__")
                s_take = pairs_idx.column("__srow__")
                joined = target.take(t_take)
                s_taken = src.take(s_take)
                for name in s_taken.column_names:
                    joined = joined.append_column(name, s_taken.column(name))
                # take() emits one chunk per input chunk: defragment once here
                # or every downstream mask/projection/encode pays per-chunk costs
                joined = joined.combine_chunks()
            else:
                # general condition: BLOCKED cartesian pairing — tile the
                # target x source grid and stream each tile through the clause
                # condition immediately, so peak memory is one tile of pairs
                # (`delta.tpu.merge.nonEquiPairBudget`) regardless of input
                # sizes. The reference handles arbitrary conditions via a real
                # join (`MergeIntoCommand.scala:335-341`); this is the bounded
                # equivalent for a columnar engine without a theta-join kernel.
                budget = int(conf.get("delta.tpu.merge.nonEquiPairBudget",
                                      8_000_000))
                m = src.num_rows
                tile = max(budget // max(m, 1), 1)
                cond = ir.and_all(residual) if residual else None
                pieces = []
                s_base = np.tile(np.arange(m, dtype=np.int64), tile)
                for t0 in range(0, target.num_rows, tile):
                    rows = min(tile, target.num_rows - t0)
                    t_idx = np.repeat(np.arange(t0, t0 + rows, dtype=np.int64), m)
                    piece = target.take(pa.array(t_idx, pa.int64()))
                    s_taken = src.take(pa.array(s_base[: rows * m], pa.int64()))
                    for name in s_taken.column_names:
                        piece = piece.append_column(name, s_taken.column(name))
                    if cond is not None:
                        piece = piece.filter(boolean_mask(cond, piece))
                    if piece.num_rows:
                        pieces.append(piece.combine_chunks())
                joined = (pa.concat_tables(pieces).combine_chunks()
                          if pieces else empty_pairs())
                return joined, tgt_tables, candidates
            if residual:
                joined = joined.filter(boolean_mask(ir.and_all(residual), joined))
            return joined, tgt_tables, candidates

    def _referenced_target_columns(
        self, metadata, target_cols, src_prefixed, key_need, residual,
    ) -> Optional[List[str]]:
        """Project the candidate scan to the target columns phase 2 can
        touch — or None when every column is needed.

        Valid only when nothing re-materializes whole target rows: deletion
        vectors on (no copy block — unclaimed/unmatched rows stay in their
        files), CDC off (no preimages), no generated columns (recompute
        reads arbitrary base columns), and every update clause a star
        (explicit assignments keep unassigned target columns, i.e. all of
        them). For a star upsert this collapses the scan to the join keys —
        the dominant cost of the DV merge path."""
        from delta_tpu.schema.generated import generated_column_names

        if not dv_common.dv_enabled(metadata) or self._use_cdf:
            return None
        if generated_column_names(metadata.schema):
            return None
        source_bare = [c[len(_SRC):] for c in src_prefixed]
        src_lower = {c.lower() for c in source_bare}
        need = set(key_need)
        for c in residual:
            need |= {r.lower() for r in ir.references(c)
                     if not r.startswith(_SRC)}
        try:
            for clause in self.matched_clauses + self.not_matched_clauses:
                if clause.condition is not None:
                    resolved = self._resolve(
                        clause.condition, target_cols, source_bare
                    )
                    need |= {r.lower() for r in ir.references(resolved)
                             if not r.startswith(_SRC)}
                if clause.kind == "update":
                    if not clause.is_star:
                        return None
                    # star update: target-only columns copy from the target
                    need |= {c.lower() for c in target_cols
                             if c.lower() not in src_lower}
        except DeltaAnalysisError:
            return None  # let the normal path raise the real resolution error
        cols = [c for c in target_cols if c.lower() in need]
        if len(cols) == len(target_cols):
            return None
        return cols or None

    # -- resident-key device path (ops/key_cache) -------------------------

    @staticmethod
    def _key_signature(t_exprs) -> str:
        return repr([repr(e) for e in t_exprs])

    def _pairs_only_shape(self, equi, read_cols, key_need, pos_col,
                          insert_only) -> bool:
        """Whether nothing of a matched target row is needed but which row
        it is, so that the resident probe's pairs can be the join: matched
        rows are marked by position in deletion vectors (or the merge only
        inserts); the statement reads no target column but the keys (star
        update, no CDF, no generated column, no clause condition on another
        column: `_referenced_target_columns`); and every target key is a
        bare column, so a matched row's key *is* its source row's key in
        the column's type (the slab packs integer keys only, NULL never
        matches)."""
        if pos_col is None and not insert_only:
            return False
        if read_cols is None or not {c.lower() for c in read_cols} <= key_need:
            return False
        return all(isinstance(t_e, ir.Column) for t_e, _ in equi)

    @staticmethod
    def _prefers_mesh() -> bool:
        """The multichip all-gather join is asked for (opt-in) and there is
        more than one device to run it on: no slab is built then."""
        import jax

        return len(jax.devices()) > 1 and conf.get_bool(
            "delta.tpu.merge.devicePath.preferMesh", False)

    def _cold_estimate_declines(self, candidates, src) -> bool:
        """``mode=auto``'s pre-decode routing check from the AddFile stats'
        row counts: on a slow link even the *optimistic* cold plan (slab
        upload, sort, probe over int32 keys) loses to the host hash join,
        and the early key decode is skipped. The cache-hit case has its own,
        upload-free economics (`_launch_resident_probe`). Both estimates go
        on the router event; no stats, no verdict."""
        n_est = _rows_from_stats(candidates)
        if n_est is None:
            return False
        import jax

        from delta_tpu.parallel import link

        rows = n_est + src.num_rows
        if not self._prefers_mesh():
            device_s = link.cold_merge_device_s(
                n_est, src.num_rows, link.profile())
        else:
            device_s = link.estimate_device_s(
                up_bytes=rows * 4,
                down_bytes=rows // 8,
                kernel_rows=rows,
                shards=len(jax.devices()),
            ).device_s
        host_est_s = rows * link.constant("HOST_JOIN_S_PER_ROW")
        self._router.setdefault("deviceEstS", round(device_s, 3))
        self._router.setdefault("hostEstS", round(host_est_s, 3))
        return device_s > host_est_s

    def _builds_table_slab(self, candidates, src, mode: str) -> bool:
        """Whether a MERGE that is pairs-only in shape and finds no slab to
        serve it will build the table's now (`_launch_slab_pipeline` over
        every file). The touched-files pre-probe is then not run: it reads
        the key columns the build reads, nothing else of a row is decoded
        for it to spare, and a slab over the files it leaves is this
        MERGE's and not the table's (never registered), so that a small
        source into a table of many files (a refresh function, a trickle of
        CDC) would find no slab at its next MERGE either."""
        from delta_tpu.ops.key_cache import key_cache_enabled

        if not key_cache_enabled() or self._prefers_mesh():
            return False
        return mode == "force" or not self._cold_estimate_declines(
            candidates, src)

    def _resident_entry_cached(self, txn, equi) -> bool:
        """A slab for this table and key signature is in the key cache
        (whether it can be advanced to the snapshot is
        `_launch_resident_probe`'s to find out)."""
        from delta_tpu.ops import key_cache as kc_mod

        return kc_mod.key_cache_enabled() and kc_mod.KeyCache.instance().peek(
            txn.snapshot.delta_log.log_path,
            self._key_signature([t for t, _ in equi])) is not None

    def _pairs_from_probe(self, probe, candidates, src, equi, key_cols,
                          metadata, insert_only) -> Optional[pa.Table]:
        """The pairs-only join: the pair table from the resident probe's
        pairs and the source alone. A pair is (physical slab row, source
        row) and the slab maps a file to its rows, so a pair *is* (file,
        position, source row); the key columns are the source's key
        expressions in the target's types. Rows in the order the decode
        route gives them: ascending candidate file, then position.

        None is a designed decline (the probe's windows overflowed, the slab
        lacks a candidate file; in ``auto`` also a device exception): the
        caller decodes after all. A pair in no candidate file means the slab
        is not this snapshot's: `_stale_slab`."""
        import numpy as np

        from delta_tpu.expr.vectorized import arrow_type_for
        from delta_tpu.ops import join_kernel
        from delta_tpu.ops import key_cache as kc_mod

        with telemetry.span_stages() as stage:
            stage(_JOIN_WAIT)
            try:
                res_p = probe.result()
            except kc_mod.DeltaProbeOverflow:
                return None
            except Exception as e:  # noqa: BLE001 — host rung (auto only)
                self._device_rung("device-finalize-fallback", e)
                return None
            stage(_JOIN_PAIRS)
            slabs = [res_p.slabs.get(f.path) for f in candidates]
            if any(ent is None for ent in slabs):
                return None
            declared = {f.name: arrow_type_for(f.data_type)
                        for f in metadata.schema.fields}
            fields = [pa.field(c, declared[c]) for c in key_cols]
            if not insert_only:
                fields.append(pa.field(POSITION_COL, pa.int64()))
            fields += [pa.field(_TID, pa.int64()), pa.field(_FID, pa.int64())]
            fields += [pa.field(n, src.column(n).type) for n in src.column_names]
            if insert_only or not len(res_p.t_pairs[0]):
                # insert-only consumes s_matched alone (no pairs were fetched)
                joined = pa.schema(fields).empty_table()
            else:
                phys, s_rows = res_p.t_pairs
                offs = np.array([off for off, _ in slabs], np.int64)
                lo = np.searchsorted(phys, offs)
                hi = np.searchsorted(
                    phys, offs + np.array([n for _, n in slabs], np.int64))
                per_file = hi - lo
                if int(per_file.sum()) != len(phys):
                    self._stale_slab("a matched slab row lies in no file of "
                                     "the snapshot")
                if (np.diff(offs) < 0).any():
                    # the slab holds the files in another order: the pairs
                    # (ascending slab row) go into the candidates' order
                    order = np.concatenate(
                        [np.arange(a, b) for a, b in zip(lo, hi)])
                    phys, s_rows = phys[order], s_rows[order]
                s_taken = src.take(pa.array(s_rows, pa.int64()))
                cols = []
                for c in key_cols:
                    s_e = next(s_e for t_e, s_e in equi
                               if t_e.name.lower() == c.lower())
                    cols.append(pc.cast(evaluate(s_e, s_taken), declared[c]))
                cols.append(pa.array(phys - np.repeat(offs, per_file)))
                # an id per target row: a slab row is in one pair at most
                cols.append(pa.array(np.arange(len(phys), dtype=np.int64)))
                cols.append(pa.array(np.repeat(
                    np.arange(len(candidates), dtype=np.int64), per_file)))
                cols += s_taken.columns
                joined = pa.table(cols, schema=pa.schema(fields))
            self._device_join = join_kernel.JoinResult(
                np.empty(0, np.int64), res_p.s_matched, res_p.any_multi)
            self._join_path = "resident"
            self._router["route"] = "pairs-only"
            return joined

    @staticmethod
    def _writes_beside(claimed_tbl, out_blocks) -> bool:
        """Whether the statement made both claimed rows to mark and rows to
        write: only then is there a write for the vectors to run beside.
        One of the two alone (a keyed delete, an insert) runs inline: no
        pool, no thread hop."""
        return (claimed_tbl is not None and claimed_tbl.num_rows > 0
                and any(b.num_rows for b in out_blocks))

    def _write_vectors(self, candidates, claimed_tbl, pool=None):
        """The phase ``delta.dml.merge.deletionVectors``: one job for every
        file a matched clause claimed rows of, by file id (each is one
        `dv_mark_deleted`, with its own `AddFile`, its own positions and its
        own vector file); returns ``(remove, re-add or None)`` a job, in that
        order. With a ``pool`` the caller's thread is writing the data file
        meanwhile: this runs on one of the pool's threads and the jobs on
        the others, under its span. Without, the jobs run inline, one after
        another."""
        import numpy as np

        def mark(job):
            add, claimed = job
            return dv_common.dv_mark_deleted(
                self.delta_log.data_path, add, claimed)

        with self._phase("delta.dml.merge.deletionVectors", "dv_ms",
                         {"files": 0, "rows": 0,
                          "overlapped": pool is not None}) as dv_ev:
            if claimed_tbl is None or not claimed_tbl.num_rows:
                return []
            fids = claimed_tbl.column(_FID).to_numpy(zero_copy_only=False)
            poss = claimed_tbl.column(POSITION_COL).to_numpy(zero_copy_only=False)
            jobs = [(candidates[int(fid)], poss[fids == fid])
                    for fid in np.unique(fids)]
            dv_ev.data.update(files=len(jobs), rows=len(poss))
            if pool is not None:
                marked = list(pool.map(telemetry.propagated(mark), jobs))
            else:
                marked = [mark(j) for j in jobs]
            if self._pairs_only:
                for (add, claimed), (_, re_add) in zip(jobs, marked):
                    self._check_claimed_were_live(add, re_add, len(claimed))
        return marked

    def _remove_files(self, actions) -> None:
        """Best effort: take the files this attempt wrote (and will not
        commit) off the table's directory, as if the failure had come
        before the write."""
        from delta_tpu.exec.scan import _abs_data_path

        for a in actions:
            try:
                os.remove(_abs_data_path(self.delta_log.data_path, a.path))
            except OSError:
                pass

    def _stale_slab(self, why: str) -> None:
        """Drop the table's slab and end this run of the body: `run` makes
        the next."""
        from delta_tpu.ops.key_cache import KeyCache

        KeyCache.instance().invalidate(self.delta_log.log_path)
        telemetry.bump_counter("merge.resident.pairsOnly.declined")
        raise _StaleResidentSlab(why)

    def _check_claimed_were_live(self, add, re_add, n_claimed: int) -> None:
        """What the decode route cross-checked ("the slab matched a row the
        decode dropped"), where it is free on the pairs-only route:
        `dv_mark_deleted` united the file's old vector with the claimed
        positions, and a union smaller than the two together means the slab
        matched a row that was deleted already. That MERGE does not commit."""
        now_dead = n_claimed + int(
            (add.deletion_vector or {}).get("cardinality", 0))
        if re_add is not None:
            stale = int(re_add.deletion_vector["cardinality"]) != now_dead
        else:  # the whole file went: more dead rows than rows
            rows = add.num_logical_records
            stale = rows is not None and now_dead > rows
        if stale:
            self._stale_slab(f"a matched row of {add.path} was deleted already")

    def _launch_resident_probe(self, txn, candidates, src, equi, target_cols,
                               key_need, pos_col, insert_only):
        """Probe the HBM-resident target key lane (if one is current for this
        table + key signature): ships only the source keys. Returns
        (entry, PendingProbe, s_keys, s_ok) or None — and when the lane
        doesn't exist yet, records the signature so a background build can
        start after this merge commits (the CDC steady-state warmup)."""
        import numpy as np

        from delta_tpu.expr.vectorized import evaluate
        from delta_tpu.ops import key_cache as kc_mod
        from delta_tpu.parallel import link

        if not kc_mod.key_cache_enabled():
            return None
        # bit mapping back to the DV-filtered decode needs physical
        # positions; without them only DV-free candidates are alignable
        # (insert-only merges never consume per-target bits)
        if (pos_col is None and not insert_only
                and any(f.deletion_vector is not None for f in candidates)):
            return None
        t_exprs = [t for t, _ in equi]
        s_exprs = [s for _, s in equi]
        sig = self._key_signature(t_exprs)
        key_cols = [c for c in target_cols if c.lower() in key_need]
        entry = kc_mod.KeyCache.instance().get(
            txn.snapshot, sig, key_cols, t_exprs, build_if_missing=False
        )
        if entry is None:
            self._resident_candidate = (sig, key_cols, t_exprs)
            return None
        packed = kc_mod._pack_lanes(src, s_exprs, evaluate)
        if packed is None:
            return None
        s_keys, s_ok = packed
        self._router["cacheHit"] = True
        if str(conf.get("delta.tpu.merge.devicePath.mode", "auto")) == "auto":
            m = len(s_keys)
            n = entry.num_rows
            p = link.profile()
            # the fused-path probe model (link.resident_probe_device_s)
            device_s = link.resident_probe_device_s(n, m, p)
            if not entry.is_resident:
                # the device copy was evicted / regrown: the probe would
                # synchronously re-ship the whole slab first — charge it
                device_s += p.upload_s(entry.capacity * 9)
            host_s = ((n + m) * link.constant("HOST_JOIN_S_PER_ROW")
                      + n * link.constant("HOST_KEY_DECODE_S_PER_ROW"))
            self._router["deviceEstS"] = round(device_s, 3)
            self._router["hostEstS"] = round(host_s, 3)
            if device_s > host_s:
                from delta_tpu.utils.telemetry import bump_counter

                bump_counter("merge.device.declined")
                self._router.update(reason="resident-estimate")
                return None
        probe = entry.probe_async(
            s_keys, s_ok, expected_version=txn.snapshot.version,
            insert_only=insert_only,
        )
        if probe is None:
            return None
        self._audit_slab_rows = entry.num_rows
        return entry, probe, s_keys, s_ok

    def _launch_slab_pipeline(self, txn, candidates, src, equi, target_cols,
                              key_need, pos_col, insert_only, metadata,
                              register: bool = True):
        """The cold fused device MERGE pipeline: decode the key projection
        per file, streaming each decoded file's packed lane onto a
        pre-sized HBM slab from an uploader thread (transfer overlaps the
        remaining Parquet decode), then launch the resident probe —
        and register the slab in the KeyCache so repeated MERGEs against a
        hot table skip the upload entirely.

        Returns ``(resident_tuple_or_None, key_pieces_or_None)`` —
        ``resident_tuple`` feeds `_finalize_resident`; ``key_pieces`` (the
        per-file decoded key tables, position column attached) is returned
        even on build failure so the caller can reuse the decode.
        ``register`` is False when the pre-probe narrowed ``candidates``:
        such a slab serves this MERGE and is not the table's."""
        import queue as queue_mod
        import threading as threading_mod

        from delta_tpu.expr.vectorized import evaluate
        from delta_tpu.ops import key_cache as kc_mod

        # DV alignment guard (mirrors the resident-hit path)
        if (pos_col is None and not insert_only
                and any(f.deletion_vector is not None for f in candidates)):
            return None, None
        t_exprs = [t for t, _ in equi]
        s_exprs = [s for _, s in equi]
        packed = kc_mod._pack_lanes(src, s_exprs, evaluate)
        if packed is None:
            return None, None
        s_keys, s_ok = packed
        snapshot = txn.snapshot
        sig = self._key_signature(t_exprs)
        key_cols = [c for c in target_cols if c.lower() in key_need]
        cache = kc_mod.KeyCache.instance()
        try:
            builder = kc_mod.SlabBuilder(
                snapshot.delta_log.log_path, snapshot.metadata.id,
                snapshot.version, sig, key_cols, t_exprs,
                self.delta_log.data_path, candidates,
                epoch=cache.epoch(snapshot.delta_log.log_path),
            )
        except Exception as e:  # noqa: BLE001 — host rung (auto only)
            self._device_rung("slab-build-failed", e)
            return None, None
        if builder.failed is not None:
            return None, None

        q: "queue_mod.Queue" = queue_mod.Queue()

        def on_ready(i, add, tab):
            q.put((add, tab))

        # carry the MERGE span chain into the uploader thread so each slab
        # upload shows as a `delta.merge.slabUpload` span on its own trace
        # lane under `delta.dml.merge` — the decode/upload overlap the
        # router assumes, finally visible in export_chrome_trace
        upload_ctx = telemetry.span_context()
        upload_errors: List[BaseException] = []

        def uploader():
            # device dispatches are async: this thread mostly queues
            # transfers, which the transfer engine overlaps with the
            # decode pool still running on the other files
            with telemetry.adopt_span_context(upload_ctx):
                while True:
                    item = q.get()
                    if item is None:
                        return
                    add, tab = item
                    try:
                        with telemetry.record_operation(
                                "delta.merge.slabUpload",
                                {"file": add.path, "rows": tab.num_rows}):
                            pos = tab.column(POSITION_COL).to_numpy(
                                zero_copy_only=False)
                            builder.add_file(add, tab, pos)
                    except Exception as e:  # noqa: BLE001 — surfaced by
                        # the MERGE thread after the join (_device_rung)
                        builder.failed = builder.failed or "slab append failed"
                        upload_errors.append(e)

        th = threading_mod.Thread(target=uploader, daemon=True,
                                  name="delta-merge-slab-upload")
        th.start()
        try:
            # full physical rows per file: no row-group pruning, positions
            # attached so DV-filtered decodes scatter into slab layout
            key_pieces = read_files_as_table(
                self.delta_log.data_path, candidates, metadata,
                columns=key_cols or None, per_file=True,
                position_column=POSITION_COL, predicate=None,
                late_materialize=False, file_ready=on_ready,
            )
        finally:
            q.put(None)
            th.join()
        # a device exception on the uploader thread or in the slab
        # allocation (the build then ran on host mirrors) is not a designed
        # decline: under mode=force it propagates from here
        if upload_errors:
            self._device_rung("slab-build-failed", upload_errors[0])
        elif builder.alloc_error is not None:
            self._device_rung("slab-alloc-failed", builder.alloc_error)
        entry = builder.finish(len(candidates))
        if entry is None:
            self._router.setdefault("reason", "slab-build-failed")
            return None, key_pieces
        # under device eligibility the candidate set is the whole table (a
        # residual-free condition prunes nothing) unless the touched-files
        # pre-probe dropped files: only the complete slab is registered, so
        # that future merges can cache-hit it
        registered = register and cache.register(entry)
        if registered:
            self._resident_candidate = None  # no background build needed
        probe = entry.probe_async(
            s_keys, s_ok, expected_version=snapshot.version,
            insert_only=insert_only,
        )
        if probe is None:
            self._router.setdefault("reason", "no-sentinel-room")
            return None, key_pieces
        self._audit_slab_rows = entry.num_rows
        return (entry, probe, s_keys, s_ok), key_pieces

    def _finalize_resident(self, resident, candidates, tgt_tables, target,
                           src, equi, pos_col, insert_only,
                           got_pairs=lambda: None):
        """Map the device-computed pairs (physical slab row → first-match
        source row) onto the DV-filtered decode: the host does only the
        O(matched) position mapping — no key re-derivation, no host-side
        pairing sort. Returns a PendingJoin whose result is a JoinResult
        (or None → the caller falls back to the host hash join).
        ``got_pairs`` is called once the pairs are in host memory."""
        import numpy as np

        from delta_tpu.ops import join_kernel
        from delta_tpu.ops import key_cache as kc_mod

        entry, probe, s_keys, s_ok = resident

        def finalize():
            # designed declines — the probe's candidate windows overflowed,
            # or the pair mapping disagrees with the slab — surface as None
            # (documented host-join fallback); any other exception is a
            # device failure and takes _device_rung
            try:
                res_p = probe.result()
                got_pairs()
                n_target = target.num_rows
                t_first_s = np.full(n_target, -1, np.int64)
                if insert_only:
                    # only s_matched / any_multi are consumed downstream
                    return join_kernel.JoinResult(
                        t_first_s, res_p.s_matched, res_p.any_multi
                    )
                row_base = 0
                for fid in sorted(tgt_tables):
                    t = tgt_tables[fid]
                    add = candidates[fid]
                    if pos_col is not None:
                        positions = t.column(pos_col).to_numpy(
                            zero_copy_only=False)
                    else:
                        positions = None
                    got = res_p.pairs_for_file(add.path, positions,
                                               t.num_rows)
                    if got is None:
                        return None  # slab/decode disagree: host fallback
                    local_idx, s_rows = got
                    t_first_s[row_base + local_idx] = s_rows
                    row_base += t.num_rows
                return join_kernel.JoinResult(t_first_s, res_p.s_matched,
                                              res_p.any_multi)
            except kc_mod.DeltaProbeOverflow:
                return None
            except Exception as e:  # noqa: BLE001 — host rung (auto only)
                self._device_rung("device-finalize-fallback", e)
                return None

        return join_kernel.PendingJoin(finalize)

    def _device_rung(self, reason: str, e: BaseException) -> None:
        """An unexpected exception on the device path (compile refusal,
        XlaRuntimeError, OOM — not a designed decline). ``mode=force`` pins
        the device, so it propagates: a kernel the chip refuses must not
        read as a correct MERGE the host quietly did. In ``auto`` the
        exception rides the ``delta.merge.router`` event, and
        `_emit_router` counts the MERGE under ``merge.device.fallback`` if
        the host join then took over (after a failed slab allocation the
        build continues on host mirrors and the device join may still
        engage)."""
        if str(conf.get("delta.tpu.merge.devicePath.mode", "auto")) == "force":
            raise e
        self._router.setdefault("reason", reason)
        self._router.setdefault("error", telemetry.exc_text(e))

    def _emit_router(self) -> None:
        """One `delta.merge.router` event per MERGE — the production-table
        observable of which executor `auto` chose — plus the
        `merge.device.*` counters the /metrics endpoint and flight recorder
        surface, and the router AUDIT record pricing the decision against
        the measured phase durations (obs/router_audit)."""
        from delta_tpu.utils.telemetry import bump_counter, record_event

        decision = self._join_path
        if self._device_join is not None:
            bump_counter("merge.device.engaged")
            if decision == "resident":
                bump_counter("merge.device.cacheHit")
        elif "error" in self._router:
            bump_counter("merge.device.fallback")  # _device_rung, mode=auto
        data = dict(self._router, decision=decision)
        # how the pairs came about: 'pairs-only' (the resident probe's pairs
        # were the join), 'decode' (a device join mapped onto decoded rows)
        # or 'host'
        data.setdefault("route", "host")
        if "cacheHit" in data:
            # a cache lookup may have hit and then been abandoned (pricing
            # decline, no sentinel room): the emitted flag reports whether
            # the ENGAGED join actually used the cache
            data["cacheHit"] = decision == "resident"
        record_event(
            "delta.merge.router", data,
            path=self.delta_log.data_path,
        )
        audit = self._emit_audit(decision)
        # workload journal: the routed decision + audit verdict persist so
        # the advisor can trend the key-cache hit trajectory across
        # processes (buffered; inert under blackout / journal disabled)
        from delta_tpu.obs import journal as journal_mod

        journal_mod.record_dml(
            self.delta_log.log_path, "merge", decision=decision,
            router={k: v for k, v in data.items() if k != "decision"},
            audit=({"miss": audit.miss, "actualMs": round(audit.actual_ms, 3),
                    "predictedMs": dict(audit.predicted_ms)}
                   if audit is not None else None),
        )

    def _emit_audit(self, decision: str):
        """Record the routed join in the audit ledger: predicted phase
        costs (through ``link.constant``, so calibration feeds back into
        what is being judged) vs the measured ``key_decode + join`` wall
        time — plus the attributable throughput samples the EWMA calibrator
        refits from. Empty joins (no candidates / empty source) have no
        measured join phase and are not audited. Returns the recorded
        audit (or None) so the journal's dml entry can carry the verdict."""
        if "join_ms" not in self.phase_ms or self._audit_units is None:
            return None
        if not conf.get_bool("delta.tpu.telemetry.enabled", True):
            return None  # blackout: no audit, and no link probe to price one
        from delta_tpu.obs import router_audit
        from delta_tpu.parallel import link

        n, m = self._audit_units
        # the device probe's real workload is the SLAB, not the (possibly
        # row-group-pruned / DV-filtered) decode — audit and calibrate the
        # prediction the router actually made
        n_dev = (self._audit_slab_rows
                 if self._audit_slab_rows is not None else n)
        actual_s = (self.phase_ms.get("key_decode_ms", 0.0)
                    + self.phase_ms["join_ms"]) / 1000.0
        key_decode_s = self.phase_ms.get("key_decode_ms", 0.0) / 1000.0
        join_s = self.phase_ms["join_ms"] / 1000.0
        # host prediction needs only the throughput constants; the device
        # prediction (and its link.profile() probe) is computed ONLY when a
        # device route structurally existed — a devicePath-off deployment
        # never pays the probe just to price a route it cannot take
        predicted_map = {
            "host": ((n + m) * link.constant("HOST_JOIN_S_PER_ROW")
                     + n * link.constant("HOST_KEY_DECODE_S_PER_ROW")),
        }
        # key the device prediction under the route actually taken (or the
        # generic "device" when the host won), so a miss reads as "the
        # rejected ROUTE's prediction beat what ran"
        device_key = "device" if decision == "host" else decision
        if self._audit_eligible:
            # the router may have recorded the estimate it ACTUALLY compared
            # (resident-hit economics, cold price, or the mesh estimator) —
            # a hindsight miss must judge that prediction, not a recomputed
            # one from a different cost model (e.g. a warm-cache decline
            # re-priced as a cold slab build could never read as a miss)
            recorded = self._router.get("deviceEstS")
            if recorded is not None:
                predicted_map[device_key] = float(recorded)
            else:
                try:
                    p = link.profile()
                    predicted_map[device_key] = (
                        link.resident_probe_device_s(n_dev, m, p)
                        if decision == "resident"
                        else link.cold_merge_device_s(n_dev, m, p))
                except Exception:  # noqa: BLE001 — pricing must not fail DML
                    pass
        # throughput samples for the calibrator — only cleanly attributable
        # phases: the host join/decode rates, and the resident probe's
        # EFFECTIVE per-row rate (fixed dispatch floor subtracted; link
        # terms folded in, which self-corrects the same prediction above)
        samples = []
        if decision == "host":
            if join_s > 0 and (n + m) > 0:
                samples.append(("HOST_JOIN_S_PER_ROW", n + m, join_s))
            if key_decode_s > 0 and n > 0:
                samples.append(("HOST_KEY_DECODE_S_PER_ROW", n, key_decode_s))
        elif decision == "resident" and (n_dev + m) > 0:
            eff = join_s + key_decode_s - link.RESIDENT_PROBE_FIXED_S
            if eff > 0:
                samples.append(("RESIDENT_PROBE_S_PER_ROW", n_dev + m, eff))
        return router_audit.record_audit(
            "merge.join", self.delta_log.data_path, decision,
            predicted_map,
            actual_s,
            units={"targetRows": n, "sourceRows": m, "slabRows": n_dev},
            samples=samples, log_path=self.delta_log.log_path,
            phases={k: round(v, 1) for k, v in self.phase_ms.items()},
        )

    def _maybe_build_resident_keys(self) -> None:
        """Post-commit: start the background build of the resident key lane
        recorded by `_launch_resident_probe`, so the NEXT merge into this
        table probes from HBM. Never blocks the committing merge."""
        from delta_tpu.ops.key_cache import key_cache_enabled

        cand = getattr(self, "_resident_candidate", None)
        if cand is None:
            return
        self._resident_candidate = None
        if not key_cache_enabled():
            return
        if str(conf.get("delta.tpu.merge.devicePath.mode", "auto")) == "off":
            return
        sig, key_cols, t_exprs = cand
        log = self.delta_log

        def build():
            try:
                from delta_tpu.ops.key_cache import KeyCache

                snap = log.update()
                min_rows = int(conf.get(
                    "delta.tpu.merge.residentKeys.minRows", 1 << 20))
                est = sum(f.num_logical_records or 0 for f in snap.all_files)
                if est < min_rows:
                    return
                e = KeyCache.instance().get(
                    snap, sig, key_cols, t_exprs, build_if_missing=True)
                if e is not None:
                    e.ensure_resident()
            except Exception:
                pass  # best-effort warmup; the next merge just stays cold

        import threading

        threading.Thread(target=build, daemon=True,
                         name="delta-merge-keys-build").start()

    def _launch_device_join(self, key_tab: pa.Table, src: pa.Table, equi):
        """Evaluate + coerce the join keys and launch the device membership
        probe asynchronously (`ops/join_kernel.py`). Composite integer keys
        pack into one int64 lane (hi<<32 | lo) when both components fit in
        int32. Returns a PendingJoin, or None when the keys aren't device-
        representable (caller falls back to the host hash join) or — in
        ``devicePath.mode=auto`` — when the link cost model says shipping
        the keys costs more than the host hash join (`parallel/link.py`)."""
        import numpy as np

        import jax

        from delta_tpu.ops import join_kernel
        from delta_tpu.parallel.mesh import state_mesh

        def to_np(vals):
            arr = vals.combine_chunks() if isinstance(vals, pa.ChunkedArray) else vals
            valid = ~np.asarray(pc.is_null(arr))
            keys = np.asarray(arr.fill_null(0).cast(pa.int64()))
            return keys, valid

        lanes = []
        for t_e, s_e in equi:
            try:
                t_vals = evaluate(t_e, key_tab)
                s_vals = evaluate(s_e, src)
            except Exception:
                return None
            t_vals, s_vals = _coerce_join_keys(t_vals, s_vals)
            if not (
                pa.types.is_integer(t_vals.type) and pa.types.is_integer(s_vals.type)
            ):
                return None
            lanes.append((to_np(t_vals), to_np(s_vals)))

        if len(lanes) == 1:
            (t_keys, t_ok), (s_keys, s_ok) = lanes[0]
        else:
            i32 = np.iinfo(np.int32)
            for (tk, t_ok_i), (sk, s_ok_i) in lanes:
                if (
                    np.min(tk, where=t_ok_i, initial=0) < i32.min
                    or np.max(tk, where=t_ok_i, initial=0) > i32.max
                    or np.min(sk, where=s_ok_i, initial=0) < i32.min
                    or np.max(sk, where=s_ok_i, initial=0) > i32.max
                ):
                    return None  # component exceeds 32 bits: host join
            (t0, t_ok0), (s0, s_ok0) = lanes[0]
            (t1, t_ok1), (s1, s_ok1) = lanes[1]
            t_keys = (t0 << 32) | (t1 & 0xFFFFFFFF)
            s_keys = (s0 << 32) | (s1 & 0xFFFFFFFF)
            t_ok = t_ok0 & t_ok1
            s_ok = s_ok0 & s_ok1

        budget_s = None
        if str(conf.get("delta.tpu.merge.devicePath.mode", "auto")) == "auto":
            from delta_tpu.parallel import link

            budget_s = (len(t_keys) + len(s_keys)) \
                * link.constant("HOST_JOIN_S_PER_ROW")
        mesh = state_mesh() if len(jax.devices()) > 1 else None
        return join_kernel.inner_join_async(
            t_keys, t_ok, s_keys, s_ok, mesh=mesh, budget_s=budget_s
        )

    def _check_star_coverage(
        self, target_cols: Sequence[str], src_cols: Sequence[str], typ: str,
        metadata,
    ) -> None:
        """Star clauses resolve every target column against the source unless
        schema evolution is on (then the star expands over source columns)."""
        if bool(conf.get("delta.tpu.schema.autoMerge.enabled", False)):
            return
        src_low = {s.lower() for s in src_cols}
        # generated columns are computed, not resolved from the source
        from delta_tpu.schema import generated as generated_mod

        gen = generated_mod.generated_column_names(metadata.schema)
        missing = [
            c for c in target_cols
            if c.lower() not in src_low and c.lower() not in gen
        ]
        if missing:
            raise errors_mod.merge_clause_unresolvable(missing[0], typ, src_cols)

    def _check_multi_match(self, pairs: pa.Table) -> None:
        """Error when a target row matches multiple source rows, unless the
        merge is a single unconditional DELETE (`:351-365`)."""
        single_delete = (
            len(self.matched_clauses) == 1
            and self.matched_clauses[0].kind == "delete"
            and self.matched_clauses[0].condition is None
        )
        if self._device_join is not None:
            if not single_delete and self._device_join.any_multi:
                raise DeltaUnsupportedOperationError(
                    "Cannot perform Merge as multiple source rows matched and "
                    "attempted to modify the same target row in the Delta table "
                    "in possibly conflicting ways."
                )
            return
        if pairs.num_rows == 0:
            return
        if single_delete:
            return
        counts = pairs.group_by(_TID).aggregate([(_TID, "count")])
        if pc.max(counts.column(f"{_TID}_count")).as_py() > 1:
            raise DeltaUnsupportedOperationError(
                "Cannot perform Merge as multiple source rows matched and attempted "
                "to modify the same target row in the Delta table in possibly "
                "conflicting ways."
            )

    # -- clause application ------------------------------------------------

    def _apply_matched(self, pairs: pa.Table, target_cols: List[str], metadata,
                       dv_mode: bool = False):
        """Matched block: rows claimed by update clauses are projected, by
        delete clauses dropped, unclaimed pairs copy the target row.

        ``dv_mode``: unclaimed pairs stay in their files (no copy block);
        the 5th return value is a (file id, physical position) table of the
        claimed rows for deletion-vector marking."""
        if pairs.num_rows == 0 or not self.matched_clauses:
            return None, 0, 0, 0, None, set()
        n = pairs.num_rows
        unclaimed = pa.chunked_array([pa.array([True] * n)])
        out_parts: List[pa.Table] = []
        n_updated = n_deleted = 0
        for clause in self.matched_clauses:
            if clause.condition is None:
                fire = unclaimed
            else:
                cond = self._resolve_in_pairs(clause.condition, pairs)
                fire = pc.and_(unclaimed, boolean_mask(cond, pairs))
            count = pc.sum(fire).as_py() or 0
            if count:
                block = pairs.filter(fire)
                if clause.kind == "update":
                    projected = self._project_update(
                        block, clause, target_cols, metadata
                    )
                    out_parts.append(projected)
                    if self._use_cdf:
                        self._cdf_blocks.append(
                            ("update_preimage", block.select(target_cols))
                        )
                        self._cdf_blocks.append(("update_postimage", projected))
                    n_updated += count
                else:
                    if self._use_cdf:
                        # distinct target rows (a legal multi-match would
                        # otherwise emit duplicate delete rows in the feed)
                        import numpy as np

                        tids = block.column(_TID).to_numpy(zero_copy_only=False)
                        _, first = np.unique(tids, return_index=True)
                        self._cdf_blocks.append((
                            "delete",
                            block.take(pa.array(np.sort(first))).select(target_cols),
                        ))
                    # count distinct target ROWS, not pairs: a single
                    # unconditional DELETE may legally multi-match, and the
                    # reference's numTargetRowsDeleted is rows deleted
                    n_deleted += pc.count_distinct(block.column(_TID)).as_py()
            unclaimed = pc.and_(unclaimed, pc.invert(fire))
        claimed_pairs = pairs.filter(pc.invert(unclaimed))
        # files with at least one FIRED row: only these are rewritten. A file
        # whose matches all fall through every clause condition stays in place
        # untouched — rewriting it would commit a remove+add with
        # dataChange=true and make CDF reconstruct delete+insert change rows
        # for rows that never logically changed.
        fired_fids: set = (
            set(pc.unique(claimed_pairs.column(_FID)).to_pylist())
            if claimed_pairs.num_rows else set()
        )
        claimed_tbl = None
        if dv_mode:
            # claimed rows get marked deleted in-place; unclaimed matched
            # pairs stay live in their files — nothing is copied
            claimed_tbl = claimed_pairs.select([_FID, POSITION_COL])
            n_rest = 0
        else:
            # unclaimed matched pairs: copy target row unchanged — but only
            # out of files actually being rewritten (fired_fids)
            rest = pairs.filter(unclaimed)
            if rest.num_rows:
                if fired_fids:
                    keep = pc.is_in(
                        rest.column(_FID),
                        value_set=pa.array(sorted(fired_fids), pa.int64()),
                    )
                    rest = rest.filter(keep)
                else:
                    rest = rest.slice(0, 0)
            if rest.num_rows:
                out_parts.append(rest.select(target_cols))
            n_rest = rest.num_rows
        out = (
            pa.concat_tables(out_parts, promote_options="permissive")
            if out_parts
            else None
        )
        return out, n_updated, n_deleted, n_rest, claimed_tbl, fired_fids

    def _resolve_in_pairs(self, e: ir.Expression, pairs: pa.Table) -> ir.Expression:
        src_cols = [c[len(_SRC):] for c in pairs.column_names if c.startswith(_SRC)]
        tgt_cols = [
            c for c in pairs.column_names
            if not c.startswith("__") and not c.startswith(_SRC)
        ]
        return self._resolve(e, tgt_cols, src_cols)

    def _project_update(self, block: pa.Table, clause: MergeClause,
                        target_cols: List[str], metadata) -> pa.Table:
        src_cols = [c[len(_SRC):] for c in block.column_names if c.startswith(_SRC)]
        if clause.is_star:
            # updateAll: SET t.c = s.c (star coverage validated statically
            # in _body; with evolution target-only columns are no-ops)
            assignments = {
                c: ir.Column(_SRC + next(s for s in src_cols if s.lower() == c.lower()))
                for c in target_cols
                if any(s.lower() == c.lower() for s in src_cols)
            }
        else:
            assignments = {}
            for col, e in clause.assignments.items():
                name = col.split(".")[-1]  # strip target alias qualifier
                assignments[name] = self._resolve_in_pairs(e, block)
        from delta_tpu.expr.vectorized import arrow_type_for

        declared = {f.name: arrow_type_for(f.data_type)
                    for f in metadata.schema.fields}
        cols = []
        for c in target_cols:
            e = None
            for k, v in assignments.items():
                if k.lower() == c.lower():
                    e = v
                    break
            if e is None:
                cols.append(block.column(c))
            else:
                new = evaluate(e, block)
                # cast to the SCHEMA's declared type — with projection
                # pushdown the assigned target column isn't decoded at all
                cols.append(pc.cast(new, declared[c], safe=False))
        out = pa.table(cols, names=target_cols)
        # recompute generated columns whose referenced base columns were
        # assigned (stale copies fail write-time checks); uses the txn's
        # metadata, the same schema the rest of the merge writes against
        from delta_tpu.schema import generated as generated_mod

        return generated_mod.recompute_stale(out, metadata.schema, list(assignments))

    def _apply_not_matched(self, pairs: pa.Table, src: pa.Table,
                           target_cols: List[str], source_cols: List[str], metadata):
        if not self.not_matched_clauses:
            return None, 0
        if self._device_join is not None:
            # device kernel computed per-source matched flags via the reverse
            # probe + psum (exact: the device path requires no residual)
            unmatched = src.filter(pa.array(~self._device_join.s_matched))
        elif pairs.num_rows:
            matched_sids = pc.unique(pairs.column(_SID))
            unmatched = src.filter(
                pc.invert(pc.is_in(src.column(_SID), value_set=matched_sids))
            )
        else:
            unmatched = src
        if unmatched.num_rows == 0:
            return None, 0
        n = unmatched.num_rows
        unclaimed = pa.chunked_array([pa.array([True] * n)])
        parts: List[pa.Table] = []
        n_inserted = 0
        from delta_tpu.expr.vectorized import arrow_type_for

        for clause in self.not_matched_clauses:
            if clause.condition is None:
                fire = unclaimed
            else:
                cond = self._resolve(clause.condition, [], source_cols)
                fire = pc.and_(unclaimed, boolean_mask(cond, unmatched))
            count = pc.sum(fire).as_py() or 0
            if count:
                block = unmatched.filter(fire)
                if clause.is_star:
                    assignments = {
                        c: ir.Column(_SRC + next(
                            s for s in source_cols if s.lower() == c.lower()
                        ))
                        for c in target_cols
                        if any(s.lower() == c.lower() for s in source_cols)
                    }
                else:
                    assignments = {
                        col.split(".")[-1]: self._resolve(e, [], source_cols)
                        for col, e in clause.assignments.items()
                    }
                from delta_tpu.schema import generated as generated_mod

                gen_cols = generated_mod.generated_column_names(metadata.schema)
                cols, names = [], []
                for f in metadata.schema.fields:
                    e = None
                    for k, v in assignments.items():
                        if k.lower() == f.name.lower():
                            e = v
                            break
                    at = arrow_type_for(f.data_type)
                    if e is None:
                        # unassigned generated columns are computed from the
                        # built row, not nulled (GeneratedColumn.scala:267)
                        if f.name.lower() in gen_cols:
                            continue
                        cols.append(pa.nulls(block.num_rows, at))
                    else:
                        cols.append(pc.cast(evaluate(e, block), at, safe=False))
                    names.append(f.name)
                part = pa.table(cols, names=names)
                part = generated_mod.compute_on_write(part, metadata.schema)
                parts.append(part.select(target_cols))
                n_inserted += count
            unclaimed = pc.and_(unclaimed, pc.invert(fire))
        out = pa.concat_tables(parts, promote_options="permissive") if parts else None
        return out, n_inserted
