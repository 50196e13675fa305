"""File pruning: partition filters + min/max data skipping, device-evaluated.

The reference only prunes on partition values (`PartitionFiltering.scala:27-42`)
— per-column min/max skipping is spec'd (`PROTOCOL.md:441-480`) and stats are
carried on every AddFile, but `filesForScan` never uses them (`stats/` holds
only shells, SURVEY §2.3). We implement the full skipping path: a data
predicate is rewritten into a *can-match* predicate over per-file stats
columns (``min.c`` / ``max.c`` / ``nullCount.c`` / ``numRecords``) and
evaluated either on device (jaxeval over `FileStateArrays`, numeric columns)
or on host (Arrow kernels over `stats_table`, covers strings).

Conservativeness invariant: a file is dropped only when the rewritten
predicate is *definitely False*; NULL (missing stats) keeps the file. Kleene
logic gives this for free: False AND unknown = False (safe to drop — the
False conjunct alone excludes every row), False OR unknown = unknown (kept).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from functools import lru_cache
from typing import List, Optional, Sequence

import numpy as np
import pyarrow.compute as pc

from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64
from delta_tpu.expr import ir
from delta_tpu.expr import partition as partition_expr
from delta_tpu.expr import synthesis
from delta_tpu.protocol.actions import AddFile, Metadata
from delta_tpu.ops import state_export
from delta_tpu.utils.config import conf

__all__ = ["DataSize", "DeltaScan", "skipping_predicate", "ConjunctRewrite",
           "conjunct_rewrites", "prune_files", "files_for_scan"]


@dataclass
class DataSize:
    bytes_compressed: Optional[int] = None
    rows: Optional[int] = None
    files: Optional[int] = None


@dataclass
class DeltaScan:
    """Result of file pruning (shape of `stats/DeltaScan.scala:29-61`)."""

    version: int
    files: List[AddFile]
    total: DataSize
    partition: DataSize
    scanned: DataSize
    partition_filters: List[ir.Expression] = field(default_factory=list)
    data_filters: List[ir.Expression] = field(default_factory=list)


def _min(c: str) -> ir.Expression:
    return ir.Column(f"min.{c}")


def _max(c: str) -> ir.Expression:
    return ir.Column(f"max.{c}")


def _nulls(c: str) -> ir.Expression:
    return ir.Column(f"nullCount.{c}")


_UNKNOWN = ir.Literal(None)

#: Resident-path fired-rewrite attribution isolates a conjunct with an
#: extra host lane pass — observability-only work, bounded to tables where
#: it is noise next to the plan itself; beyond this, scan-level attribution
#: (documented over-attribution) applies.
_ATTRIBUTION_ISOLATE_MAX_FILES = 65_536


#: De Morgan / comparison flips for pushing NOT through (`Not(Lt)` ≡ `Ge`
#: etc.; `Not(Eq)` stays UNKNOWN: excluding on min=max=lit would trust
#: possibly-truncated foreign bounds to be exact). The inequality flips are
#: NOT equivalent over floating columns: a NaN row fails every comparison
#: (Python/IEEE semantics, which this engine's evaluators share), so
#: ``NOT (f < L)`` is TRUE for it while ``f >= L`` is FALSE — and min/max
#: stats ignore NaN, so the flipped rewrite would prune the NaN row's file.
#: They therefore require ``types`` and only fire when every referenced
#: column is non-floating; ``Not(Ne)`` ≡ ``Eq`` is safe either way (both
#: sides are FALSE for a NaN row).
_NOT_FLIP = {ir.Lt: ir.Ge, ir.Le: ir.Gt, ir.Gt: ir.Le, ir.Ge: ir.Lt,
             ir.Ne: ir.Eq}


def _not_flip_safe(c: ir.Expression, types) -> bool:
    if type(c) is ir.Ne:
        return True
    if types is None:
        return False
    from delta_tpu.schema.types import DoubleType, FloatType

    return not any(isinstance(types.get(col.lower()), (FloatType, DoubleType))
                   for col in ir.references(c))


def skipping_predicate(
    e: ir.Expression, partition_cols: frozenset = frozenset(),
    types=None, synthesize: Optional[bool] = None,
) -> ir.Expression:
    """Rewrite a data predicate into a can-match predicate over stats columns.
    Returns ``Literal(None)`` (= keep) for unsupported shapes. Partition
    columns have no stats lanes — references to them rewrite to UNKNOWN
    (they only reach here inside mixed OR branches; pure partition conjuncts
    are routed to partition pruning upstream).

    ``types`` (lowercased column name → schema DataType) arms the
    synthesis fallback (`expr/synthesis`): arithmetic / string / temporal
    shapes the base rules cannot lower rewrite into sound interval or
    monotone-wrap can-match predicates instead of UNKNOWN. With
    ``types=None`` (or ``delta.tpu.read.predicateSynthesis=false``) the
    base behavior is unchanged."""

    def _is_part(col: ir.Expression) -> bool:
        return isinstance(col, ir.Column) and col.name.lower() in partition_cols

    t = type(e)
    if t is ir.And:
        return ir.And(
            skipping_predicate(e.left, partition_cols, types, synthesize),
            skipping_predicate(e.right, partition_cols, types, synthesize),
        )
    if t is ir.Or:
        return ir.Or(
            skipping_predicate(e.left, partition_cols, types, synthesize),
            skipping_predicate(e.right, partition_cols, types, synthesize),
        )
    if t is ir.Not:
        c = e.child
        if isinstance(c, ir.IsNull):
            return skipping_predicate(ir.IsNotNull(c.child), partition_cols, types, synthesize)
        if isinstance(c, ir.IsNotNull):
            return skipping_predicate(ir.IsNull(c.child), partition_cols, types, synthesize)
        if all(col.lower() in partition_cols for col in ir.references(c)):
            return e  # exact per-file partition verdict, negation included
        if isinstance(c, ir.Not):
            return skipping_predicate(c.child, partition_cols, types, synthesize)
        tc = type(c)
        if tc in _NOT_FLIP and _not_flip_safe(c, types):
            # NULL operands agree (both sides yield NULL for a NULL row);
            # the NaN hazard is gated by _not_flip_safe
            return skipping_predicate(
                _NOT_FLIP[tc](c.left, c.right), partition_cols, types, synthesize)
        if tc is ir.And:  # De Morgan: each side rewrites conservatively
            return skipping_predicate(
                ir.Or(ir.Not(c.left), ir.Not(c.right)), partition_cols, types, synthesize)
        if tc is ir.Or:
            return skipping_predicate(
                ir.And(ir.Not(c.left), ir.Not(c.right)), partition_cols, types, synthesize)
        return _synth_fallback(e, partition_cols, types, synthesize)
    if any(_is_part(c) for c in getattr(e, "children", ())):
        # a partition column's value is constant per file: keep the predicate
        # as-is and evaluate it exactly against the bound partition value —
        # unless it also references data columns (no lane to bind)
        if all(col.lower() in partition_cols for col in ir.references(e)):
            return e
        return _UNKNOWN
    # normalize <col> <op> <lit>
    cmp_map = {ir.Eq: ir.Eq, ir.Lt: ir.Lt, ir.Le: ir.Le, ir.Gt: ir.Gt, ir.Ge: ir.Ge}
    if t in cmp_map:
        l, r = e.left, e.right
        flip = {ir.Lt: ir.Gt, ir.Le: ir.Ge, ir.Gt: ir.Lt, ir.Ge: ir.Le, ir.Eq: ir.Eq}
        if isinstance(l, ir.Literal) and isinstance(r, ir.Column):
            e = flip[t](r, l)  # type: ignore[operator]
            t = type(e)
            l, r = e.left, e.right
        if not (isinstance(l, ir.Column) and isinstance(r, ir.Literal)):
            return _synth_fallback(e, partition_cols, types, synthesize)
        c, lit = l.name, r
        if lit.value is None:
            return ir.Literal(False)  # col <op> NULL matches nothing
        if t is ir.Eq:
            return ir.And(ir.Le(_min(c), lit), ir.Ge(_max(c), lit))
        if t is ir.Lt:
            return ir.Lt(_min(c), lit)
        if t is ir.Le:
            return ir.Le(_min(c), lit)
        if t is ir.Gt:
            return ir.Gt(_max(c), lit)
        if t is ir.Ge:
            return ir.Ge(_max(c), lit)
    if t is ir.In and isinstance(e.value, ir.Column):
        opts = [o for o in e.options if isinstance(o, ir.Literal) and o.value is not None]
        if len(opts) != len(e.options):
            return _UNKNOWN
        out: Optional[ir.Expression] = None
        for o in opts:
            one = skipping_predicate(ir.Eq(e.value, o), partition_cols, types, synthesize)
            out = one if out is None else ir.Or(out, one)
        return out if out is not None else ir.Literal(False)
    if t is ir.IsNull and isinstance(e.child, ir.Column):
        return ir.Gt(_nulls(e.child.name), ir.Literal(0))
    if t is ir.IsNotNull and isinstance(e.child, ir.Column):
        return ir.Lt(_nulls(e.child.name), ir.Column("numRecords"))
    if t is ir.StartsWith and isinstance(e.left, ir.Column) and isinstance(e.right, ir.Literal):
        p = e.right.value
        if isinstance(p, str) and p:
            c = e.left.name
            lower = ir.Ge(_max(c), ir.Literal(p))  # some value >= the prefix
            hi = _prefix_upper_bound(p)
            if hi is None:
                return lower
            # every string with prefix p is strictly < hi
            return ir.And(ir.Lt(_min(c), ir.Literal(hi)), lower)
    return _synth_fallback(e, partition_cols, types, synthesize)


def _synth_fallback(e: ir.Expression, partition_cols: frozenset,
                    types, synthesize: Optional[bool]) -> ir.Expression:
    """Hand an unsupported leaf to the synthesis layer when armed.
    ``synthesize`` is tri-state: ``False`` (the attribution baseline in
    :func:`conjunct_rewrites`) skips it even with types present; ``True``
    forces it past the conf — the journal's DEFERRED fingerprinting uses
    this, having resolved the conf at SCAN time into ``types`` (reading
    the process-global conf on the writer thread would stamp a scan with
    whatever conf window happens to be active at flush time); ``None``
    (callers on the scan path) consults the conf here."""
    if types is None or synthesize is False:
        return _UNKNOWN
    if synthesize is None and not conf.get_bool(
            "delta.tpu.read.predicateSynthesis", True):
        return _UNKNOWN
    return synthesis.synthesize(
        e, partition_cols, types,
        base=lambda x: skipping_predicate(x, partition_cols))


def _prefix_upper_bound(p: str) -> Optional[str]:
    """Smallest string greater than every string with prefix ``p`` (in
    code-point order): bump the last bumpable char. None = unbounded."""
    chars = list(p)
    while chars:
        cp = ord(chars[-1])
        if cp < 0x10FFFF:
            nxt = cp + 1
            if 0xD800 <= nxt <= 0xDFFF:  # skip the surrogate block
                nxt = 0xE000
            chars[-1] = chr(nxt)
            return "".join(chars)
        chars.pop()
    return None


@dataclass
class ConjunctRewrite:
    """One conjunct's skipping rewrite plus its synthesis attribution:
    ``attempted`` means the base rules could not exclude on this shape (so
    synthesis was consulted); ``synthesized`` that synthesis produced a
    rewrite that can; ``family`` is the rewrite family label (arithmetic /
    string / cast / ...)."""

    conjunct: ir.Expression
    rewritten: ir.Expression
    attempted: bool = False
    synthesized: bool = False
    family: Optional[str] = None


def conjunct_rewrites(
    filters: Sequence[ir.Expression],
    partition_cols: frozenset,
    types,
) -> List[ConjunctRewrite]:
    """Per-conjunct skipping rewrites with synthesis attribution. The AND
    of the rewrites equals ``skipping_predicate(and_all(filters))`` (the
    rewrite distributes over conjunctions), so callers can evaluate the
    fused predicate AND still attribute which conjuncts only lower thanks
    to synthesis."""
    out: List[ConjunctRewrite] = []
    for f in filters:
        for c in ir.split_conjuncts(f):
            # the attribution baseline is TYPED but synthesis-free: the NOT
            # comparison pushdown (a base-rule fix, type-gated for the NaN
            # hazard) must not read as "synthesized"
            base_rw = skipping_predicate(c, partition_cols, types,
                                         synthesize=False)
            base_ok = synthesis.can_exclude(base_rw)
            if base_ok or types is None:
                out.append(ConjunctRewrite(c, base_rw))
                continue
            rw = skipping_predicate(c, partition_cols, types)
            ok = synthesis.can_exclude(rw)
            out.append(ConjunctRewrite(
                c, rw, attempted=True, synthesized=ok,
                family=synthesis.classify_family(c) if ok else None))
    return out


def _count_rewrites(rewrites: Sequence[ConjunctRewrite]) -> None:
    """One ``scan.rewrites.{synthesized,unknown}`` event per conjunct the
    base rules couldn't lower — bumped by the tier that actually SERVED the
    prune (resident serve or the generic prune), never both."""
    from delta_tpu.utils.telemetry import bump_counter

    for r in rewrites:
        if r.attempted:
            bump_counter("scan.rewrites.synthesized" if r.synthesized
                         else "scan.rewrites.unknown")


def _record_fired(rewrite: ConjunctRewrite) -> None:
    from delta_tpu.obs import scan_report

    scan_report.record_rewrite_fired(
        rewrite.family or "other",
        synthesis.shape(rewrite.conjunct),
        synthesis.shape(rewrite.rewritten),
    )


def _attribute_fired(
    rewrites: Sequence[ConjunctRewrite],
    excluded: Sequence[AddFile],
    metadata: Metadata,
) -> None:
    """Per-conjunct attribution of a file-tier prune: a synthesized rewrite
    *fired* when it alone excludes at least one of the files the fused
    predicate dropped. Best-effort — attribution must never fail a scan."""
    synths = [r for r in rewrites if r.synthesized]
    if not synths or not excluded:
        return
    from delta_tpu.expr.vectorized import evaluate

    try:
        table = state_export.stats_table(excluded, metadata)
    except Exception:  # noqa: BLE001 — attribution is observability only
        return
    for r in synths:
        try:
            verdict = evaluate(r.rewritten, table)
            hit = pc.any(pc.equal(pc.cast(verdict, "bool"), False)).as_py()
        except Exception:  # noqa: BLE001
            hit = False
        if hit:
            _record_fired(r)


def _prune_host(files: Sequence[AddFile], metadata: Metadata, pred: ir.Expression) -> np.ndarray:
    from delta_tpu.expr.vectorized import evaluate

    table = state_export.stats_table(files, metadata)
    try:
        verdict = evaluate(pred, table)
        # keep unless definitely False
        keep = pc.fill_null(pc.cast(verdict, "bool"), True)
    except Exception:  # noqa: BLE001 — a stats/type surprise (e.g. foreign
        # stats that contradict the declared schema under a synthesized
        # rewrite) must degrade to keep-everything, never fail the scan
        return np.ones(len(files), bool)
    return np.asarray(keep)


class _StatsLaneTypes:
    """`compile_residual`'s type view of `FileStateArrays.device_env`: every
    ``min.c`` / ``max.c`` lane is a float64 lane held as int64 order keys,
    every other lane (counts, sizes, partition codes) plain int64."""

    @staticmethod
    def get(name: str):
        from delta_tpu.schema.types import DoubleType, LongType

        return DoubleType() if name.startswith(("min.", "max.")) else LongType()


@lru_cache(maxsize=256)
def _compiled_skipping(pred: ir.Expression):
    """jit-compiled skipping predicate, cached per expression so repeat scans
    reuse the executable (env shapes are the jit cache key). A TPU's float64
    is not IEEE, so the min/max bounds compare as exact int64 order keys —
    the residual path's lowering (`jaxeval.compile_residual`); shapes that
    need float arithmetic over the bounds (multi-column synthesis
    candidates) raise ``NotDeviceCompilable`` and prune on the host."""
    ensure_compilation_cache()
    import jax

    from delta_tpu.expr.jaxeval import compile_expr, compile_residual

    return jax.jit(compile_expr(compile_residual(pred, _StatsLaneTypes).expr))


def _prune_device(arrays: state_export.FileStateArrays, pred: ir.Expression) -> Optional[np.ndarray]:
    from delta_tpu.expr.jaxeval import NotDeviceCompilable

    try:
        with enable_x64():
            col = _compiled_skipping(pred)(arrays.device_env())
    except NotDeviceCompilable:
        return None  # designed decline: no exact device form / unbound lane
    except Exception as e:  # noqa: BLE001 — host rung (_prune_host) takes
        # over, counted and with the exception on the delta.scan.prune span
        from delta_tpu.utils import telemetry

        telemetry.bump_counter("scan.prune.deviceFallback")
        telemetry.add_span_data(deviceError=telemetry.exc_text(e))
        return None
    keep = np.asarray(col.values, bool) | ~np.asarray(col.valid, bool)  # NULL keeps
    if keep.ndim == 0:
        keep = np.full(arrays.num_files, bool(keep))
    return keep


def prune_files(
    files: Sequence[AddFile],
    metadata: Metadata,
    data_filters: Sequence[ir.Expression],
    prefer_device: bool = True,
) -> List[AddFile]:
    """Apply min/max skipping; returns the files that may contain matches."""
    if not files or not data_filters:
        return list(files)
    pcols = frozenset(c.lower() for c in metadata.partition_columns)
    rewrites = conjunct_rewrites(list(data_filters), pcols,
                                 synthesis.schema_types(metadata))
    _count_rewrites(rewrites)
    pred = ir.and_all([r.rewritten for r in rewrites])
    keep: Optional[np.ndarray] = None
    # The device path pays a dispatch + transfer per scan; below a few
    # thousand files the vectorized host evaluator finishes before a single
    # device round-trip even on PCIe-attached chips, so route small file
    # lists to the host (delta.tpu.device.pruning.minFiles to tune).
    min_files = int(conf.get("delta.tpu.device.pruning.minFiles", 4096))
    if prefer_device and len(files) >= min_files:
        arrays = state_export.files_to_arrays(files, metadata)
        keep = _prune_device(arrays, pred)
    from delta_tpu.utils.telemetry import add_span_data

    # which tier served, on the enclosing delta.scan.prune span
    add_span_data(tier="host" if keep is None else "device")
    if keep is None:
        keep = _prune_host(files, metadata, pred)
    kept = [f for f, k in zip(files, keep) if k]
    if len(kept) < len(files):
        _attribute_fired(rewrites, [f for f, k in zip(files, keep) if not k],
                         metadata)
    return kept


def _resident_scan(
    snapshot,
    partition_filters: Sequence[ir.Expression],
    data_filters: Sequence[ir.Expression],
) -> Optional[DeltaScan]:
    """Serve a scan from the HBM/mirror-resident state cache
    (`ops/state_cache`, the reference's `StateCache` role): only the few
    surviving files materialize as dataclasses — ``all_files`` (every
    AddFile as a Python object) is never built. Partition predicates lower
    to dictionary-code ranges on the same lanes (the reference's primary
    pruning path, `PartitionFiltering.scala:27-43`). Only taken when the
    range lowering is EXACT (no strict comparison was relaxed), so the
    result matches the evaluator file-for-file. None → normal path."""
    if not conf.get_bool("delta.tpu.stateCache.serveScans", True):
        return None
    if getattr(snapshot, "delta_log", None) is None:
        return None  # synthetic snapshots (tests/tools) have no log handle
    import numpy as np

    from delta_tpu.ops.state_cache import DeviceStateCache, extract_range_union
    from delta_tpu.utils.telemetry import bump_counter

    entry = DeviceStateCache.instance().get(snapshot)
    if entry is None:
        bump_counter("stateCache.scan.fallback.noentry")
        return None
    pcols = frozenset(c.lower() for c in snapshot.metadata.partition_columns)
    rewrites = conjunct_rewrites(
        list(partition_filters) + list(data_filters), pcols,
        synthesis.schema_types(snapshot.metadata))
    pred = ir.and_all([r.rewritten for r in rewrites])
    terms = extract_range_union(pred, entry.columns, entry.part_info,
                                str_lanes=entry.str_lanes)
    if not terms or not all(t.exact for t in terms):
        bump_counter("stateCache.scan.fallback.lowering")
        return None
    n_main = len(terms)
    if partition_filters and data_filters:
        # partition-only leg: same lanes, stats bounds dropped — one batch,
        # one dispatch; feeds the DataSize the scan reports for the
        # partition-pruning stage. (Pure-partition queries skip it: the
        # main leg IS the partition leg.)
        ppred = skipping_predicate(ir.and_all(list(partition_filters)), pcols)
        pterms = extract_range_union(ppred, entry.columns, entry.part_info,
                                     str_lanes=entry.str_lanes)
        if not pterms or not all(t.exact for t in pterms):
            bump_counter("stateCache.scan.fallback.lowering")
            return None
        terms = terms + pterms
    plans = entry.plan_ranges(terms, k=max(entry.num_rows, 1),
                              expected_version=snapshot.version)
    if plans is None:
        bump_counter("stateCache.scan.fallback.version")
        return None
    bump_counter("stateCache.scan.resident")
    _count_rewrites(rewrites)  # this tier serves: it owns the count

    def _union(chunk):
        if len(chunk) == 1:
            return chunk[0].rows
        return np.unique(np.concatenate([p.rows for p in chunk]))

    rows = _union(plans[:n_main])
    paths = [entry.paths[i] for i in rows]
    kept = snapshot.files_for_paths(paths)
    alive = entry.h_alive[: entry.num_rows]
    sizes = entry.h_size[: entry.num_rows]
    total_bytes = int(sizes[alive].sum())
    n_alive = int(alive.sum())
    if len(rows) < n_alive:
        # fired-rewrite attribution on the resident path: isolate each
        # synthesized conjunct on the host mirrors when its rewrite lowers
        # to a single range term; multi-term/unlowerable rewrites — and
        # large tables, where an extra per-conjunct host lane pass would
        # rival the resident plan this path exists to keep O(ms) —
        # attribute at scan level (the scan did prune and the conjunct is
        # part of the conjunction that pruned it)
        isolate = n_alive <= _ATTRIBUTION_ISOLATE_MAX_FILES
        for r in (x for x in rewrites if x.synthesized):
            fired = True
            if isolate:
                terms_i = extract_range_union(r.rewritten, entry.columns,
                                              entry.part_info,
                                              str_lanes=entry.str_lanes)
                if terms_i is not None and len(terms_i) == 1:
                    plans_i = entry.plan_ranges(
                        terms_i, k=1, use_device=False,
                        expected_version=snapshot.version)
                    if plans_i is not None:
                        fired = plans_i[0].count < n_alive
            if fired:
                _record_fired(r)
    total = DataSize(bytes_compressed=total_bytes, files=n_alive)
    if partition_filters:
        prows = _union(plans[n_main:]) if data_filters else rows
        partition = DataSize(
            bytes_compressed=int(sizes[prows].sum()), files=len(prows))
    else:
        partition = total  # unpartitioned: nothing pruned by partition
    return DeltaScan(
        version=snapshot.version,
        files=kept,
        total=total,
        partition=partition,
        scanned=DataSize(
            bytes_compressed=sum(f.size or 0 for f in kept),
            files=len(kept),
            rows=sum(f.num_logical_records or 0 for f in kept) or None,
        ),
        partition_filters=list(partition_filters),
        data_filters=list(data_filters),
    )


def files_for_scan(
    snapshot,
    filters: Sequence[ir.Expression] = (),
    keep_num_indexed_cols: Optional[int] = None,
) -> DeltaScan:
    """Partition-prune then stats-prune the snapshot's files for a query.

    The partition step matches `PartitionFiltering.scala:27-42`; the stats
    step is the skipping path the reference leaves unwired. Unpartitioned
    tables with an exactly-lowerable predicate serve from the resident
    state cache instead of materializing every AddFile."""
    from delta_tpu.utils.telemetry import observe, record_operation

    with record_operation("delta.scan.planning") as pev:
        scan = _files_for_scan_impl(snapshot, filters, keep_num_indexed_cols)
        pev.data.update(
            filesTotal=scan.total.files, filesAfterPartition=scan.partition.files,
            filesScanned=scan.scanned.files,
        )
    # unmeasured (telemetry blackout) or a bare snapshot shim (tests prune
    # synthetic file lists with no DeltaLog behind them): skip the series
    from delta_tpu.obs import scan_report

    scan_report.record_phase("planning", pev)
    delta_log = getattr(snapshot, "delta_log", None)
    if pev.duration_us is not None and delta_log is not None:
        from delta_tpu.obs.fleet import table_label

        # hashed table label ONLY — a new series has no back-compat pull
        # toward the raw-path label, and bounded label bytes is the whole
        # point of the hash (the fleet registry resolves it back)
        observe("delta.scan.planning.duration_ms", pev.duration_us / 1000.0,
                table=table_label(delta_log.data_path))
    return scan


def _files_for_scan_impl(
    snapshot,
    filters: Sequence[ir.Expression],
    keep_num_indexed_cols: Optional[int],
) -> DeltaScan:
    metadata = snapshot.metadata
    # read-side char padding (ApplyCharTypePadding): literals compared to
    # char(n) columns pad to width, so they match the stored padded form
    from delta_tpu.schema.char_varchar import pad_char_literals

    filters = [pad_char_literals(f, metadata) for f in filters]
    part_schema = metadata.partition_schema
    part_cols = metadata.partition_columns
    partition_filters: List[ir.Expression] = []
    data_filters: List[ir.Expression] = []
    for f in filters:
        for conj in ir.split_conjuncts(f):
            if partition_expr.is_partition_predicate(conj, part_cols):
                partition_filters.append(conj)
            else:
                data_filters.append(conj)

    if data_filters or partition_filters:
        from delta_tpu.utils.telemetry import record_operation

        with record_operation("delta.scan.stateCache") as rev:
            fast = _resident_scan(snapshot, partition_filters, data_filters)
            rev.data["served"] = fast is not None
        if fast is not None:
            return fast

    all_files = snapshot.all_files
    total = DataSize(
        bytes_compressed=sum(f.size or 0 for f in all_files), files=len(all_files)
    )
    if partition_filters:
        pred = ir.and_all(partition_filters)
        # strict: a NULL partition verdict is constant for the whole file, so
        # no row in it can satisfy the WHERE clause — prune it
        after_part = [
            f for f in all_files if partition_expr.matches(pred, f, part_schema)
        ]
    else:
        after_part = list(all_files)
    partition = DataSize(
        bytes_compressed=sum(f.size or 0 for f in after_part), files=len(after_part)
    )

    from delta_tpu.utils.telemetry import record_operation as _rec_op

    with _rec_op("delta.scan.prune", {"candidates": len(after_part)}):
        kept = prune_files(after_part, metadata, data_filters)
    scanned = DataSize(
        bytes_compressed=sum(f.size or 0 for f in kept),
        files=len(kept),
        rows=sum(f.num_logical_records or 0 for f in kept) or None,
    )
    return DeltaScan(
        version=snapshot.version,
        files=kept,
        total=total,
        partition=partition,
        scanned=scanned,
        partition_filters=partition_filters,
        data_filters=data_filters,
    )
