"""Row-group data skipping: footer-stats pushdown + a bounded footer cache.

Second pruning tier inside the Parquet read path. File-level pruning
(`ops/pruning.files_for_scan`) decides WHICH files a query touches; this
module decides which *row groups inside each surviving file* must actually
decode, using the per-row-group min/max/null-count statistics every Parquet
footer already carries. The reference gets this for free from parquet-mr's
row-group/page filters (`ParquetFileFormat` pushdown); here the same
predicate IR (`expr/ir.py`) is rewritten once by
`ops.pruning.skipping_predicate` and evaluated row-group-at-a-time against a
stats environment — so both tiers share one conservativeness story:

* a row group is dropped only when the rewritten predicate is *definitely
  False*; NULL (missing/unsafe stats) keeps it (Kleene semantics);
* NaN float bounds (legacy writers) invalidate that column's bounds;
* binary bounds are never used (truncation is undetectable);
* columns missing from the file (schema evolution) resolve to NULL ⇒ keep;
* partition-column references (mixed OR branches) bind to the file's typed
  partition values, exactly like the file tier's ``stats_table``.

The footer cache (:class:`FooterCache`) is a bounded LRU keyed by
``abs_path`` and validated by ``(size, mtime_ns)`` so hot-table queries stop
re-parsing footers per open — a rewritten file (same path, new bytes) drops
its stale entry on the next lookup. Capacity:
``delta.tpu.read.footerCacheEntries`` (0 disables caching entirely).

:func:`stats_from_footer` derives protocol AddFile stats
(minValues/maxValues/nullCount/numRecords) from the same footer statistics —
the shared writer (`exec/write.write_files`) hands it the footer its encoder
just made, and CONVERT TO DELTA the footer it read, so neither decodes or
walks a whole data file just to compute stats; both fall back to the rows
themselves when the footer is absent or cannot give the same answer.
"""
from __future__ import annotations

import math
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field as dataclass_field
from typing import Any, Dict, FrozenSet, List, Optional, Sequence

import numpy as np

from delta_tpu.expr import ir
from delta_tpu.utils.config import conf

__all__ = [
    "FooterCache",
    "read_footer",
    "footer_cache_info",
    "RowGroupPlan",
    "plan_row_groups",
    "row_group_offsets",
    "row_groups_for_positions",
    "stats_from_footer",
]


# ---------------------------------------------------------------------------
# Footer cache
# ---------------------------------------------------------------------------


class FooterCache:
    """Bounded LRU of parsed Parquet footers (``pq.FileMetaData``).

    Entries are keyed by absolute path and validated against the file's
    current ``(size, mtime_ns)`` on every lookup — an in-place rewrite
    invalidates the stale footer without any explicit purge. A parsed
    footer is immutable in Arrow, so one cached object serves concurrent
    readers; the cached metadata also feeds ``pq.ParquetFile(...,
    metadata=...)`` so a planned file opens without re-parsing its footer.
    """

    _instance: Optional["FooterCache"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._lock = threading.Lock()
        # abs_path -> ((size, mtime_ns), FileMetaData)
        self._entries: "OrderedDict[str, tuple]" = OrderedDict()

    @classmethod
    def instance(cls) -> "FooterCache":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = FooterCache()
            return cls._instance

    @staticmethod
    def capacity() -> int:
        return int(conf.get("delta.tpu.read.footerCacheEntries", 1024))

    def get(self, abs_path: str):
        """The file's parsed footer; cached when the cache is enabled."""
        return self.lookup(abs_path)[0]

    def lookup(self, abs_path: str):
        """``(the file's parsed footer, whether the cache held it)``."""
        import pyarrow.parquet as pq

        from delta_tpu.utils.telemetry import bump_counter

        cap = self.capacity()
        if cap <= 0:
            return pq.read_metadata(abs_path), False
        st = os.stat(abs_path)
        key = (st.st_size, st.st_mtime_ns)
        with self._lock:
            hit = self._entries.get(abs_path)
            if hit is not None and hit[0] == key:
                self._entries.move_to_end(abs_path)
                bump_counter("footerCache.hits")
                return hit[1], True
        meta = pq.read_metadata(abs_path)
        bump_counter("footerCache.misses")
        with self._lock:
            self._entries[abs_path] = (key, meta)
            self._entries.move_to_end(abs_path)
            while len(self._entries) > cap:
                self._entries.popitem(last=False)
                bump_counter("footerCache.evictions")
        return meta, False

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)


def read_footer(abs_path: str):
    return FooterCache.instance().get(abs_path)


def footer_cache_info() -> dict:
    """Residency snapshot of the process footer cache — served by the obs
    endpoint's ``/healthz`` next to the hit/miss counters, so an operator
    can tell a cold cache from a disabled one."""
    cache = FooterCache.instance()
    return {"entries": len(cache), "capacity": cache.capacity()}


# ---------------------------------------------------------------------------
# Pushdown planner
# ---------------------------------------------------------------------------


class _StatsEnv(dict):
    """Row environment for the rewritten skipping predicate: lookups are
    case-insensitive and *missing stats resolve to NULL* instead of raising
    — NULL keeps the row group (the conservativeness invariant), which is
    exactly what absent/evolved columns must do."""

    def __contains__(self, key: object) -> bool:  # Column.eval probes first
        return True

    def __getitem__(self, key):
        if isinstance(key, str):
            return super().get(key.lower())
        return super().get(key)


def _column_index(meta) -> Dict[str, int]:
    """lowercased top-level leaf name -> column-chunk index. Nested leaves
    (``a.b``, list/map paths) are skipped — only flat columns carry stats
    lanes, matching the file tier."""
    out: Dict[str, int] = {}
    if meta.num_row_groups == 0:
        return out
    rg0 = meta.row_group(0)
    for j in range(rg0.num_columns):
        p = rg0.column(j).path_in_schema
        if "." in p:
            continue
        out[p.lower()] = j
    return out


def _float_leaves(meta, col_index: Dict[str, int]) -> FrozenSet[str]:
    out = set()
    for name, j in col_index.items():
        if meta.schema.column(j).physical_type in ("FLOAT", "DOUBLE"):
            out.add(name)
    return frozenset(out)


def _safe_bounds(mn: Any, mx: Any, is_float: bool):
    """Drop bound pairs the planner must not trust: binary (possibly
    truncated) and NaN floats (legacy writers put NaN in min/max, making
    the pair meaningless)."""
    if isinstance(mn, bytes) or isinstance(mx, bytes):
        return None, None
    if is_float and (
        (isinstance(mn, float) and math.isnan(mn))
        or (isinstance(mx, float) and math.isnan(mx))
    ):
        return None, None
    return mn, mx


def _rg_env(meta, i: int, col_index: Dict[str, int],
            float_leaves: FrozenSet[str],
            part_row: Optional[Dict[str, Any]]) -> _StatsEnv:
    rg = meta.row_group(i)
    env = _StatsEnv()
    env["numrecords"] = rg.num_rows
    for name, j in col_index.items():
        try:
            st = rg.column(j).statistics
        except Exception:
            st = None
        if st is None:
            continue
        try:
            if st.has_null_count:
                env[f"nullcount.{name}"] = st.null_count
            if st.has_min_max:
                mn, mx = _safe_bounds(st.min, st.max, name in float_leaves)
                if mn is not None:
                    env[f"min.{name}"] = mn
                if mx is not None:
                    env[f"max.{name}"] = mx
        except Exception:
            continue  # undecodable stats value: leave lanes NULL (keep)
    if part_row:
        for k, v in part_row.items():
            env[k.lower()] = v
    return env


@dataclass
class RowGroupPlan:
    """Surviving row groups of one file. ``skipped_bytes`` is the
    uncompressed size of the pruned groups (the decode work avoided);
    ``fired`` lists the synthesized rewrites that individually excluded at
    least one pruned group (family + conjunct/rewrite shape fingerprints)
    for ``ScanReport.rewritesFired`` attribution."""

    keep: List[int]
    total: int
    skipped_bytes: int = 0
    fired: List[Dict[str, str]] = dataclass_field(default_factory=list)


def plan_row_groups(
    meta,
    predicate: ir.Expression,
    part_row: Optional[Dict[str, Any]] = None,
    partition_cols: FrozenSet[str] = frozenset(),
    types: Optional[Dict[str, Any]] = None,
    rewrites: Optional[List] = None,
) -> RowGroupPlan:
    """Evaluate ``predicate`` against each row group's footer statistics;
    a group survives unless the rewritten can-match predicate is definitely
    False. Single-group files short-circuit: the file tier already ruled.
    ``types`` (lowercased column name → schema DataType) arms the predicate
    synthesis fallback for arithmetic/string/temporal shapes — the SAME
    shared rewrite the file tier evaluates, so both tiers keep one
    conservativeness story. ``rewrites`` short-circuits the rewrite: a
    scan-constant ``conjunct_rewrites(...)`` list computed ONCE by the
    caller (the per-file decode loop must not re-derive it per footer)."""
    from delta_tpu.expr import synthesis
    from delta_tpu.ops.pruning import conjunct_rewrites, skipping_predicate

    n = meta.num_row_groups
    all_groups = list(range(n))
    if n <= 1:
        return RowGroupPlan(all_groups, n)
    if rewrites is None and types is not None:
        rewrites = conjunct_rewrites([predicate], partition_cols, types)
    if rewrites is not None:
        rewritten = ir.and_all([r.rewritten for r in rewrites])
    else:
        rewritten = skipping_predicate(predicate, partition_cols)
    if isinstance(rewritten, ir.Literal) and rewritten.value is None:
        return RowGroupPlan(all_groups, n)  # nothing lowerable: keep all
    col_index = _column_index(meta)
    float_leaves = _float_leaves(meta, col_index)
    keep: List[int] = []
    skipped_bytes = 0
    pruned_envs: List[_StatsEnv] = []
    for i in all_groups:
        env = _rg_env(meta, i, col_index, float_leaves, part_row)
        try:
            verdict = rewritten.eval(env)
        except Exception:
            verdict = None  # uncomparable stats value vs literal: keep
        if verdict is False:
            skipped_bytes += meta.row_group(i).total_byte_size
            pruned_envs.append(env)
        else:
            keep.append(i)
    fired: List[Dict[str, str]] = []
    if pruned_envs and rewrites is not None:
        for r in rewrites:
            if not r.synthesized:
                continue
            if any(_safe_false(r.rewritten, env) for env in pruned_envs):
                fired.append({
                    "family": r.family or "other",
                    "conjunct": synthesis.shape(r.conjunct),
                    "rewrite": synthesis.shape(r.rewritten),
                })
    return RowGroupPlan(keep, n, skipped_bytes, fired)


def _safe_false(expr: ir.Expression, env: _StatsEnv) -> bool:
    try:
        return expr.eval(env) is False
    except Exception:
        return False


def row_group_offsets(meta) -> np.ndarray:
    """Physical row offset of each row group; length ``num_row_groups + 1``
    (the last entry is the file's row count). Positions emitted for pruned
    reads are offset by these so deletion-vector DML keeps writing TRUE
    file positions."""
    counts = np.asarray(
        [meta.row_group(i).num_rows for i in range(meta.num_row_groups)],
        dtype=np.int64,
    )
    off = np.zeros(len(counts) + 1, dtype=np.int64)
    np.cumsum(counts, out=off[1:])
    return off


def row_groups_for_positions(meta, positions) -> FrozenSet[int]:
    """Row groups containing any of the given PHYSICAL row positions — the
    position-targeted selection the CDF deletion-vector diff uses (it knows
    exactly which rows changed before reading a single data page)."""
    off = row_group_offsets(meta)
    pos = np.asarray(positions, dtype=np.int64)
    pos = pos[(pos >= 0) & (pos < off[-1])]
    if pos.size == 0:
        return frozenset()
    return frozenset(int(i) for i in np.unique(np.searchsorted(off, pos, side="right") - 1))


# ---------------------------------------------------------------------------
# Footer-derived AddFile stats (the shared writer, CONVERT TO DELTA)
# ---------------------------------------------------------------------------


def stats_from_footer(meta, num_indexed_cols: int = 32) -> Optional[Dict[str, Any]]:
    """Protocol stats (numRecords/minValues/maxValues/nullCount) derived
    from footer row-group statistics, or ``None`` when the footer cannot
    stand in for a pass over the rows:

    * an indexed column that is not a flat leaf (struct, list, map);
    * any indexed column chunk without a statistics block (stats-disabled
      writer, or bounds omitted for oversized binary values) while the
      chunk holds non-null values;
    * NaN float bounds (legacy writers — bounds untrustworthy);
    * a float bound of zero: writers store a zero minimum as -0.0 and a zero
      maximum as +0.0 whatever the rows held, so the sign is not the data's.

    It serves the write path (`exec/write.write_files`, on the footer the
    encoder hands back) and CONVERT TO DELTA (on a footer read from disk).
    Bounds the decode path would not emit either (binary, decimal,
    non-finite floats) are simply omitted — that matches
    ``exec.parquet.collect_stats`` encoding rules, and what this returns is
    what ``collect_stats`` returns for the same rows, key for key and in the
    same order, so the two JSON strings are equal byte for byte."""
    import pyarrow as pa

    from delta_tpu.exec.parquet import has_stat_bounds, json_stat_value

    try:
        arrow_schema = meta.schema.to_arrow_schema()
    except Exception:
        return None
    col_index = _column_index(meta)
    row_groups = [meta.row_group(i) for i in range(meta.num_row_groups)]
    names = arrow_schema.names[: num_indexed_cols if num_indexed_cols >= 0 else None]
    mins: Dict[str, Any] = {}
    maxs: Dict[str, Any] = {}
    nulls: Dict[str, Any] = {}
    for name in names:
        j = col_index.get(name.lower())
        if j is None:
            return None  # nested/unmapped: the footer can't cover this column
        t = arrow_schema.field(name).type
        is_float = pa.types.is_floating(t)
        total_null = 0
        col_mins: List[Any] = []
        col_maxs: List[Any] = []
        bounds_incomplete = False
        for rg in row_groups:
            try:
                st = rg.column(j).statistics
            except Exception:
                st = None
            if st is None or not st.has_null_count:
                return None  # can't even derive nullCount: decode fallback
            total_null += st.null_count
            if st.has_min_max:
                try:
                    mn, mx = st.min, st.max
                except Exception:
                    return None
                if is_float and (
                    (isinstance(mn, float) and math.isnan(mn))
                    or (isinstance(mx, float) and math.isnan(mx))
                ):
                    return None  # NaN-polluted bounds: decode fallback
                col_mins.append(mn)
                col_maxs.append(mx)
            elif st.null_count != rg.num_rows:
                # values exist but the writer withheld bounds (e.g. long
                # binary): only a decode can produce them
                bounds_incomplete = True
        nulls[name] = total_null
        if not has_stat_bounds(t) or total_null == meta.num_rows:
            continue  # same columns collect_stats skips
        if bounds_incomplete or not col_mins:
            return None
        try:
            mn_v = min(col_mins)
            mx_v = max(col_maxs)
        except TypeError:
            return None
        if is_float and (mn_v == 0 or mx_v == 0):
            return None  # the zero's sign is the writer's, not the rows'
        mn_j = json_stat_value(mn_v)
        mx_j = json_stat_value(mx_v, round_up=True)
        if mn_j is not None:
            mins[name] = mn_j
        if mx_j is not None:
            maxs[name] = mx_j
    return {
        "numRecords": meta.num_rows,
        "minValues": mins,
        "maxValues": maxs,
        "nullCount": nulls,
    }
