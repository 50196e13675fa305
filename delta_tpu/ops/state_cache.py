"""Device-resident snapshot state: table metadata cached in HBM.

The reference caches reconstructed state as a Spark-memory Dataset
(`util/StateCache.scala:34-110` backing `Snapshot.scala:88-111`), so repeat
queries replay nothing. The TPU-native equivalent keeps the *scan-planning
lanes* of the reconciled state — per-file min/max/nullCount stats, sizes,
aliveness — resident in HBM, keyed by table, and updates them incrementally
as the log tails forward: each new commit appends a handful of rows
device-side (one small upload + one scatter/slice kernel), so steady-state
queries pay **zero bulk upload**.

Why this is the piece that makes the chip win: on any host↔device link,
re-uploading O(files) state per query prices the device out of
interactive planning; from residency, a *batch* of N predicates over F files
and C stat columns is one dispatch reading N·F·C lanes from HBM (~800 GB/s)
against a host evaluator bound by DRAM (~10 GB/s single-core), and one
small packed block-bitmap download finished exactly on the host mirrors
(coarse-fine; see ``_plan_device``).

Precision: stats lanes are stored as float32 with **conservative rounding**
— min lanes round toward -inf, max lanes toward +inf, and query bounds round
outward the same way (`_f32_down`/`_f32_up`) — so a float32 verdict can only
*keep* extra files, never drop a matching one. NaN = missing stat = keep.
The skipping rewrite only ever tests ``min.c`` against upper bounds and
``max.c`` against lower bounds (`ops/pruning.skipping_predicate`), which is
what makes one rounding direction per lane sufficient.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from delta_tpu.expr import ir
from delta_tpu.parallel import link
from delta_tpu.utils.config import conf
from delta_tpu.utils.jaxcache import ensure_compilation_cache

__all__ = [
    "ResidentState", "DeviceStateCache", "PlanResult", "extract_ranges",
    "RangeSet",
]


def _f32_down(x: np.ndarray) -> np.ndarray:
    """float64 → float32 rounded toward -inf (result <= x). NaN passes."""
    with np.errstate(invalid="ignore", over="ignore"):
        f = x.astype(np.float32)
        bump = f.astype(np.float64) > x
    if bump.any():
        f = f.copy()
        f[bump] = np.nextafter(f[bump], np.float32(-np.inf))
    return f


def _f32_up(x: np.ndarray) -> np.ndarray:
    """float64 → float32 rounded toward +inf (result >= x). NaN passes."""
    with np.errstate(invalid="ignore", over="ignore"):
        f = x.astype(np.float32)
        bump = f.astype(np.float64) < x
    if bump.any():
        f = f.copy()
        f[bump] = np.nextafter(f[bump], np.float32(np.inf))
    return f


def _next_pow2(n: int, floor: int = 1024) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


# -- range extraction from skipping predicates ------------------------------


@dataclass
class RangeSet:
    """One query as per-column bounds: keep file iff for every column c,
    ``max.c >= lo[c] AND min.c <= hi[c]`` (NaN bound = unconstrained).
    ``verdict`` short-circuits structural cases: 'empty' (matches nothing),
    'all' (prunes nothing)."""

    lo: np.ndarray  # float64, len C, NaN = -inf
    hi: np.ndarray  # float64, len C, NaN = +inf
    verdict: Optional[str] = None  # None | 'empty' | 'all'
    # True when the lowering lost nothing: no strict comparison was relaxed
    # to non-strict, so the range verdict EQUALS the exact evaluator's
    exact: bool = True


def _part_lane_rows(codes: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Partition lane rows from int32 codes: min=max=code; null (-1) becomes
    the inverted range (+inf, -inf) so every bounded query prunes it exactly
    (NaN would mean 'missing stat: keep' — the wrong semantics for a KNOWN
    null partition value)."""
    f = codes.astype(np.float64)
    lo = np.where(codes >= 0, f, np.inf)
    hi = np.where(codes >= 0, f, -np.inf)
    return lo, hi


@dataclass
class PartLane:
    """One partition column's dictionary lane: codes are ranks in VALUE
    order at build time (typed order for numeric/temporal columns, code-
    point order for strings), so value ranges lower to code ranges. A tail
    extension that arrives out of order appends its code at the end and
    clears ``sorted`` — equality lowering survives, range lowering stops
    until the entry rebuilds."""

    values: List[str]  # code -> raw partition string
    parsed: Optional[np.ndarray]  # typed sort keys (float64) or None (lex)
    code_of: Dict[str, int]
    sorted: bool = True
    dt: object = None  # DataType used to parse (set iff parsed is not None)

    def eq_code(self, lit) -> Optional[int]:
        """Code whose value equals the literal; -1 = no file has it; None =
        the literal isn't comparable against this lane."""
        if self.parsed is not None:
            if isinstance(lit, bool) or not isinstance(lit, (int, float)):
                return None
            v = float(lit)
            if self.sorted:
                i = int(np.searchsorted(self.parsed, v))
                return i if i < len(self.parsed) and self.parsed[i] == v else -1
            hits = np.nonzero(self.parsed == v)[0]
            return int(hits[0]) if len(hits) else -1
        if not isinstance(lit, str):
            return None
        c = self.code_of.get(lit)
        return c if c is not None else -1

    def bound_code(self, lit, op) -> Optional[Tuple[float, float]]:
        """(lo, hi) code bounds (NaN = unbounded) for `col <op> lit`, or
        None when not lowerable (unsorted lane / type mismatch)."""
        import bisect

        if not self.sorted:
            return None
        if self.parsed is not None:
            if isinstance(lit, bool) or not isinstance(lit, (int, float)):
                return None
            v = float(lit)
            left = int(np.searchsorted(self.parsed, v, side="left"))
            right = int(np.searchsorted(self.parsed, v, side="right"))
        else:
            if not isinstance(lit, str):
                return None
            left = bisect.bisect_left(self.values, lit)
            right = bisect.bisect_right(self.values, lit)
        if op == "lt":
            return (np.nan, left - 1 + 0.0)  # codes < first value >= lit
        if op == "le":
            return (np.nan, right - 1 + 0.0)
        if op == "gt":
            return (right + 0.0, np.nan)
        if op == "ge":
            return (left + 0.0, np.nan)
        return None


def _intersect_ranges(a: RangeSet, b: RangeSet) -> RangeSet:
    """Conjunction of two boxes: per-column max of lows / min of highs
    (NaN = unbounded, so fmax/fmin ignore it)."""
    if a.verdict == "empty" or b.verdict == "empty":
        return RangeSet(a.lo, a.hi, verdict="empty",
                        exact=a.exact and b.exact)
    if a.verdict == "all":
        return RangeSet(b.lo, b.hi, verdict=b.verdict,
                        exact=a.exact and b.exact)
    if b.verdict == "all":
        return RangeSet(a.lo, a.hi, verdict=a.verdict,
                        exact=a.exact and b.exact)
    return RangeSet(np.fmax(a.lo, b.lo), np.fmin(a.hi, b.hi),
                    exact=a.exact and b.exact)


def extract_range_union(
    pred: ir.Expression,
    columns: Sequence[str],
    part_info: Optional[Dict[str, PartLane]] = None,
    max_terms: int = 8,
    str_lanes: Optional[frozenset] = None,
) -> Optional[List[RangeSet]]:
    """Lower a rewritten skipping predicate to a UNION of per-column range
    boxes (limited DNF): OR branches union, AND distributes (capped at
    ``max_terms``), and partition IN-lists lower to runs of consecutive
    dictionary codes. Every term exact ⇒ the union equals the exact
    evaluator's keep-set (terms may overlap; callers union row sets).
    None when any branch doesn't lower — the caller falls back."""
    t = type(pred)
    one = extract_ranges(pred, columns, part_info, str_lanes)
    if one is not None:
        return [one]
    if t is ir.Or:
        l = extract_range_union(pred.left, columns, part_info, max_terms,
                                str_lanes)
        if l is None:
            return None
        r = extract_range_union(pred.right, columns, part_info, max_terms,
                                str_lanes)
        if r is None or len(l) + len(r) > max_terms:
            return None
        return l + r
    if t is ir.And:
        l = extract_range_union(pred.left, columns, part_info, max_terms,
                                str_lanes)
        if l is None:
            return None
        r = extract_range_union(pred.right, columns, part_info, max_terms,
                                str_lanes)
        if r is None or len(l) * len(r) > max_terms:
            return None
        return [_intersect_ranges(a, b) for a in l for b in r]
    if (t is ir.In and part_info and isinstance(pred.value, ir.Column)):
        pmap = {c.lower(): c for c in part_info}
        key = pmap.get(pred.value.name.lower())
        if key is None:
            return None
        part = part_info[key]
        i = list(columns).index(key)
        codes = []
        for o in pred.options:
            if not isinstance(o, ir.Literal) or o.value is None:
                return None
            c = part.eq_code(o.value)
            if c is None:
                return None
            if c >= 0:
                codes.append(c)
        if not codes:
            e = RangeSet(np.full(len(columns), np.nan),
                         np.full(len(columns), np.nan), verdict="empty")
            return [e]
        codes = sorted(set(codes))
        runs: List[Tuple[int, int]] = []
        for c in codes:
            if runs and c == runs[-1][1] + 1:
                runs[-1] = (runs[-1][0], c)
            else:
                runs.append((c, c))
        if len(runs) > max_terms:
            return None
        out = []
        for lo_c, hi_c in runs:
            lo = np.full(len(columns), np.nan)
            hi = np.full(len(columns), np.nan)
            lo[i], hi[i] = float(lo_c), float(hi_c)
            out.append(RangeSet(lo, hi))
        return out
    return None


def extract_ranges(
    pred: ir.Expression,
    columns: Sequence[str],
    part_info: Optional[Dict[str, PartLane]] = None,
    str_lanes: Optional[frozenset] = None,
) -> Optional[RangeSet]:
    """Lower a *rewritten* skipping predicate (``min.c``/``max.c`` lanes for
    stats columns; RAW column references for partition columns, which the
    rewrite passes through) to per-column range bounds, or None when the
    shape doesn't fit (ORs, null-count tests, unknown columns → caller
    routes that query to the generic path). Strict stat comparisons are
    relaxed to non-strict — pruning may keep a boundary file it could have
    dropped, never the reverse. Partition lowerings stay exact: dictionary
    codes are discrete, so strict bounds bisect exactly."""
    col_ix = {c: i for i, c in enumerate(columns)}
    pmap = {c.lower(): c for c in (part_info or {})}
    lo = np.full(len(columns), np.nan)
    hi = np.full(len(columns), np.nan)
    empty = False
    exact = True

    def set_bounds(i: int, b_lo: float, b_hi: float) -> None:
        nonlocal empty
        if not np.isnan(b_lo):
            lo[i] = b_lo if np.isnan(lo[i]) else max(lo[i], b_lo)
        if not np.isnan(b_hi):
            hi[i] = b_hi if np.isnan(hi[i]) else min(hi[i], b_hi)

    def walk_part(e, t) -> bool:
        """Partition-column comparisons: Column(p) <op> Literal, both
        orientations (the skipping rewrite does not normalize these)."""
        nonlocal empty
        flip = {ir.Lt: ir.Gt, ir.Le: ir.Ge, ir.Gt: ir.Lt, ir.Ge: ir.Le,
                ir.Eq: ir.Eq}
        l, r = e.left, e.right
        if isinstance(l, ir.Literal) and isinstance(r, ir.Column):
            t = flip[t]
            l, r = r, l
        if not (isinstance(l, ir.Column) and isinstance(r, ir.Literal)):
            return False
        key = pmap.get(l.name.lower())
        if key is None:
            return False
        part = part_info[key]
        i = col_ix[key]
        if r.value is None:
            empty = True  # col <op> NULL matches nothing
            return True
        if t is ir.Eq:
            code = part.eq_code(r.value)
            if code is None:
                return False
            if code < 0:
                empty = True  # value absent from the table entirely
                return True
            set_bounds(i, float(code), float(code))
            return True
        op = {ir.Lt: "lt", ir.Le: "le", ir.Gt: "gt", ir.Ge: "ge"}.get(t)
        if op is None:
            return False
        b = part.bound_code(r.value, op)
        if b is None:
            return False
        set_bounds(i, *b)
        if not np.isnan(hi[i]) and hi[i] < 0:
            empty = True  # upper bound below every code
        if not np.isnan(lo[i]) and lo[i] > len(part.values) - 1:
            empty = True  # lower bound above every code
        return True

    def walk(e: ir.Expression) -> bool:
        nonlocal empty, exact
        t = type(e)
        if t is ir.And:
            return walk(e.left) and walk(e.right)
        if t is ir.Literal:
            if e.value is None or e.value is True:
                return True  # unknown/true conjunct prunes nothing
            if e.value is False:
                empty = True
                return True
            return False
        if t in (ir.Le, ir.Lt, ir.Ge, ir.Gt, ir.Eq):
            l, r = e.left, e.right
            if pmap and walk_part(e, t):
                return True
            if t is ir.Eq:
                return False  # stat lanes never see raw equality
            if not (isinstance(l, ir.Column) and isinstance(r, ir.Literal)):
                return False
            name = l.name
            base = name[4:] if name.startswith(("min.", "max.")) else None
            if (isinstance(r.value, str) and base is not None
                    and base in (str_lanes or frozenset())):
                # string stat lane: compare in 6-byte-prefix space; the
                # truncation makes the bound conservative, never exact
                from delta_tpu.ops.state_export import string_prefix_lane_value

                v = string_prefix_lane_value(r.value)
                exact = False
            elif not isinstance(r.value, (int, float)) or isinstance(r.value, bool):
                return False
            else:
                v = float(r.value)
            if name.startswith("min.") and t in (ir.Le, ir.Lt):
                i = col_ix.get(name[4:])
                if i is None:
                    return False
                if t is ir.Lt:
                    exact = False
                hi[i] = v if np.isnan(hi[i]) else min(hi[i], v)
                return True
            if name.startswith("max.") and t in (ir.Ge, ir.Gt):
                i = col_ix.get(name[4:])
                if i is None:
                    return False
                if t is ir.Gt:
                    exact = False
                lo[i] = v if np.isnan(lo[i]) else max(lo[i], v)
                return True
            return False
        return False

    if not walk(pred):
        return None
    if empty:
        return RangeSet(lo, hi, verdict="empty", exact=exact)
    if np.isnan(lo).all() and np.isnan(hi).all():
        return RangeSet(lo, hi, verdict="all", exact=exact)
    return RangeSet(lo, hi, exact=exact)


# -- the resident entry ------------------------------------------------------


@dataclass
class PlanResult:
    """One query's plan from the resident state. ``rows`` are row indices
    into the entry's layout (map to paths via ``ResidentState.paths``);
    ``overflow`` means more than K files survived and the caller must
    fall back for this query (counts stay exact)."""

    count: int
    rows: np.ndarray
    overflow: bool = False
    # 'device' | 'device-sharded' | 'host-resident' | 'verdict'
    via: str = "host-resident"


class ResidentState:
    """One table's scan-planning lanes in HBM + exact host mirrors.

    Rows are append-only (a re-added path gets a fresh row; the old one's
    alive bit drops); device arrays are padded to a power-of-two capacity so
    tail appends hit a handful of compiled kernel shapes.
    """

    def __init__(self, log_path: str, metadata_id: str, version: int,
                 columns: List[str], paths: List[str],
                 lanes: Dict[str, np.ndarray],
                 part_info: Optional[Dict[str, "PartLane"]] = None,
                 str_lanes: Optional[frozenset] = None):
        self.log_path = log_path
        self.metadata_id = metadata_id
        self.version = version
        self.columns = columns
        # partition pseudo-lanes: column name -> dictionary metadata; the
        # lane itself lives in h_lo/h_hi as min=max=code (+inf/-inf = null
        # partition value: an inverted range that no bounded query keeps)
        self.part_info: Dict[str, PartLane] = part_info or {}
        # stats columns whose lanes hold 6-byte string prefixes: literals
        # must transform through the same encoding, and bounds are never
        # exact (see state_export.string_prefix_lane_value)
        self.str_lanes: frozenset = str_lanes or frozenset()
        self.paths = list(paths)
        self.path_to_row: Dict[str, int] = {p: i for i, p in enumerate(paths)}
        n = len(paths)
        self.num_rows = n
        self.capacity = _next_pow2(max(n, 1))
        # exact host mirrors (float64 bounds; the device carries f32)
        self.h_alive = np.ones(n, bool)
        self.h_lo = lanes["min"]  # (C, n) float64
        self.h_hi = lanes["max"]
        self.h_size = lanes["size"]  # (n,) int64
        self._dead = 0
        self._dev = None  # lazily-built device arrays
        self._dev_shards = 1  # mesh shards the residency is placed over
        self._lock = threading.RLock()
        self.last_used = 0.0
        # device-memory accounting (obs/hbm_ledger: gc-backstopped)
        from delta_tpu.obs.hbm_ledger import Account

        self._hbm = Account("stateCache")

    # -- device residency -------------------------------------------------

    def _pad2(self, a: np.ndarray, fill) -> np.ndarray:
        out = np.full((a.shape[0], self.capacity), fill, np.float32)
        out[:, : a.shape[1]] = a
        return out

    def _build_device(self, shards: int = 1) -> None:
        mins = self._pad2(_f32_down(self.h_lo), np.nan)
        maxs = self._pad2(_f32_up(self.h_hi), np.nan)
        alive = np.zeros(self.capacity, bool)
        alive[: self.num_rows] = self.h_alive[: self.num_rows]
        per_device = None
        if shards > 1:
            # sharded residency: lanes split along the file axis over the
            # 1-D state mesh, so the shard_map plan kernel reads its slice
            # locally — each device's slice accounts under ITS ledger entry
            from delta_tpu.parallel.mesh import (NamedSharding, P,
                                                 state_mesh)
            from delta_tpu.parallel.mesh import STATE_AXIS as _AX

            mesh = state_mesh(shards)
            lane = NamedSharding(mesh, P(None, _AX))
            flat = NamedSharding(mesh, P(_AX))
            self._dev = {
                "mins": link.to_device(mins, lane),
                "maxs": link.to_device(maxs, lane),
                "alive": link.to_device(alive, flat),
            }
            per = self.device_bytes // shards
            per_device = {i: per for i in range(shards)}
        else:
            self._dev = {
                "mins": link.to_device(mins),
                "maxs": link.to_device(maxs),
                "alive": link.to_device(alive),
            }
        self._dev_shards = shards
        self._hbm.on(self, self.device_bytes, per_device=per_device)

    @property
    def device_bytes(self) -> int:
        c = len(self.columns)
        return self.capacity * (2 * c * 4 + 1)

    def ensure_resident(self, shards: Optional[int] = None) -> None:
        with self._lock:
            if self._dev is None:
                self._build_device(shards if shards is not None else 1)

    @property
    def is_resident(self) -> bool:
        return self._dev is not None

    @property
    def resident_shards(self) -> int:
        """Mesh shards the device residency is placed over (1 = unsharded
        or not resident)."""
        return self._dev_shards if self._dev is not None else 1

    def drop_device(self) -> None:
        with self._lock:
            self._dev = None
            self._dev_shards = 1
            self._hbm.off()

    # -- incremental tail apply ------------------------------------------

    def apply_tail(self, version: int, removed_paths: Sequence[str],
                   added: Tuple[List[str], np.ndarray, np.ndarray, np.ndarray]) -> bool:
        """Advance to ``version``: drop removed paths, append added rows
        (paths, lo(C,k), hi(C,k), size(k)). Returns False when the entry
        must be rebuilt instead (capacity overflow / too much garbage)."""
        add_paths, add_lo, add_hi, add_size = added
        k = len(add_paths)
        with self._lock:
            # Pass 1: count dead rows WITHOUT mutating the mirrors, so the
            # rebuild-needed verdict below can bail with the entry still
            # exactly at its old version (a concurrent plan_ranges holding
            # expected_version=old must keep seeing consistent state).
            dead_rows: List[int] = []
            seen_dead = set()
            for p in removed_paths:
                r = self.path_to_row.get(p)
                if r is not None and self.h_alive[r] and r not in seen_dead:
                    dead_rows.append(r)
                    seen_dead.add(r)
            for p in add_paths:
                # re-add supersedes the old row's stats
                r = self.path_to_row.get(p)
                if r is not None and self.h_alive[r] and r not in seen_dead:
                    dead_rows.append(r)
                    seen_dead.add(r)
            start = self.num_rows
            if (start + k > self.capacity
                    or self._dead + len(dead_rows) > max(1024, self.num_rows // 2)):
                return False
            # Pass 2: committed — kill exactly the rows Pass 1 counted
            # (re-added paths keep their mapping until the append below
            # overwrites it; removed paths drop theirs)
            for p in removed_paths:
                self.path_to_row.pop(p, None)
            self.h_alive[dead_rows] = False
            self._dead += len(dead_rows)
            if k:
                self.h_alive = np.concatenate([self.h_alive, np.ones(k, bool)])
                self.h_lo = np.concatenate([self.h_lo, add_lo], axis=1)
                self.h_hi = np.concatenate([self.h_hi, add_hi], axis=1)
                self.h_size = np.concatenate([self.h_size, add_size])
                for i, p in enumerate(add_paths):
                    self.paths.append(p)
                    self.path_to_row[p] = start + i
                self.num_rows = start + k
            if self._dev is not None:
                if self._dev_shards > 1:
                    # sharded lanes: drop and rebuild lazily from the
                    # updated mirrors on the next device plan — a scatter
                    # across shard-local index spaces isn't worth its
                    # compile-cache footprint, and the router already
                    # prices the cold re-upload honestly (_price_plan)
                    self._dev = None
                    self._dev_shards = 1
                    self._hbm.off()
                else:
                    self._apply_tail_device(dead_rows, start, k, add_lo, add_hi)
            self.version = version
            return True

    def map_tail_lanes(self, arr, metadata):
        """Translate a decoded tail's FileStateArrays into this entry's lane
        space: stats lanes pass through; partition codes re-map through the
        entry dictionaries, EXTENDING them for unseen values (an append that
        sorts after the current maximum keeps range lowering alive; an
        out-of-order value clears ``sorted`` — equality keeps working and
        the next rebuild re-sorts). None → caller rebuilds."""
        from delta_tpu.ops.state_export import _stat_to_lane

        with self._lock:
            if not arr.paths:  # pure-remove tail: no lanes to translate
                z = np.empty((len(self.columns), 0))
                return [], z, z.copy(), np.empty(0, np.int64)
            part_cols = sorted(self.part_info.keys())
            if part_cols != sorted(arr.partition_codes.keys()):
                return None
            stats_cols = [c for c in self.columns if c not in self.part_info]
            if stats_cols != sorted(arr.stats_min.keys()):
                return None
            mapped: Dict[str, np.ndarray] = {}
            for c in part_cols:
                part = self.part_info[c]
                tail_values = arr.partition_dicts[c]
                trans = np.empty(len(tail_values), np.int64)
                for j, v in enumerate(tail_values):
                    code = part.code_of.get(v)
                    if code is None:
                        code = len(part.values)
                        if code >= (1 << 24):
                            return None
                        if part.parsed is not None:
                            pv = _stat_to_lane(v, part.dt)
                            # a new STRING mapping to an already-present sort
                            # key ("1.0" joining "1") would split one value
                            # across two codes — rebuild (which falls back
                            # to lex order) instead of mis-serving equality
                            if pv is None or bool(np.any(part.parsed == pv)):
                                return None
                            if part.sorted and len(part.parsed):
                                part.sorted = pv > part.parsed[-1]
                            part.parsed = np.append(part.parsed, pv)
                        elif part.sorted and len(part.values):
                            part.sorted = v > part.values[-1]
                        part.values.append(v)
                        part.code_of[v] = code
                    trans[j] = code
                codes = arr.partition_codes[c]
                if len(tail_values) == 0:  # all-null tail for this column
                    mapped[c] = np.full(len(codes), -1, np.int32)
                else:
                    mapped[c] = np.where(
                        codes >= 0, trans[np.maximum(codes, 0)], -1
                    ).astype(np.int32)
            lanes = _stacked_lanes(arr, stats_cols, mapped)
            return (list(arr.paths), lanes["min"], lanes["max"],
                    lanes["size"])

    def _apply_tail_device(self, dead_rows, start, k, add_lo, add_hi) -> None:
        """One small upload + one jitted scatter/slice update in HBM.

        Shapes are bucketed (pow2 pads; out-of-range scatter indices use
        XLA drop semantics) so a steady commit stream reuses a handful of
        compiled executables."""
        dev = self._dev
        cap = self.capacity
        d = _next_pow2(max(len(dead_rows), 1), floor=8)
        dead = np.full(d, cap, np.int32)  # cap = out of bounds -> dropped
        dead[: len(dead_rows)] = dead_rows
        a = _next_pow2(max(k, 1), floor=8)
        rows = np.full(a, cap, np.int32)
        rows[:k] = np.arange(start, start + k, dtype=np.int32)
        lo32 = np.full((self.h_lo.shape[0], a), np.nan, np.float32)
        hi32 = np.full((self.h_hi.shape[0], a), np.nan, np.float32)
        lo32[:, :k] = _f32_down(add_lo)
        hi32[:, :k] = _f32_up(add_hi)
        rows_dev = link.to_device(rows)
        dev["alive"] = _scatter_bool(dev["alive"], link.to_device(dead), False)
        dev["alive"] = _scatter_bool(dev["alive"], rows_dev, True)
        dev["mins"] = _scatter_cols(dev["mins"], rows_dev, link.to_device(lo32))
        dev["maxs"] = _scatter_cols(dev["maxs"], rows_dev, link.to_device(hi32))

    # -- serving ----------------------------------------------------------

    def plan_ranges(self, ranges: Sequence[RangeSet], k=256,
                    use_device: Optional[bool] = None,
                    expected_version: Optional[int] = None) -> Optional[List[PlanResult]]:
        """Evaluate a batch of range queries against the resident lanes:
        one dispatch, one packed-bitmap download. Structural verdicts
        short-circuit; device/host routing follows the link cost model unless
        pinned (each PlanResult records the route in ``via``).

        ``k`` caps each result's row list: a scalar for the whole batch, or
        a per-range sequence (len(ranges)) — so a multi-term (OR/IN) query
        that needs its complete row set for the post-plan union doesn't
        force every single-term query sharing the dispatch onto huge plans.

        Runs under the entry lock so a concurrent ``apply_tail`` cannot
        mutate the mirrors mid-plan; ``expected_version`` guards the other
        race — the entry advancing *past* the caller's snapshot between
        lookup and plan — by returning None (caller re-plans or falls back).
        """
        n = len(ranges)
        ks = (np.full(n, int(k), np.int64) if np.isscalar(k)
              else np.asarray(k, np.int64))
        if len(ks) != n:
            raise ValueError(f"per-range k length {len(ks)} != {n} ranges")
        priced = None
        with self._lock:
            if expected_version is not None and self.version != expected_version:
                return None
            real_ix = [i for i, r in enumerate(ranges) if r.verdict is None]
            out: List[Optional[PlanResult]] = [None] * n
            alive_rows = np.nonzero(self.h_alive[: self.num_rows])[0]
            for i, r in enumerate(ranges):
                if r.verdict == "empty":
                    out[i] = PlanResult(0, np.empty(0, np.int64), via="verdict")
                elif r.verdict == "all":
                    out[i] = PlanResult(len(alive_rows), alive_rows[:ks[i]],
                                        overflow=len(alive_rows) > ks[i],
                                        via="verdict")
            if not real_ix:
                return out  # type: ignore[return-value]
            lo = np.stack([ranges[i].lo for i in real_ix])  # (M, C)
            hi = np.stack([ranges[i].hi for i in real_ix])
            real_ks = ks[real_ix]
            if use_device is None:
                use_device, priced = self._route_plan(len(real_ix))
            import time as _time

            shards = self._plan_shards(priced, len(real_ix)) if use_device else 1
            t0 = _time.perf_counter_ns()
            results = (self._plan_device(lo, hi, real_ks, shards=shards)
                       if use_device
                       else self._plan_host(lo, hi, real_ks))
            plan_s = (_time.perf_counter_ns() - t0) / 1e9
            ran_shards = self._dev_shards if use_device else 1
            via = ("device-sharded" if ran_shards > 1
                   else "device" if use_device else "host-resident")
            for j, i in enumerate(real_ix):
                results[j].via = via
                out[i] = results[j]
        # router audit OUTSIDE the entry lock: the ledger (and, with
        # calibration enabled, its state-file read-modify-write) must not
        # serialize concurrent planners or a tail apply. Only AUTO-routed
        # batches audit — a pinned mode made no priceable decision (and the
        # disabled/forced paths never pay the link probe just to price one).
        if priced is not None:
            from delta_tpu.obs import router_audit

            device_s, host_s, cells, device_fixed_s, sharded_s, _ns = priced
            # per-cell calibrator sample with the predictor's FIXED terms
            # (dispatch latency, bitmap download, cold upload) subtracted
            # first — the prediction re-adds them, so a sample that folded
            # them in would double-count the overhead and overpredict the
            # device forever
            if use_device:
                eff = plan_s - device_fixed_s
                # a sharded run did cells/shards per-device work: sample the
                # per-cell rate at the per-shard cell count so calibration
                # fits the device, not the mesh
                cal_cells = cells // max(ran_shards, 1)
                samples = ([("DEVICE_PRUNE_S_PER_CELL", cal_cells, eff)]
                           if eff > 0 else [])
            else:
                samples = [("HOST_PRUNE_S_PER_CELL", cells, plan_s)]
            predictions = {"device": device_s, "host-resident": host_s}
            if sharded_s is not None:
                predictions["device-sharded"] = sharded_s
            router_audit.record_audit(
                "scan.plan", self.log_path, via,
                predictions, plan_s,
                units={"cells": cells, "queries": len(real_ix)},
                samples=samples, log_path=self.log_path,
                # once per planned query: the calibrator state-file write
                # must be interval-throttled, not per-plan
                calibration_flush=False,
            )
        return out  # type: ignore[return-value]

    def _price_plan(self, m: int) -> Tuple[float, float, int, float,
                                           Optional[float], int]:
        """The router's cost model for planning ``m`` range queries against
        this entry: (device_s, host_s, cells, device_fixed_s, sharded_s,
        shards). ``device_fixed_s`` is the cell-count-independent part of
        the device price (dispatch + download + cold upload) — what the
        calibrator must subtract from a measured sample before fitting the
        per-cell rate. ``sharded_s`` prices the same plan over the
        shard_map mesh (None when no multi-device mesh is feasible) with
        the calibratable per-shard constants, so the audit record carries
        the sharded-vs-single decision. Constants read through
        ``link.constant`` so calibration feeds back."""
        cells = m * self.num_rows * max(len(self.columns), 1)
        host_s = cells * link.constant("HOST_PRUNE_S_PER_CELL")
        p = link.profile()
        down_bytes = m * max(self.capacity // BLOCK // 8, 1)
        fixed_s = 2 * p.latency_s + p.download_s(down_bytes)
        if self._dev is None:
            # cold build ships the full lanes once; amortized over later
            # queries, but charge it to this call for honest routing
            fixed_s += p.upload_s(self.device_bytes)
        device_s = fixed_s + cells * link.constant("DEVICE_PRUNE_S_PER_CELL")
        shards = self._feasible_shards()
        sharded_s = None
        if shards > 1:
            sharded_s = fixed_s + link.sharded_plan_device_s(cells, shards, p)
        return device_s, host_s, cells, fixed_s, sharded_s, shards

    def _feasible_shards(self) -> int:
        """Largest pow2 shard count the mesh and the lane layout admit: the
        capacity must split into whole 1024-file BLOCKs per shard (capacity
        is pow2, so divisibility is monotone in the shard count). 1 when
        sharded planning is disabled or there is one device."""
        if not conf.get_bool("delta.tpu.distributed.plan.enabled", True):
            return 1
        if conf.get("delta.tpu.distributed.plan.mode", "auto") == "off":
            return 1
        try:
            import jax

            nd = len(jax.devices())
        except Exception:
            return 1
        s = 1
        while s * 2 <= nd and self.capacity % (s * 2 * BLOCK) == 0:
            s *= 2
        return s

    def _plan_shards(self, priced, m: int) -> int:
        """Shard count for a device-routed plan batch. Existing residency
        wins (no placement thrash); otherwise "force" takes the full mesh
        and "auto" takes it only when the per-shard cost model says the
        dispatch+gather tax beats the 1/shards cell scan win."""
        if self._dev is not None:
            return self._dev_shards
        s = self._feasible_shards()
        if s <= 1:
            return 1
        if conf.get("delta.tpu.distributed.plan.mode", "auto") == "force":
            return s
        if priced is not None:
            device_s, _h, _c, fixed_s, sharded_s, shards = priced
            return shards if (sharded_s is not None
                              and sharded_s < device_s) else 1
        # pinned device route (devicePlan.mode=force) skipped batch pricing:
        # price only the sharded-vs-single choice here
        cells = m * self.num_rows * max(len(self.columns), 1)
        p = link.profile()
        single = cells * link.constant("DEVICE_PRUNE_S_PER_CELL")
        return s if link.sharded_plan_device_s(cells, s, p) < single else 1

    def _route_plan(self, m: int):
        """(use_device, priced) for ``m`` range queries: the enabled/mode
        short-circuits run BEFORE any pricing, so a disabled or pinned
        deployment never pays the link probe — and gets no audit record,
        since no priceable decision was made. ``priced`` is the
        ``_price_plan`` tuple in auto mode, else None. The device side
        enters at its best price (sharded when the mesh wins)."""
        if not conf.get_bool("delta.tpu.stateCache.devicePlan.enabled", True):
            return False, None
        mode = conf.get("delta.tpu.stateCache.devicePlan.mode", "auto")
        if mode == "force":
            return True, None
        if mode == "off":
            return False, None
        priced = self._price_plan(m)
        best_device = (priced[0] if priced[4] is None
                       else min(priced[0], priced[4]))
        return best_device < priced[1], priced

    def _plan_host(self, lo: np.ndarray, hi: np.ndarray,
                   ks: np.ndarray) -> List[PlanResult]:
        n = self.num_rows
        mins, maxs = self.h_lo[:, :n], self.h_hi[:, :n]
        alive = self.h_alive[:n]
        out = []
        for q in range(lo.shape[0]):
            keep = alive.copy()
            for c in range(lo.shape[1]):
                if not np.isnan(lo[q, c]):
                    keep &= ~(maxs[c] < lo[q, c])  # NaN stat keeps
                if not np.isnan(hi[q, c]):
                    keep &= ~(mins[c] > hi[q, c])
            rows = np.nonzero(keep)[0]
            k = ks[q]
            out.append(PlanResult(len(rows), rows[:k], overflow=len(rows) > k))
        return out

    def _plan_device(self, lo: np.ndarray, hi: np.ndarray,
                     ks: np.ndarray, shards: int = 1) -> List[PlanResult]:
        """Coarse-fine plan: the device culls 1024-file BLOCKS (one dispatch
        over the resident f32 lanes, one tiny packed-bitmap download); the
        host then evaluates exactly (float64 mirrors) inside the surviving
        blocks only. Index extraction never runs on device — measured on a
        v5e, a vmapped ``nonzero``/``top_k`` over (256, 1M) costs 0.7-2.4 s
        where the block-bitmap reduction costs ~0.1 s — and the fine pass
        erases the f32 slop, so device results equal host results exactly.

        With sharded residency (``shards > 1``) the cull runs as a
        shard_map over the state mesh: each device evaluates its 1/shards
        slice of the lanes, the block bitmaps all-gather along the file
        axis, and the identical host fine pass finishes — so sharded
        results equal single-device results equal host results exactly,
        by construction."""
        self.ensure_resident(shards)
        m = lo.shape[0]
        mb = _next_pow2(m, floor=8)  # bucket the query-batch dim too
        lo_p = np.full((mb, lo.shape[1]), np.nan, np.float32)
        hi_p = np.full((mb, hi.shape[1]), np.nan, np.float32)
        lo_p[:m] = _f32_down(lo)
        hi_p[:m] = _f32_up(hi)
        try:
            if self._dev_shards > 1:
                from delta_tpu.utils import telemetry

                telemetry.bump_counter("dist.plan.sharded")
                bl = _sharded_block_kernel(
                    self._dev["mins"], self._dev["maxs"], self._dev["alive"],
                    link.to_device(lo_p), link.to_device(hi_p), BLOCK,
                    self._dev_shards,
                )
                blocks = link.to_host(bl)[:m].astype(bool)
            else:
                bits = _block_kernel(
                    self._dev["mins"], self._dev["maxs"], self._dev["alive"],
                    link.to_device(lo_p), link.to_device(hi_p), BLOCK,
                )
                n_blocks = self.capacity // BLOCK
                blocks = np.unpackbits(link.to_host(bits)[:m], axis=1,
                                       count=n_blocks)
        except Exception as e:  # noqa: BLE001 — degradation ladder, first
            # rung: a shard_map/lowering failure (mesh reshape race, OOM on
            # the coarse cull) must cost latency, not the query — the host
            # fine pass over every block is the same exact evaluation the
            # device pass would have narrowed. devicePlan.mode=force pins
            # the device, so there the failure propagates instead of
            # reading as a device plan the host quietly served.
            if conf.get("delta.tpu.stateCache.devicePlan.mode",
                        "auto") == "force":
                raise
            from delta_tpu.utils import telemetry

            telemetry.bump_counter("dist.degraded.plan")
            telemetry.add_span_data(deviceError=telemetry.exc_text(e))
            return self._plan_host(lo, hi, ks)
        return self._fine_pass(blocks, lo, hi, ks)

    def _fine_pass(self, blocks: np.ndarray, lo: np.ndarray, hi: np.ndarray,
                   ks: np.ndarray) -> List[PlanResult]:
        """Exact float64 host evaluation inside the device-surviving blocks
        — shared by the single-device and sharded coarse passes."""
        n = self.num_rows
        mins, maxs, alive = self.h_lo[:, :n], self.h_hi[:, :n], self.h_alive[:n]
        out = []
        for q in range(lo.shape[0]):
            hit = np.nonzero(blocks[q])[0]
            if not len(hit):
                out.append(PlanResult(0, np.empty(0, np.int64)))
                continue
            cand = np.concatenate([
                np.arange(b * BLOCK, min((b + 1) * BLOCK, n)) for b in hit
            ])
            cand = cand[cand < n]
            keep = alive[cand].copy()
            for c in range(lo.shape[1]):
                if not np.isnan(lo[q, c]):
                    keep &= ~(maxs[c][cand] < lo[q, c])
                if not np.isnan(hi[q, c]):
                    keep &= ~(mins[c][cand] > hi[q, c])
            rows = cand[keep]
            k = ks[q]
            out.append(PlanResult(len(rows), rows[:k], overflow=len(rows) > k))
        return out


@functools.lru_cache(maxsize=None)
def _scatter_bool_fn(value: bool):
    ensure_compilation_cache()
    import jax

    return jax.jit(lambda a, r: a.at[r].set(value, mode="drop"))


def _scatter_bool(arr, rows, value: bool):
    return _scatter_bool_fn(value)(arr, rows)


@functools.lru_cache(maxsize=None)
def _scatter_cols_fn():
    ensure_compilation_cache()
    import jax

    return jax.jit(lambda a, r, v: a.at[:, r].set(v, mode="drop"))


def _scatter_cols(arr, rows, vals):
    return _scatter_cols_fn()(arr, rows, vals)


# device block-cull granularity: pow2 ≤ the capacity floor in _next_pow2, so
# the padded capacity always divides evenly
BLOCK = 1024


@functools.lru_cache(maxsize=None)
def _block_kernel_fn(block: int):
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    def kernel(mins, maxs, alive, lo, hi):
        # mins/maxs: (C, cap) f32; alive: (cap,) bool; lo/hi: (M, C) f32.
        # keep[m, f] = alive[f] AND over columns: the file's [min,max] range
        # can intersect the query's [lo,hi]; NaN (either side) = no bound.
        keep = jnp.broadcast_to(alive[None, :], (lo.shape[0], alive.shape[0]))
        for c in range(lo.shape[1]):  # static unroll: C is a lane count
            mn, mx = mins[c][None, :], maxs[c][None, :]
            lo_c, hi_c = lo[:, c:c + 1], hi[:, c:c + 1]
            keep = keep & (jnp.isnan(mx) | jnp.isnan(lo_c) | (mx >= lo_c))
            keep = keep & (jnp.isnan(mn) | jnp.isnan(hi_c) | (mn <= hi_c))
        blocks = keep.reshape(keep.shape[0], keep.shape[1] // block, block).any(axis=2)
        return jnp.packbits(blocks, axis=1)

    return jax.jit(kernel)


def _block_kernel(mins, maxs, alive, lo, hi, block: int):
    return _block_kernel_fn(block)(mins, maxs, alive, lo, hi)


@functools.lru_cache(maxsize=None)
def _sharded_block_kernel_fn(block: int, ncols: int, shards: int):
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    from delta_tpu.parallel.mesh import P, STATE_AXIS, state_mesh
    from delta_tpu.utils.jaxcompat import shard_map

    mesh = state_mesh(shards)

    def kernel(mins, maxs, alive, lo, hi):
        # per-shard slices: mins/maxs (C, cap/shards), alive (cap/shards,);
        # lo/hi replicated (M, C). Same can-intersect test as _block_kernel
        # over this shard's files; each shard reduces its own 1024-file
        # blocks and the out-spec all-gathers the block maps along the
        # file axis — so the merged map is bit-identical to the
        # single-device cull.
        keep = jnp.broadcast_to(alive[None, :], (lo.shape[0], alive.shape[0]))
        for c in range(ncols):  # static unroll: C is a lane count
            mn, mx = mins[c][None, :], maxs[c][None, :]
            lo_c, hi_c = lo[:, c:c + 1], hi[:, c:c + 1]
            keep = keep & (jnp.isnan(mx) | jnp.isnan(lo_c) | (mx >= lo_c))
            keep = keep & (jnp.isnan(mn) | jnp.isnan(hi_c) | (mn <= hi_c))
        blocks = keep.reshape(
            keep.shape[0], keep.shape[1] // block, block
        ).any(axis=2)
        return blocks.astype(jnp.uint8)

    sm = shard_map(
        kernel, mesh=mesh,
        in_specs=(P(None, STATE_AXIS), P(None, STATE_AXIS), P(STATE_AXIS),
                  P(), P()),
        out_specs=P(None, STATE_AXIS),
    )
    return jax.jit(sm)


def _sharded_block_kernel(mins, maxs, alive, lo, hi, block: int, shards: int):
    return _sharded_block_kernel_fn(block, lo.shape[1], shards)(
        mins, maxs, alive, lo, hi
    )


# -- building entries from snapshots ----------------------------------------


def _lanes_from_arrays(arr, columns: Sequence[str]):
    lo = np.stack([arr.stats_min[c] for c in columns]) if columns else np.empty((0, arr.num_files))
    hi = np.stack([arr.stats_max[c] for c in columns]) if columns else np.empty((0, arr.num_files))
    return {"min": lo, "max": hi, "size": arr.size.astype(np.int64)}


def _string_stat_cols(metadata) -> List[str]:
    from delta_tpu.schema.types import StringType

    pset = set(metadata.partition_columns)
    return sorted(f.name for f in metadata.schema.fields
                  if isinstance(f.data_type, StringType) and f.name not in pset)


def _build_part_info(arr, metadata):
    """Value-sort each partition dictionary (typed order when the column
    type parses every value, else code-point order), remap codes to ranks,
    and emit (part_info, remapped_codes) — or None when a dictionary is too
    large for exact f32 lanes."""
    from delta_tpu.ops.state_export import _NUMERIC, _stat_to_lane

    types = {f.name: f.data_type for f in metadata.schema.fields}
    part_info: Dict[str, PartLane] = {}
    remapped: Dict[str, np.ndarray] = {}
    for c in sorted(arr.partition_codes.keys()):
        values = list(arr.partition_dicts[c])
        if len(values) > (1 << 24):  # codes must stay f32-exact
            return None
        dt = types.get(c)
        parsed = None
        if isinstance(dt, _NUMERIC):
            p = [_stat_to_lane(v, dt) for v in values]
            if all(x is not None for x in p):
                cand = np.asarray(p, np.float64)
                # duplicate sort keys ("1" vs "1.0") would make a value
                # range span two codes non-contiguously — fall back to lex
                if len(np.unique(cand)) == len(cand):
                    parsed = cand
        if parsed is not None:
            order = np.argsort(parsed, kind="stable")
            parsed = parsed[order]
        else:
            order = np.argsort(np.asarray(values, object), kind="stable")
            dt = None
        rank = np.empty(len(values), np.int64)
        rank[order] = np.arange(len(values))
        codes = arr.partition_codes[c]
        if len(values) == 0:
            # every alive file carries null for this column: no dictionary,
            # all codes -1 (the inverted-range lane prunes them exactly)
            remapped[c] = np.full(len(codes), -1, np.int32)
        else:
            remapped[c] = np.where(
                codes >= 0, rank[np.maximum(codes, 0)], -1).astype(np.int32)
        svals = [values[i] for i in order]
        part_info[c] = PartLane(
            values=svals, parsed=parsed,
            code_of={v: i for i, v in enumerate(svals)}, dt=dt,
        )
    return part_info, remapped


def _stacked_lanes(arr, stats_cols, part_codes: Dict[str, np.ndarray]):
    """Combined lane stack: stats columns first (sorted), then partition
    pseudo-lanes (sorted) — matching the entry's ``columns`` order."""
    lanes = _lanes_from_arrays(arr, stats_cols)
    if part_codes:
        lo_rows, hi_rows = [], []
        for c in sorted(part_codes.keys()):
            lo_r, hi_r = _part_lane_rows(part_codes[c])
            lo_rows.append(lo_r)
            hi_rows.append(hi_r)
        lanes["min"] = np.concatenate([lanes["min"], np.stack(lo_rows)], axis=0)
        lanes["max"] = np.concatenate([lanes["max"], np.stack(hi_rows)], axis=0)
    return lanes


def build_entry(snapshot) -> Optional[ResidentState]:
    """Full build of a resident entry from a snapshot's columnar state —
    partitioned tables included (dictionary-coded partition lanes; the
    reference's primary pruning path, `PartitionFiltering.scala:27-43`,
    served from the same block-cull kernel). None when the shape is
    unsupported (odd stats / oversized dictionaries)."""
    from delta_tpu.ops.state_export import arrays_from_columns

    str_cols = _string_stat_cols(snapshot.metadata)
    arr = arrays_from_columns(
        snapshot._columnar, snapshot._alive_mask, snapshot.metadata,
        string_prefix_cols=str_cols,
    )
    if arr is None:
        return None
    built = _build_part_info(arr, snapshot.metadata)
    if built is None:
        return None
    part_info, remapped = built
    stats_cols = sorted(arr.stats_min.keys())
    columns = stats_cols + sorted(part_info.keys())
    return ResidentState(
        log_path=snapshot.delta_log.log_path,
        metadata_id=snapshot.metadata.id,
        version=snapshot.version,
        columns=columns,
        paths=list(arr.paths),
        lanes=_stacked_lanes(arr, stats_cols, remapped),
        part_info=part_info,
        str_lanes=frozenset(str_cols),
    )


def _decode_tail(snapshot, from_version: int):
    """Decode commits (from_version, snapshot.version] to (removed_paths,
    FileStateArrays) or None when incremental apply isn't safe (metadata
    change in the tail, missing commit files, undecodable shapes). The
    caller maps the arrays into its entry's lane space (partition code
    translation happens there, under the entry lock)."""
    from delta_tpu.log.columnar import decode_segment
    from delta_tpu.ops.state_export import arrays_from_columns
    from delta_tpu.protocol import filenames
    from delta_tpu.protocol.actions import Metadata

    log = snapshot.delta_log
    paths = [
        f"{log.log_path}/{filenames.delta_file(v)}"
        for v in range(from_version + 1, snapshot.version + 1)
    ]
    try:
        cols = decode_segment(log.store, [], paths)
    except Exception:
        return None
    if any(isinstance(a, Metadata) for a in cols.other_actions):
        return None  # schema/config may have changed -> rebuild
    w = cols.winner_mask()
    alive, _ = cols.replay(winner=w)
    dead_winner = w & ~alive
    removed = cols.paths_for(np.nonzero(dead_winner)[0])
    arr = arrays_from_columns(
        cols, alive, snapshot.metadata,
        string_prefix_cols=_string_stat_cols(snapshot.metadata))
    if arr is None:
        return None
    return removed, arr


class DeviceStateCache:
    """Process-wide registry of :class:`ResidentState` entries with an HBM
    byte budget (`delta.tpu.stateCache.maxBytes`) and LRU eviction — the
    TPU analogue of the reference's `StateCache` Spark-memory cache."""

    _instance: Optional["DeviceStateCache"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._entries: Dict[str, ResidentState] = {}
        self._lock = threading.RLock()
        self._build_locks: Dict[str, threading.Lock] = {}
        self._tick = 0

    @classmethod
    def instance(cls) -> "DeviceStateCache":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = DeviceStateCache()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._instance_lock:
            cls._instance = None

    def invalidate(self, log_path: str) -> None:
        with self._lock:
            e = self._entries.pop(log_path, None)
            self._build_locks.pop(log_path, None)
            if e is not None:
                e.drop_device()  # return its bytes to the HBM ledger

    def _lookup(self, key: str, snapshot):
        """Registry-lock lookup. Returns (entry_or_None, verdict): 'hit',
        'older' (serve from host), or 'advance' (tail apply / rebuild)."""
        with self._lock:
            e = self._entries.get(key)
            if e is not None and e.metadata_id != snapshot.metadata.id:
                e = None  # table replaced in place
            if e is None:
                return None, "advance"
            if e.version > snapshot.version:
                return None, "older"  # time travel below residency
            return e, ("hit" if e.version == snapshot.version else "advance")

    def get(self, snapshot) -> Optional[ResidentState]:
        """Entry current at the snapshot's version: cache hit, incremental
        tail apply, or full rebuild. None when unsupported or disabled.

        The registry lock covers only lookups/inserts; the seconds-long
        decode/build work runs under a per-table build lock so a cold build
        for one table never stalls cache hits for another."""
        if not conf.get_bool("delta.tpu.stateCache.enabled", True):
            return None
        key = snapshot.delta_log.log_path
        with self._lock:
            self._tick += 1
            tick = self._tick
            build_lock = self._build_locks.setdefault(key, threading.Lock())
        e, verdict = self._lookup(key, snapshot)
        if verdict == "older":
            return None
        if verdict == "hit":
            e.last_used = tick
            return e
        with build_lock:
            # re-check: another thread may have advanced/built meanwhile
            e, verdict = self._lookup(key, snapshot)
            if verdict == "older":
                return None
            if verdict == "hit":
                e.last_used = tick
                return e
            from delta_tpu.utils import telemetry

            if e is not None:  # behind: try the incremental tail
                with telemetry.record_operation(
                    "delta.stateCache.tailApply",
                    {"fromVersion": e.version, "toVersion": snapshot.version},
                    path=snapshot.delta_log.data_path,
                ) as tev:
                    tail = _decode_tail(snapshot, e.version)
                    ok = False
                    if tail is not None:
                        removed, arr = tail
                        added = e.map_tail_lanes(arr, snapshot.metadata)
                        if added is not None:
                            ok = e.apply_tail(snapshot.version, removed, added)
                    tev.data["applied"] = ok
                if not ok:
                    e = None
            if e is None:
                with telemetry.record_operation(
                    "delta.stateCache.build",
                    {"version": snapshot.version},
                    path=snapshot.delta_log.data_path,
                ) as bev:
                    e = build_entry(snapshot)
                    bev.data["built"] = e is not None
                telemetry.bump_counter("stateCache.builds")
                if e is None:
                    return None
                with self._lock:
                    old = self._entries.get(key)
                    if old is not None and old is not e:
                        old.drop_device()  # rebuilt: old entry's HBM returns
                    self._entries[key] = e
            e.last_used = tick
            with self._lock:
                self._evict_over_budget(keep=key)
            # state-cache growth can push the PROCESS-WIDE device budget
            # over: apply key-cache LRU pressure now (no entry/registry
            # lock held here), not at the next merge
            from delta_tpu.obs import hbm_ledger

            hbm_ledger.maybe_relieve()
            return e

    def _evict_over_budget(self, keep: str) -> None:
        # HBM budget: drop device arrays LRU (host mirrors keep serving)
        budget = int(conf.get("delta.tpu.stateCache.maxBytes", 2 << 30))
        resident = [(p, e) for p, e in self._entries.items() if e.is_resident]
        total = sum(e.device_bytes for _, e in resident)
        for p, e in sorted(resident, key=lambda kv: kv[1].last_used):
            if total <= budget:
                break
            if p == keep:
                continue
            e.drop_device()
            total -= e.device_bytes
        # host budget: entries (mirrors + path dictionaries) are themselves
        # sizable — drop whole tables LRU beyond maxEntries
        max_entries = int(conf.get("delta.tpu.stateCache.maxEntries", 16))
        if len(self._entries) > max_entries:
            for p, e in sorted(self._entries.items(),
                               key=lambda kv: kv[1].last_used):
                if p == keep:
                    continue
                self._entries.pop(p, None)
                self._build_locks.pop(p, None)
                e.drop_device()  # return its bytes to the HBM ledger
                if len(self._entries) <= max_entries:
                    break
