"""TPC-H ``LINEITEM`` under the refresh functions RF1 and RF2: the table of
:mod:`benchmark.tables.lineitem` (its generator and its Arrow form, imported,
not copied), the generator of refresh sets, and the plain reference.

Everything here is numpy and pyarrow; nothing is imported from the engine.

A refresh set (specification 2.5-2.7, data sets 4.2) is a pair of functions
over ``orders`` orders each (SF x 1500): **RF1**, the new-sales refresh,
inserts every line of ``orders`` new orders; **RF2**, the old-sales refresh,
deletes every line of ``orders`` loaded orders. :meth:`Generator.refresh_set`
makes set ``k`` from the seed as dbgen lays them out (as remembered offline;
the configuration's file lists it under ``assumed``): RF2's keys are the k-th
run of ``orders`` loaded orders in key order, RF1's orders take the same
order indices on the next unused eighth of the sparse key (the load uses the
first 8 of every 32 key values, set k the second 8 of the 32 its indices
fall in), their lines drawn by the load's own column rules.

The reference (:func:`ref_refresh`) applies a run of functions to the loaded
rows in plain numpy: an RF1 appends each of its rows unless a row of its
order key is held (``MERGE ... WHEN NOT MATCHED THEN INSERT *``: a repeated
RF1 inserts nothing), an RF2 drops every row whose order key is in its list
(``MERGE ... WHEN MATCHED THEN DELETE``). :func:`diff_rows` counts how far
an Arrow table the engine returned is from the reference's, by the primary
key (``l_orderkey``, ``l_linenumber``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import (Any, Callable, Dict, List, Mapping, Optional, Sequence,
                    Tuple, Union)

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc

# Generator (extended below), Rows and to_arrow are what the harness and the
# control take from a table module
from benchmark.tables.lineitem import (COLUMNS, COMMENT_CHUNK, DECIMAL,  # noqa: F401
                                       LINE_STATUS, NAMES, RETURN_FLAGS,
                                       SHIP_INSTRUCT, SHIP_MODE, Rows,
                                       _comments, _decimal, _enumerated,
                                       text_pool, to_arrow)
from benchmark.tables.lineitem import Generator as _LineitemGenerator
from benchmark.tables.store_sales import in_threads

KEY = ("l_orderkey", "l_linenumber")
RF2_KEY = "o_orderkey"  # the one column of RF2's source
_COMMENT_ROWS = 1 << 23  # comments taken and compared at once
_ENUMERATED = {"l_returnflag": RETURN_FLAGS, "l_linestatus": LINE_STATUS,
               "l_shipinstruct": SHIP_INSTRUCT, "l_shipmode": SHIP_MODE}

#: a function of a refresh stream: RF1's rows, or RF2's order keys
Function = Union[Rows, np.ndarray]


def sparse_key(index: np.ndarray) -> np.ndarray:
    """Order number to the load's order key: 8 keys used, 24 skipped. A
    refresh set's new orders take the next 8 of the 32: the key plus 8."""
    index = np.asarray(index, np.int64)
    return (index // 8) * 32 + index % 8 + 1


@dataclass
class RefreshSet:
    """One pair: RF1's rows (with the stream their comments are cut from)
    and RF2's order keys."""

    rf1: Rows
    rf2: np.ndarray

    def rf2_arrow(self) -> pa.Table:
        return pa.table({RF2_KEY: pa.array(self.rf2, pa.int64())})


class Generator(_LineitemGenerator):
    """The load's generator, and the refresh sets that follow the load."""

    def refresh_set(self, base: Rows, k: int, orders: int,
                    sizes: Optional[Sequence[int]] = None) -> RefreshSet:
        """Set ``k`` (0, 1, ...) of ``orders`` orders a function. The loaded
        orders ``k * orders .. (k + 1) * orders - 1`` are RF2's; RF1's take
        the same indices on the second eighth of the key, 1 to 7 lines each
        (``sizes``, for a test that wants them so), every column by the
        load's rules from a stream of the set's own."""
        lo, hi = (int(x) for x in self.p["lines_per_order"])
        first = k * orders
        loaded = int(base.lanes["l_orderkey"][-1])
        old = sparse_key(np.arange(first, first + orders))
        if old[-1] > loaded:
            raise ValueError(f"refresh set {k} of {orders} orders reaches key "
                             f"{old[-1]}, past the load's last, {loaded}")
        sizes = (self._rng(3, k).integers(lo, hi + 1, orders, dtype=np.int32)
                 if sizes is None else np.asarray(sizes, np.int32))
        # stream (1, c) is the load's chunk c; the sets' start far above them
        lanes = self._chunk((1 << 20) + k, int(sizes.sum()), first, sizes)
        lanes["l_orderkey"] = lanes["l_orderkey"] + np.int32(8)
        return RefreshSet(Rows(lanes, (self.seed, 4, k)), old)


# -- the plain reference ---------------------------------------------------------


@dataclass
class Part:
    """Rows as the reference holds them: the numeric lanes (the enumerated
    strings as their codes) and a way to their comments."""

    lanes: Dict[str, np.ndarray]
    comments: Callable[[], pa.ChunkedArray]

    def __len__(self) -> int:
        return len(self.lanes[KEY[0]])


def part_of(rows: Rows) -> Part:
    return Part(rows.lanes, lambda: comments_of(rows))


def part_from_arrow(table: pa.Table) -> Part:
    """The rows of an Arrow table of the 16 columns (a control is given its
    data so)."""
    def compact(name: str) -> np.ndarray:
        """A lane of its own memory (not a view of the table's, which may be
        10 GB), in 32 bits where its values fit, as the generator's do."""
        lane = lane_from_arrow(table.column(name), name)
        i32 = np.iinfo(np.int32)
        if lane.dtype == np.int64 and len(lane) and (
                i32.min < lane.min() and lane.max() <= i32.max):
            return lane.astype(np.int32)
        return lane.copy()

    names = [n for n in NAMES if n != "l_comment"]
    lanes = in_threads(names, compact, workers=4)
    comments = table.column("l_comment")
    return Part(dict(zip(names, lanes)), lambda: comments)


class _Lanes(Mapping):
    """A state's numeric lanes by name, each cut from the parts when it is
    asked for and not kept: at 60M rows the fifteen of them side by side
    with the table read back would not fit the host's memory."""

    def __init__(self, parts: List[Part], keep: np.ndarray):
        self._parts, self._keep = parts, keep

    def __getitem__(self, name: str) -> np.ndarray:
        return np.concatenate([p.lanes[name] for p in self._parts])[self._keep]

    def __iter__(self):
        return iter(self._parts[0].lanes)

    def __len__(self) -> int:
        return len(self._parts[0].lanes)


@dataclass
class State:
    """What the table holds after a run of functions: the numeric lanes of
    its rows (the loaded rows that survive, in order, then each RF1's), and
    where each row came from, so that its comment can be found: ``origin``
    indexes the concatenation of ``parts`` (the load, then every RF1
    applied)."""

    lanes: Mapping  # name -> lane, see :class:`_Lanes`
    origin: np.ndarray
    parts: List[Part]

    def __len__(self) -> int:
        return len(self.origin)

    def part_comments(self) -> pa.ChunkedArray:
        """``l_comment`` of every row of every part: what ``origin``
        indexes."""
        return pa.chunked_array(
            [c for p in self.parts for c in p.comments().chunks], pa.string())

    def comments(self) -> pa.ChunkedArray:
        """``l_comment`` of the state's rows."""
        return self.part_comments().take(pa.array(self.origin))

    def to_arrow(self, columns: Sequence[str]) -> pa.Table:
        """The state's rows as the engine would return them, ``columns``
        only."""
        types = dict(COLUMNS)

        def column(name):
            if name == "l_comment":
                return self.comments()
            lane, t = self.lanes[name], types[name]
            if t == DECIMAL:
                return _decimal(lane)
            if name in _ENUMERATED:
                return _enumerated(lane.astype(np.int8), _ENUMERATED[name])
            if t == pa.date32():
                return pa.array(lane.astype(np.int32), pa.int32()).cast(t)
            return pa.array(lane.astype(t.to_pandas_dtype(), copy=False), t)

        return pa.Table.from_arrays(
            in_threads(list(columns), column),
            schema=pa.schema([pa.field(n, types[n], False) for n in columns]))


def packed_key(lanes: Mapping) -> np.ndarray:
    """The primary key as one int64: a line number is 1..7, three bits."""
    return (lanes[KEY[0]].astype(np.int64) << 3) | lanes[KEY[1]].astype(np.int64)


def comments_of(rows: Rows) -> pa.ChunkedArray:
    """The comments :func:`to_arrow` gives ``rows``, and nothing else."""
    n = len(rows)
    pool = text_pool()
    jobs = [(c, min(COMMENT_CHUNK, n - c * COMMENT_CHUNK))
            for c in range(-(-n // COMMENT_CHUNK))]
    return pa.chunked_array(in_threads(
        jobs, lambda j: _comments(rows.comment_seed, j[0], j[1], pool)),
        pa.string())


def _order_index(keys: np.ndarray) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """(sorted order keys, the order that sorts them; None where they are
    sorted as they stand, as the load's are)."""
    if len(keys) < 2 or bool((keys[1:] >= keys[:-1]).all()):
        return keys, None
    order = np.argsort(keys, kind="stable")
    return keys[order], order


def _rows_of(index, keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Every (position in ``keys``, row) whose row holds that order key."""
    sk, order = index
    lo = np.searchsorted(sk, keys, "left")
    n = np.searchsorted(sk, keys, "right") - lo
    which = np.repeat(np.arange(len(keys)), n)
    at = np.repeat(lo - (np.cumsum(n) - n), n) + np.arange(int(n.sum()))
    return which, at if order is None else order[at]


def _all_lines(rows: np.ndarray, which: np.ndarray) -> np.ndarray:
    return rows


class Refresher:
    """The loaded rows under a stream of refresh functions, one at a time.

    ``rf2_deletes(rows, which)`` names the rows an RF2 deletes among the
    held rows that match (``which``: the key's place in the list): all of
    them. A control passes another choice."""

    def __init__(self, base: Part, rf2_deletes=_all_lines):
        self.parts = [base]
        self.alive = [np.ones(len(base), bool)]
        self.index = [_order_index(base.lanes[KEY[0]])]
        self.rf2_deletes = rf2_deletes

    def rf1(self, new: Part) -> int:
        """Append each row whose order key no held row has; how many."""
        keys = new.lanes[KEY[0]]
        held = np.zeros(len(keys), bool)
        for ix, live in zip(self.index, self.alive):
            which, rows = _rows_of(ix, keys)
            held[which[live[rows]]] = True
        self.parts.append(new)
        self.alive.append(~held)
        self.index.append(_order_index(keys))
        return int((~held).sum())

    def rf2(self, keys: np.ndarray) -> int:
        """Drop every held row of the orders ``keys``; how many."""
        deleted = 0
        for ix, live in zip(self.index, self.alive):
            which, rows = _rows_of(ix, np.asarray(keys))
            keep = live[rows]
            rows = self.rf2_deletes(rows[keep], which[keep])
            live[rows] = False
            deleted += len(rows)
        return deleted

    def state(self) -> State:
        keep = np.concatenate(self.alive)
        origin = np.flatnonzero(keep)
        if len(keep) < 2**31:
            origin = origin.astype(np.int32)
        parts = list(self.parts)
        return State(_Lanes(parts, keep), origin, parts)


def ref_refresh(base: Rows, functions: Sequence[Function],
                ) -> Tuple[State, List[Tuple[int, int]]]:
    """The loaded rows after ``functions`` in order: a :class:`Rows` is an
    RF1, an array of order keys an RF2. Returns the state and each
    function's (rows inserted, rows deleted)."""
    ref = Refresher(part_of(base))
    counts = [(ref.rf1(part_of(f)), 0) if isinstance(f, Rows)
              else (0, ref.rf2(f)) for f in functions]
    return ref.state(), counts


def first_line_only(rows: np.ndarray, which: np.ndarray) -> np.ndarray:
    """What an RF2 deletes in the control ``delete_first_line_only``: of each
    order's held rows the first, as a join that stops at a key's first match
    would."""
    if not len(rows):
        return rows
    order = np.lexsort((rows, which))
    rows, which = rows[order], which[order]
    return rows[np.append(True, which[1:] != which[:-1])]


# -- the comparison ----------------------------------------------------------------


def key_index(state: State) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted primary keys, the order that sorts them)."""
    key = packed_key(state.lanes)
    order = np.argsort(key, kind="stable")
    return key[order], order


def lane_from_arrow(col, name: str) -> np.ndarray:
    """One numeric or enumerated Arrow column back to its lane: int64 keys
    and decimals (as hundredths), int32 line numbers and dates (as days),
    int8 codes of the four enumerated strings; a NULL or an unknown string
    as the type's least value, which no lane holds. A type other than the
    published one raises."""
    arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    want = dict(COLUMNS)[name]
    if arr.type != want:
        raise TypeError(f"{name}: expected {want}, got {arr.type}")
    n = len(arr)
    if name in _ENUMERATED:
        codes = pc.index_in(arr, value_set=pa.array(_ENUMERATED[name]))
        return np.asarray(codes.cast(pa.int8()).fill_null(-128))
    if want == DECIMAL:
        words = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                              count=2 * (arr.offset + n)).reshape(-1, 2)
        out = words[arr.offset:, 0].copy()
    else:
        ints = arr.cast(pa.int32()) if want == pa.date32() else arr
        out = np.asarray(ints.fill_null(0))
    if arr.null_count:
        out = out.copy()
        out[~np.asarray(arr.is_valid())] = np.iinfo(out.dtype).min
    return out


def diff_rows(got: pa.Table, want: State, index=None,
              memo: Optional[Dict[str, Any]] = None) -> Dict[str, int]:
    """How far ``got`` (any row order; the key's two columns and any others
    of the table's) is from ``want``: rows of ``want`` it lacks, rows it has
    and should not (by primary key, a second copy of a key among them), and
    cells that differ in the rows both hold. ``index`` is :func:`key_index`
    of ``want``; ``memo`` carries the pairing of rows from one call to the
    next, and is used again only where ``got`` has the same keys in the same
    order."""
    names = got.column_names
    numeric = [n for n in names if n != "l_comment"]
    lanes = dict(zip(numeric, in_threads(
        numeric, lambda n: lane_from_arrow(got.column(n), n))))
    key = packed_key(lanes)
    if memo and "key" in memo and np.array_equal(memo["key"], key):
        gi, wi, counts = memo["gi"], memo["wi"], memo["counts"]
    else:
        wk, wo = index if index is not None else key_index(want)
        go = np.argsort(key, kind="stable")
        gk = key[go]
        first = np.append(True, gk[1:] != gk[:-1])  # a key's first copy
        go, gk = go[first], gk[first]
        at = np.minimum(np.searchsorted(wk, gk), max(len(wk) - 1, 0))
        held = (wk[at] == gk) if len(wk) else np.zeros(len(gk), dtype=bool)
        gi, wi = go[held], wo[at[held]]
        counts = {"rows_missing": int(len(wk) - len(np.unique(wk[at[held]]))),
                  "rows_extra": int(len(first) - held.sum())}
        if memo is not None:
            memo.update(key=key, gi=gi, wi=wi, counts=counts)
    wrong = in_threads(numeric, lambda n: int(np.count_nonzero(
        lanes[n][gi] != want.lanes[n][wi])))
    cells_wrong = int(sum(wrong))
    if "l_comment" in names:
        col = got.column("l_comment")
        if col.type != pa.string():
            raise TypeError(f"l_comment: expected string, got {col.type}")
        whole = want.part_comments()
        for a in range(0, len(gi), _COMMENT_ROWS):  # a piece at a time: memory
            b = a + _COMMENT_ROWS
            same = pc.equal(col.take(pa.array(gi[a:b])),
                            whole.take(pa.array(want.origin[wi[a:b]])))
            cells_wrong += len(gi[a:b]) - int(
                pc.sum(same.fill_null(False)).as_py() or 0)
    return dict(counts, cells_wrong=cells_wrong)
