"""TPC-DS ``store_sales`` at its 23 published columns: the generator and the
plain reference.

Everything here is numpy and pyarrow; nothing is imported from the engine.
A table lives in memory as :class:`Rows`: one int64-free compact lane per
column (surrogate keys and quantity as they are, ``decimal(7,2)`` measures as
integer cents), with ``NULL`` as the sentinel :data:`NULL`. The generator
makes rows from ``(seed, stream)``; the reference answers the two questions
the cells ask of the engine, an upsert (:func:`ref_upsert`) and a
conjunctive range filter (:func:`ref_filter`), over those lanes, and
:func:`diff_rows` counts how far an Arrow table the engine returned is from
the reference's answer.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

NULL = np.iinfo(np.int32).min  # no column's domain reaches it

# name, kind, nullable: kind is "id32" | "id64" | "int" | "dec" (decimal(7,2))
COLUMNS: List[Tuple[str, str, bool]] = [
    ("ss_sold_date_sk", "id32", True),
    ("ss_sold_time_sk", "id32", True),
    ("ss_item_sk", "id32", False),
    ("ss_customer_sk", "id32", True),
    ("ss_cdemo_sk", "id32", True),
    ("ss_hdemo_sk", "id32", True),
    ("ss_addr_sk", "id32", True),
    ("ss_store_sk", "id32", True),
    ("ss_promo_sk", "id32", True),
    ("ss_ticket_number", "id64", False),
    ("ss_quantity", "int", True),
    ("ss_wholesale_cost", "dec", True),
    ("ss_list_price", "dec", True),
    ("ss_sales_price", "dec", True),
    ("ss_ext_discount_amt", "dec", True),
    ("ss_ext_sales_price", "dec", True),
    ("ss_ext_wholesale_cost", "dec", True),
    ("ss_ext_list_price", "dec", True),
    ("ss_ext_tax", "dec", True),
    ("ss_coupon_amt", "dec", True),
    ("ss_net_paid", "dec", True),
    ("ss_net_paid_inc_tax", "dec", True),
    ("ss_net_profit", "dec", True),
]
NAMES = [c[0] for c in COLUMNS]
KEY = ("ss_item_sk", "ss_ticket_number")
DECIMAL = pa.decimal128(7, 2)
_ARROW = {"id32": pa.int32(), "id64": pa.int64(), "int": pa.int32(),
          "dec": DECIMAL}
# strides coprime to every item domain of a multiple of 2, 3, 5 and 17 (SF10's
# 102,000 = 2^4 * 3 * 5^3 * 17): item j of a ticket is base + j * stride
_STRIDES = np.array([7, 11, 13, 19, 23, 29, 31, 37, 41, 43, 47, 53, 59, 61,
                     67, 71], dtype=np.int64)


def arrow_schema() -> pa.Schema:
    return pa.schema([pa.field(n, _ARROW[k], nullable)
                      for n, k, nullable in COLUMNS])


@dataclass
class Rows:
    """Column lanes of equal length; int32 except the ticket number."""

    lanes: Dict[str, np.ndarray]

    def __len__(self) -> int:
        return len(self.lanes[KEY[0]])

    def take(self, idx: np.ndarray) -> "Rows":
        return Rows({n: a[idx] for n, a in self.lanes.items()})

    def slice(self, start: int, stop: int) -> "Rows":
        return Rows({n: a[start:stop] for n, a in self.lanes.items()})

    def select(self, names: Sequence[str]) -> "Rows":
        return Rows({n: self.lanes[n] for n in names})

    def packed_key(self) -> np.ndarray:
        """The primary key as one int64, ticket-major: rows arrive in ticket
        order, and a sort of keys that are all but sorted is quick."""
        return ((self.lanes[KEY[1]].astype(np.int64) << 32)
                | self.lanes[KEY[0]].astype(np.int64))


def concat(parts: Sequence[Rows]) -> Rows:
    return Rows({n: np.concatenate([p.lanes[n] for p in parts])
                 for n in parts[0].lanes})


# -- generator -----------------------------------------------------------------


def in_threads(jobs, fn, workers: int = 12):
    """numpy releases the GIL inside its loops: columns and chunks in
    threads. The result does not depend on the number of threads."""
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=min(len(jobs), workers)) as pool:
        return list(pool.map(fn, jobs))


class Generator:
    """``store_sales`` rows from a seed, laid down in ticket order.

    ``params`` is the configuration file's ``table`` object: ``rows``, the
    key domains, ``items_per_ticket``, ``null_share`` and ``chunks``. The
    table is made in ``chunks`` equal runs of rows, each from its own
    random stream over its own run of the sale dates, so that they can be
    made side by side; tickets are numbered from 1 as sales arrive, so
    ``ss_sold_date_sk`` is non-decreasing in ``ss_ticket_number``. Upsert
    sources continue the numbering on the last sale date, as the next sales
    would."""

    def __init__(self, params: Dict[str, Any], seed: int):
        self.p = params
        self.seed = int(seed)
        self.rows = int(params["rows"])
        self.base_tickets = 0  # set by base()
        self._sizes = tuple(int(x) for x in params["items_per_ticket"])

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def _ticket_sizes(self, rng, rows: int) -> np.ndarray:
        lo, hi = self._sizes
        sizes = rng.integers(lo, hi + 1, rows // lo + 1, dtype=np.int32)
        ends = np.cumsum(sizes, dtype=np.int64)
        n = int(np.searchsorted(ends, rows)) + 1
        sizes = sizes[:n].copy()
        sizes[-1] -= int(ends[n - 1] - rows)  # the last ticket is cut short
        return sizes

    def _tickets(self, rng, sizes: np.ndarray, first_ticket: int,
                 dates: Tuple[int, int]) -> Rows:
        """The rows of ``len(sizes)`` new tickets numbered from
        ``first_ticket``; sale dates uniform over ``dates`` (first, count)
        and sorted."""
        d = self.p["domains"]
        t, rows = len(sizes), int(sizes.sum())

        def per_ticket(values: np.ndarray) -> np.ndarray:
            return np.repeat(values.astype(np.int32), sizes)

        def ids(domain: int) -> np.ndarray:
            return rng.integers(1, domain + 1, t, dtype=np.int32)

        starts = np.cumsum(sizes, dtype=np.int64) - sizes
        within = np.arange(rows, dtype=np.int64) - np.repeat(starts, sizes)
        base = np.repeat(rng.integers(0, d["item"], t, dtype=np.int64), sizes)
        stride = np.repeat(_STRIDES[rng.integers(0, len(_STRIDES), t)], sizes)
        lanes = {
            "ss_sold_date_sk": per_ticket(
                dates[0] + np.sort(rng.integers(0, dates[1], t))),
            "ss_sold_time_sk": per_ticket(
                rng.integers(d["time"][0], d["time"][1] + 1, t)),
            "ss_item_sk": ((base + within * stride) % d["item"] + 1
                           ).astype(np.int32),
            "ss_customer_sk": per_ticket(ids(d["customer"])),
            "ss_cdemo_sk": per_ticket(ids(d["cdemo"])),
            "ss_hdemo_sk": per_ticket(ids(d["hdemo"])),
            "ss_addr_sk": per_ticket(ids(d["addr"])),
            "ss_store_sk": per_ticket(ids(d["store"])),
            "ss_ticket_number": np.repeat(
                np.arange(first_ticket, first_ticket + t, dtype=np.int64),
                sizes),
        }
        lanes.update(self._line_items(rng, rows))
        return Rows({n: lanes[n] for n in NAMES})

    def _line_items(self, rng, n: int) -> Dict[str, np.ndarray]:
        """What a correction of a sale changes: promotion, quantity and the
        twelve measures, in dsdgen's pricing order, in integer cents."""
        def pct(hi: int) -> np.ndarray:
            return rng.integers(0, hi + 1, n, dtype=np.int32)

        qty = rng.integers(1, 101, n, dtype=np.int32)
        wholesale = rng.integers(100, 10_001, n, dtype=np.int32)
        list_price = wholesale * (100 + pct(200)) // 100
        sales = list_price * (100 - pct(100)) // 100
        ext_sales = sales * qty
        ext_wholesale = wholesale * qty
        # a product of two amounts passes int32; a percentage of one does not
        ext_tax = (ext_sales.astype(np.int64) * pct(9) // 100).astype(np.int32)
        coupon = np.where(
            pct(99) < 20,
            (ext_sales.astype(np.int64) * pct(100) // 100).astype(np.int32),
            np.int32(0))
        net_paid = ext_sales - coupon
        return {
            "ss_promo_sk": rng.integers(
                1, self.p["domains"]["promo"] + 1, n, dtype=np.int32),
            "ss_quantity": qty,
            "ss_wholesale_cost": wholesale,
            "ss_list_price": list_price,
            "ss_sales_price": sales,
            "ss_ext_discount_amt": (list_price - sales) * qty,
            "ss_ext_sales_price": ext_sales,
            "ss_ext_wholesale_cost": ext_wholesale,
            "ss_ext_list_price": list_price * qty,
            "ss_ext_tax": ext_tax,
            "ss_coupon_amt": coupon,
            "ss_net_paid": net_paid,
            "ss_net_paid_inc_tax": net_paid + ext_tax,
            "ss_net_profit": net_paid - ext_wholesale,
        }

    def _nulls(self, rng, rows: Rows, names: Sequence[str]) -> None:
        n = len(rows)
        k = int(round(n * float(self.p["null_share"])))
        for name in names:
            rows.lanes[name][rng.integers(0, n, k)] = NULL

    def base(self) -> Rows:
        """The table as loaded: ``rows`` rows over every sale date."""
        chunks = int(self.p["chunks"])
        first, count = self.p["domains"]["date"]
        cuts = [self.rows * c // chunks for c in range(chunks + 1)]
        days = [count * c // chunks for c in range(chunks + 1)]
        sizes = in_threads(range(chunks), lambda c: self._ticket_sizes(
            self._rng(0, c, 0), cuts[c + 1] - cuts[c]))
        firsts = np.cumsum([1] + [len(s) for s in sizes])
        self.base_tickets = int(firsts[-1] - 1)
        nullable = [n for n, _, nullable in COLUMNS if nullable]
        out = Rows({n: np.empty(self.rows, np.int64 if k == "id64"
                                else np.int32) for n, k, _ in COLUMNS})

        def make(c: int) -> None:
            rng = self._rng(0, c, 1)
            part = self._tickets(rng, sizes[c], int(firsts[c]),
                                 (first + days[c], days[c + 1] - days[c]))
            self._nulls(rng, part, nullable)
            for n in NAMES:
                out.lanes[n][cuts[c]:cuts[c + 1]] = part.lanes[n]

        in_threads(range(chunks), make)
        return out

    def upsert_source(self, base: Rows, index: int, rows: int,
                      existing_share: float) -> Rows:
        """Source ``index`` of an upsert stream: ``existing_share`` of its
        rows re-state line items of ``base`` drawn uniformly without
        replacement (the sale's own columns kept; promotion, quantity and
        measures drawn anew), the rest are fresh tickets on the last sale
        date, numbered after every earlier source's. Shuffled, every key
        once."""
        rng = self._rng(1, index)
        n_old = int(rows * existing_share)
        n_new = rows - n_old
        old = base.take(rng.choice(len(base), n_old, replace=False))
        redrawn = self._line_items(rng, n_old)
        old.lanes.update(redrawn)
        first, count = self.p["domains"]["date"]
        # room for the most tickets a source can hold, so that a source's
        # numbers do not depend on the sources before it
        stride = n_new // self._sizes[0] + 1
        new = self._tickets(rng, self._ticket_sizes(rng, n_new),
                            self.base_tickets + 1 + index * stride,
                            (first + count - 1, 1))
        out = concat([old, new])
        self._nulls(rng, out, list(redrawn))
        return out.take(rng.permutation(rows))


# -- Arrow in and out ----------------------------------------------------------


def _validity(valid: np.ndarray):
    return pa.py_buffer(np.packbits(valid, bitorder="little"))


def _column_to_arrow(lane: np.ndarray, kind: str, nullable: bool):
    n = len(lane)
    valid = None
    if nullable:
        valid = lane != NULL
        valid = None if valid.all() else valid
    bitmap = None if valid is None else _validity(valid)
    if kind == "dec":
        words = np.empty((n, 2), dtype=np.int64)
        words[:, 0] = lane
        words[:, 1] = lane >> 31  # sign extension: 0 or -1
        data = pa.py_buffer(words)
    else:
        data = pa.py_buffer(np.ascontiguousarray(lane))
    return pa.Array.from_buffers(
        _ARROW[kind], n, [bitmap, data],
        null_count=0 if valid is None else int(n - valid.sum()))


def to_arrow(rows: Rows) -> pa.Table:
    """The rows as the engine is given them: int32, int64, decimal(7,2)."""
    arrays = in_threads(COLUMNS, lambda c: _column_to_arrow(rows.lanes[c[0]],
                                                       c[1], c[2]))
    return pa.Table.from_arrays(arrays, schema=arrow_schema())


def lane_from_arrow(col, kind: str) -> np.ndarray:
    """One Arrow column back to its lane (cents, NULL as the sentinel)."""
    arr = col.combine_chunks() if isinstance(col, pa.ChunkedArray) else col
    n = len(arr)
    if arr.type != _ARROW[kind]:
        raise TypeError(f"expected {_ARROW[kind]}, got {arr.type}")
    if kind == "dec":
        words = np.frombuffer(arr.buffers()[1], dtype=np.int64,
                              count=2 * (arr.offset + n)).reshape(-1, 2)
        out = words[arr.offset:, 0].astype(np.int32)
    else:
        out = np.array(arr.fill_null(0).to_numpy(zero_copy_only=False))
    if arr.null_count:
        out[~arr.is_valid().to_numpy(zero_copy_only=False)] = NULL
    return out


# -- the plain reference -------------------------------------------------------


def ref_upsert(writes: Sequence[Rows]) -> Tuple[Rows, List[Tuple[int, int]]]:
    """Reference for a run of MERGE ... WHEN MATCHED UPDATE * WHEN NOT
    MATCHED INSERT *: ``writes`` is the loaded table and then each source
    in the order merged. Concatenate, the last write of a key wins. Returns
    the table, and for each source the rows it (updated, inserted): a key's
    first write inserts it and every later one updates it."""
    key = np.concatenate([w.packed_key() for w in writes])
    order = np.argsort(key, kind="stable")
    key = key[order]
    new_key = np.append(True, key[1:] != key[:-1])
    keep = np.zeros(len(key), dtype=bool)
    keep[order[np.append(new_key[1:], True)]] = True  # each key's last write
    ends = np.cumsum([len(w) for w in writes])
    inserted = np.bincount(np.searchsorted(ends, order[new_key], side="right"),
                           minlength=len(writes))
    counts = [(len(w) - int(i), int(i))
              for w, i in zip(writes[1:], inserted[1:])]
    del key, order, new_key
    names = list(writes[0].lanes)
    lanes = in_threads(names, lambda n: np.concatenate(
        [w.lanes[n] for w in writes])[keep], workers=4)
    return Rows(dict(zip(names, lanes))), counts


_OPS = {">=": np.greater_equal, "<=": np.less_equal, ">": np.greater,
        "<": np.less, "=": np.equal}


def ref_filter(state: Rows, terms: Sequence[Tuple[str, str, int]],
               columns: Sequence[str]) -> Rows:
    """Reference scan: rows where every ``(column, op, literal)`` holds
    (a NULL holds nothing), projected to ``columns``."""
    keep = np.ones(len(state), dtype=bool)
    for col, op, value in terms:
        lane = state.lanes[col]
        keep &= _OPS[op](lane, value) & (lane != NULL)
    idx = np.flatnonzero(keep)
    return Rows({c: state.lanes[c][idx] for c in columns})


def diff_rows(got: pa.Table, want: Rows, index=None,
              memo: Optional[Dict[str, Any]] = None) -> Dict[str, int]:
    """How far ``got`` (any row order) is from ``want``: rows of ``want``
    it lacks, rows it has and should not (by primary key, a second copy of
    a key among them), and cells that differ in the rows both hold.
    ``want`` names the columns, the key's among them; a missing column or
    another type than the published one raises. ``index`` is
    :func:`key_index` of ``want``, for a caller that compares it twice;
    ``memo`` carries the pairing of rows from one such call to the next,
    and is used again only where ``got`` has the same keys in the same
    order."""
    kinds = {n: k for n, k, _ in COLUMNS}
    names = list(want.lanes)
    lanes = in_threads(names, lambda n: lane_from_arrow(got.column(n), kinds[n]))
    got_rows = Rows(dict(zip(names, lanes)))
    key = got_rows.packed_key()
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
    wrong = in_threads(names, lambda n: int(np.count_nonzero(
        got_rows.lanes[n][gi] != want.lanes[n][wi])))
    return dict(counts, cells_wrong=int(sum(wrong)))


def key_index(rows: Rows) -> Tuple[np.ndarray, np.ndarray]:
    """(sorted keys, the order that sorts them)."""
    key = rows.packed_key()
    order = np.argsort(key, kind="stable")
    return key[order], order
