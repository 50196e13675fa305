"""TPC-H ``LINEITEM`` at its 16 published columns: the generator and the
plain reference for query Q6.

Everything here is numpy and pyarrow; nothing is imported from the engine.
A table lives in memory as :class:`Rows`: one compact integer lane a
numeric column (dates as days since 1970-01-01, the four ``decimal(15,2)``
columns as unscaled hundredths), from which :func:`to_arrow` makes the
Arrow table the engine is given, flags, modes and comments included. The
generator makes rows from ``(seed, chunk)``; the reference answers Q6
(:func:`ref_q6`) over the lanes in integers, its bounds from
``decimal.Decimal``, and :func:`ref_q6_float_bounds` is the same with the
bounds folded in float64: what a control puts in the program's place.

Generation follows the specification's clause 4.2.3 as far as it could be
recalled offline; each rule is under ``assumed`` in the configuration's
file.
"""
from __future__ import annotations

import datetime as _dt
from dataclasses import dataclass, field
from decimal import Decimal
from typing import Any, Dict, List, Sequence, Tuple

import numpy as np
import pyarrow as pa

from benchmark.tables.store_sales import in_threads

EPOCH = _dt.date(1970, 1, 1)
CURRENT_DATE = (_dt.date(1995, 6, 17) - EPOCH).days
DECIMAL = pa.decimal128(15, 2)

# name, Arrow type
COLUMNS: List[Tuple[str, pa.DataType]] = [
    ("l_orderkey", pa.int64()),
    ("l_partkey", pa.int64()),
    ("l_suppkey", pa.int64()),
    ("l_linenumber", pa.int32()),
    ("l_quantity", DECIMAL),
    ("l_extendedprice", DECIMAL),
    ("l_discount", DECIMAL),
    ("l_tax", DECIMAL),
    ("l_returnflag", pa.string()),
    ("l_linestatus", pa.string()),
    ("l_shipdate", pa.date32()),
    ("l_commitdate", pa.date32()),
    ("l_receiptdate", pa.date32()),
    ("l_shipinstruct", pa.string()),
    ("l_shipmode", pa.string()),
    ("l_comment", pa.string()),
]
NAMES = [c[0] for c in COLUMNS]
RETURN_FLAGS = ["R", "A", "N"]
LINE_STATUS = ["O", "F"]
SHIP_INSTRUCT = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
SHIP_MODE = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
_WORDS = ("furiously carefully quickly slyly blithely fluffily ironic final "
          "regular special express pending bold even silent unusual packages "
          "deposits requests accounts foxes ideas theodolites pinto beans "
          "instructions dependencies excuses platelets asymptotes courts "
          "dolphins multipliers sleep wake are cajole haggle nag use boost "
          "affix detect integrate above across against along among the").split()


def arrow_schema() -> pa.Schema:
    return pa.schema([pa.field(n, t, False) for n, t in COLUMNS])


@dataclass
class Rows:
    """Column lanes of equal length (every numeric column, and the codes of
    the four enumerated ones); ``comment_seed`` names the stream the
    comments are cut from; ``by_year`` is the reference's own (see
    :func:`_shipped_in`)."""

    lanes: Dict[str, np.ndarray]
    comment_seed: Tuple[int, ...] = ()
    by_year: Dict[int, Tuple[np.ndarray, ...]] = field(default_factory=dict)

    def __len__(self) -> int:
        return len(self.lanes["l_shipdate"])


# -- generator -----------------------------------------------------------------


class Generator:
    """``LINEITEM`` rows from a seed, laid down order by order.

    ``params`` is the configuration file's ``table`` object: ``rows``,
    ``chunks``, ``lines_per_order``, ``order_dates`` (first day and count),
    ``parts``, ``suppliers``. The table is made in ``chunks`` equal runs of
    rows, each from its own random stream, so that they can be made side
    by side; orders are numbered as they are made, on the specification's
    sparse key (the first 8 of every 32)."""

    def __init__(self, params: Dict[str, Any], seed: int):
        self.p = params
        self.seed = int(seed)
        self.rows = int(params["rows"])

    def _rng(self, *stream: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, *stream])

    def _order_sizes(self, rng, rows: int) -> np.ndarray:
        lo, hi = (int(x) for x in self.p["lines_per_order"])
        sizes = rng.integers(lo, hi + 1, rows // lo + 1, dtype=np.int32)
        ends = np.cumsum(sizes, dtype=np.int64)
        n = int(np.searchsorted(ends, rows)) + 1
        sizes = sizes[:n].copy()
        sizes[-1] -= int(ends[n - 1] - rows)  # the last order is cut short
        return sizes

    def _chunk(self, c: int, rows: int, first_order: int,
               sizes: np.ndarray) -> Dict[str, np.ndarray]:
        rng = self._rng(1, c)
        first_day, days = (int(x) for x in self.p["order_dates"])
        parts, suppliers = int(self.p["parts"]), int(self.p["suppliers"])
        # int32 throughout: a chunk's rows, SF10's order numbers and keys and
        # a line's price in hundredths are all below 2^31
        orders = len(sizes)
        starts = np.cumsum(sizes, dtype=np.int32) - sizes
        order_of = np.repeat(np.arange(orders, dtype=np.int32), sizes)
        index = np.int32(first_order) + order_of
        orderdate = np.repeat(
            rng.integers(first_day, first_day + days, orders, dtype=np.int32),
            sizes)
        pk = rng.integers(1, parts + 1, rows, dtype=np.int32)
        quantity = rng.integers(1, 51, rows, dtype=np.int32)
        # P_RETAILPRICE in hundredths: 90000 + (key/10 mod 20001) + 100 (key mod 1000)
        retail = 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)
        shipdate = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
        receipt = shipdate + rng.integers(1, 31, rows, dtype=np.int32)
        returned = rng.integers(0, 2, rows, dtype=np.int8)  # R or A
        which = rng.integers(0, 4, rows, dtype=np.int32)
        return {
            # the sparse key: 8 orders, then 24 keys unused
            "l_orderkey": (index // 8) * 32 + index % 8 + 1,
            "l_partkey": pk,
            "l_suppkey": (pk + which * (suppliers // 4 + (pk - 1) // suppliers))
            % suppliers + 1,
            "l_linenumber": np.arange(rows, dtype=np.int32) - starts[order_of] + 1,
            "l_quantity": quantity * 100,
            "l_extendedprice": quantity * retail,
            "l_discount": rng.integers(0, 11, rows, dtype=np.int8),
            "l_tax": rng.integers(0, 9, rows, dtype=np.int8),
            "l_returnflag": np.where(receipt <= CURRENT_DATE, returned,
                                     2).astype(np.int8),
            "l_linestatus": (shipdate <= CURRENT_DATE).astype(np.int8),
            "l_shipdate": shipdate,
            "l_commitdate": orderdate + rng.integers(30, 91, rows, dtype=np.int32),
            "l_receiptdate": receipt,
            "l_shipinstruct": rng.integers(0, 4, rows, dtype=np.int8),
            "l_shipmode": rng.integers(0, 7, rows, dtype=np.int8),
        }

    def base(self) -> Rows:
        chunks = max(int(self.p["chunks"]), 1)
        per = -(-self.rows // chunks)
        counts = [min(per, self.rows - c * per) for c in range(chunks)]
        counts = [n for n in counts if n > 0]
        sizes = in_threads(list(enumerate(counts)), lambda cn: self._order_sizes(
            self._rng(0, cn[0]), cn[1]))
        firsts = np.cumsum([0] + [len(s) for s in sizes])
        made = in_threads(list(range(len(counts))), lambda c: self._chunk(
            c, counts[c], int(firsts[c]), sizes[c]))
        lanes = {n: np.concatenate([m[n] for m in made]) for n in made[0]}
        return Rows(lanes, (self.seed, 2))


# -- Arrow out --------------------------------------------------------------------


def _decimal(lane: np.ndarray) -> pa.Array:
    words = np.zeros((len(lane), 2), dtype=np.int64)  # no value is negative
    words[:, 0] = lane
    return pa.Array.from_buffers(DECIMAL, len(lane), [None, pa.py_buffer(words)],
                                 null_count=0)


def _enumerated(codes: np.ndarray, values: Sequence[str]) -> pa.Array:
    return pa.DictionaryArray.from_arrays(
        pa.array(codes, pa.int8()), pa.array(values, pa.string())
    ).cast(pa.string())


def text_pool(size: int = 1 << 20) -> np.ndarray:
    """The text the comments are cut from, as bytes: words of the
    specification's grammar, in an order of its own fixed seed."""
    rng = np.random.default_rng(19920101)
    words = [_WORDS[i] for i in rng.integers(0, len(_WORDS), size // 4)]
    return np.frombuffer(" ".join(words).encode()[:size], np.uint8)


def _comments(seed: Tuple[int, ...], chunk: int, rows: int,
              pool: np.ndarray) -> pa.Array:
    """``rows`` comments of 10 to 43 characters, each a piece of the pool."""
    rng = np.random.default_rng([*seed, chunk])
    lens = rng.integers(10, 44, rows, dtype=np.int32)
    starts = rng.integers(0, len(pool) - 43, rows, dtype=np.int32)
    offsets = np.zeros(rows + 1, np.int32)
    np.cumsum(lens, out=offsets[1:])
    index = np.repeat(starts - offsets[:-1], lens)
    index += np.arange(offsets[-1], dtype=np.int32)
    return pa.Array.from_buffers(
        pa.string(), rows, [None, pa.py_buffer(offsets),
                            pa.py_buffer(pool[index])], null_count=0)


COMMENT_CHUNK = 1 << 20


def to_arrow(rows: Rows) -> pa.Table:
    """The rows as the engine is given them: the 16 columns, typed as the
    configuration states, no NULLs."""
    n = len(rows)
    lanes = rows.lanes
    pool = text_pool()
    jobs = [(c, min(COMMENT_CHUNK, n - c * COMMENT_CHUNK))
            for c in range(-(-n // COMMENT_CHUNK))]
    comments = pa.chunked_array(in_threads(
        jobs, lambda j: _comments(rows.comment_seed, j[0], j[1], pool)),
        pa.string())

    def column(name_type):
        name, t = name_type
        lane = lanes.get(name)
        if name == "l_comment":
            return comments
        if t == DECIMAL:
            return _decimal(lane)
        if t == pa.string():
            values = {"l_returnflag": RETURN_FLAGS, "l_linestatus": LINE_STATUS,
                      "l_shipinstruct": SHIP_INSTRUCT,
                      "l_shipmode": SHIP_MODE}[name]
            return _enumerated(lane, values)
        if t == pa.date32():
            return pa.array(lane, pa.int32()).cast(t)
        return pa.array(lane.astype(t.to_pandas_dtype(), copy=False), t)

    return pa.Table.from_arrays(in_threads(COLUMNS, column),
                                schema=arrow_schema())


# -- the plain reference ---------------------------------------------------------


def days(day: _dt.date) -> int:
    return (day - EPOCH).days


def _shipped_in(rows: Rows, year: int) -> Tuple[np.ndarray, ...]:
    """(discount, quantity, extended price) of the rows shipped in ``year``,
    cut out once a year and kept: 80 parameter triples share 5 years, and
    a pass over 60M rows for each would take longer than the window."""
    if year not in rows.by_year:
        ship = rows.lanes["l_shipdate"]
        at = np.flatnonzero((ship >= days(_dt.date(year, 1, 1)))
                            & (ship < days(_dt.date(year + 1, 1, 1))))
        rows.by_year[year] = tuple(rows.lanes[n][at] for n in (
            "l_discount", "l_quantity", "l_extendedprice"))
    return rows.by_year[year]


def _q6_sum(rows: Rows, year: int, keep_discount: np.ndarray,
            quantity: int) -> Decimal:
    """sum(l_extendedprice * l_discount) over the rows shipped in ``year``
    with a kept discount and fewer than ``quantity`` units, in integers of
    1/10000; ``keep_discount[d]`` says whether a discount of d hundredths
    is inside the bounds."""
    discount, units, price = _shipped_in(rows, year)
    mask = keep_discount[discount] & (units < quantity * 100)
    total = int(np.sum(price[mask].astype(np.int64) * discount[mask]))
    return Decimal(total).scaleb(-4)


def ref_q6(rows: Rows, year: int, discount: Decimal, quantity: int) -> Decimal:
    """Q6 (specification 2.4.6) with DATE the first of January of ``year``,
    DISCOUNT and QUANTITY: the revenue as a ``Decimal`` of scale 4. The
    bounds ``DISCOUNT -+ 0.01`` are computed in ``Decimal``."""
    lo, hi = discount - Decimal("0.01"), discount + Decimal("0.01")
    keep = np.array([lo <= Decimal(d).scaleb(-2) <= hi for d in range(11)])
    return _q6_sum(rows, year, keep, quantity)


def ref_q6_float_bounds(rows: Rows, year: int, discount: Decimal,
                        quantity: int) -> Decimal:
    """The same with the bounds folded in float64 and the column compared
    as float64, the arithmetic of a lower precision than the decimal the
    configuration states: ``0.06 + 0.01`` is below 0.07 there."""
    lo, hi = float(discount) - 0.01, float(discount) + 0.01
    keep = np.array([lo <= d / 100 <= hi for d in range(11)])
    return _q6_sum(rows, year, keep, quantity)
