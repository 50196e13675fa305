"""TPC-H ``LINEITEM`` under query Q1, the pricing summary report: the table
of :mod:`benchmark.tables.lineitem` (its generator and its Arrow form,
imported, not copied) and the plain reference for Q1.

Everything here is numpy and pyarrow; nothing is imported from the engine.
:func:`ref_q1` answers Q1 (specification 2.4.1.2) over the lanes of
:class:`Rows`: the masks and the sums a group in integers of the unscaled
values (int64 inside a chunk, the chunks added in Python integers), the
results as ``decimal.Decimal`` in an Arrow table of the ten columns, typed
and ordered as the query asks. :func:`ref_q1_float_sums` is the same with
every sum accumulated in float64 and converted back: what a control puts in
the program's place.

Departures from the specification's text: the table reference is
``delta.`<path>``` (the engine's catalog is not set up by the benchmark); a
mean is typed ``decimal(15,2)`` and is the exact quotient rounded half away
from zero, the engine's stated semantic (the specification's answer set
rounds to two digits as well).

A window asks for up to 61 values of ``DELTA`` and a pass over 60M rows for
each would take longer than the window, so the rows are added up once
(:func:`_prepared`): those shipped up to the earliest cutoff, and a sum a
day and a group for the 60 days after it.
"""
from __future__ import annotations

import datetime as _dt
from decimal import Decimal
from typing import Callable, Dict, List, Tuple

import numpy as np
import pyarrow as pa

# Generator, Rows and to_arrow are what the harness and the control take from
# a table module
from benchmark.tables.lineitem import (LINE_STATUS, RETURN_FLAGS, Generator,  # noqa: F401
                                       Rows, days, to_arrow)
from benchmark.tables.store_sales import in_threads

LAST_SHIPDATE = days(_dt.date(1998, 12, 1))
DELTA = (60, 120)  # the substitution parameter's domain (2.4.1.3)
CHUNK = 1 << 21
#: the summed expressions, in unscaled units, and the scale of each
SUMS = (("sum_qty", 2), ("sum_base_price", 2), ("sum_disc_price", 4),
        ("sum_charge", 6), ("sum_disc", 2))
COLUMNS: List[Tuple[str, pa.DataType]] = [
    ("l_returnflag", pa.string()), ("l_linestatus", pa.string()),
    ("sum_qty", pa.decimal128(38, 2)), ("sum_base_price", pa.decimal128(38, 2)),
    ("sum_disc_price", pa.decimal128(38, 4)), ("sum_charge", pa.decimal128(38, 6)),
    ("avg_qty", pa.decimal128(15, 2)), ("avg_price", pa.decimal128(15, 2)),
    ("avg_disc", pa.decimal128(15, 2)), ("count_order", pa.int64())]

Add = Callable[[np.ndarray], float]


def exact(values: np.ndarray) -> int:
    return int(values.sum(dtype=np.int64))


def in_float64(values: np.ndarray) -> float:
    return float(values.astype(np.float64).sum())


def _group_of(lanes, at) -> np.ndarray:
    """A row's group, 0..5: return flag x line status, as the generator
    numbers them."""
    return lanes["l_returnflag"][at] * np.int8(len(LINE_STATUS)) \
        + lanes["l_linestatus"][at]


def _values(lanes, at) -> Tuple[np.ndarray, ...]:
    """The five summed expressions of the rows ``at``, in int64: quantity and
    price in 1/100, ``price * (1 - discount)`` in 1/10^4 (``100 - d`` over
    hundredths), ``price * (1 - discount) * (1 + tax)`` in 1/10^6, the
    discount in 1/100."""
    price = lanes["l_extendedprice"][at].astype(np.int64)
    discount = lanes["l_discount"][at].astype(np.int64)
    disc_price = price * (100 - discount)
    return (lanes["l_quantity"][at].astype(np.int64), price, disc_price,
            disc_price * (100 + lanes["l_tax"][at].astype(np.int64)), discount)


def _add_into(into: Dict[int, list], group: int, count: int, sums) -> None:
    have = into.setdefault(group, [0] * (1 + len(SUMS)))
    have[0] += count
    for i, s in enumerate(sums):
        have[1 + i] += s


def _sums_upto(rows: Rows, cutoff: int, add: Add) -> Dict[int, list]:
    """``{group: [count, the five sums]}`` over the rows shipped on or before
    day ``cutoff``, a chunk at a time."""
    lanes, n = rows.lanes, len(rows)

    def chunk(start: int):
        at = slice(start, min(start + CHUNK, n))
        keep = lanes["l_shipdate"][at] <= cutoff
        group, values = _group_of(lanes, at), _values(lanes, at)
        out = {}
        for g in range(len(RETURN_FLAGS) * len(LINE_STATUS)):
            sel = keep & (group == g)
            count = int(np.count_nonzero(sel))
            if count:
                out[g] = (count, [add(v[sel]) for v in values])
        return out

    total: Dict[int, list] = {}
    for part in in_threads(list(range(0, n, CHUNK)), chunk):
        for g, (count, sums) in part.items():
            _add_into(total, g, count, sums)
    return total


def _prepared(rows: Rows, add: Add):
    """``(early, by_day)``: the sums of the rows shipped up to the earliest
    cutoff of the parameter's domain, and ``by_day[d]`` those of the rows
    shipped ``d + 1`` days after it, for the 60 days up to the latest. Kept
    on the rows (``Rows.by_year`` is the reference's own store)."""
    key = ("q1", add.__name__)
    if key not in rows.by_year:
        first = LAST_SHIPDATE - DELTA[1]
        ship = rows.lanes["l_shipdate"]
        at = np.flatnonzero((ship > first) & (ship <= LAST_SHIPDATE - DELTA[0]))
        day = (ship[at] - (first + 1)).astype(np.int64)
        group, values = _group_of(rows.lanes, at), _values(rows.lanes, at)
        by_day: List[Dict[int, list]] = [{} for _ in range(DELTA[1] - DELTA[0])]
        order = np.argsort(day * 8 + group, kind="stable")
        cell = (day * 8 + group)[order]
        edges = np.flatnonzero(np.diff(cell, prepend=-1, append=1 << 40))
        for a, b in zip(edges[:-1], edges[1:]):
            rows_of = order[a:b]
            _add_into(by_day[int(cell[a]) // 8], int(cell[a]) % 8, int(b - a),
                      [add(v[rows_of]) for v in values])
        rows.by_year[key] = (_sums_upto(rows, first, add), by_day)
    return rows.by_year[key]


def _sums(rows: Rows, delta: int, add: Add) -> Dict[int, list]:
    cutoff = LAST_SHIPDATE - int(delta)
    if not DELTA[0] <= delta <= DELTA[1]:
        return _sums_upto(rows, cutoff, add)
    early, by_day = _prepared(rows, add)
    total = {g: list(v) for g, v in early.items()}
    for part in by_day[:DELTA[1] - int(delta)]:
        for g, v in part.items():
            _add_into(total, g, v[0], v[1:])
    return total


def _mean(total: int, count: int, scale: int) -> Decimal:
    """``total / count`` of unscaled units, rounded half away from zero at
    the units' own scale."""
    q, r = divmod(abs(total), count)
    units = (q + (2 * r >= count)) * (1 if total >= 0 else -1)
    return Decimal(units).scaleb(-scale)


def _table(sums: Dict[int, list]) -> pa.Table:
    """The answer: a row a group that has rows, ordered by return flag and
    line status, the ten columns typed as the query's."""
    rows = []
    for g, (count, *totals) in sums.items():
        qty, price, disc_price, charge, disc = (int(round(t)) for t in totals)
        rows.append((RETURN_FLAGS[g // len(LINE_STATUS)],
                     LINE_STATUS[g % len(LINE_STATUS)],
                     Decimal(qty).scaleb(-2), Decimal(price).scaleb(-2),
                     Decimal(disc_price).scaleb(-4), Decimal(charge).scaleb(-6),
                     _mean(qty, count, 2), _mean(price, count, 2),
                     _mean(disc, count, 2), count))
    rows.sort(key=lambda r: r[:2])
    return pa.Table.from_arrays(
        [pa.array([r[i] for r in rows], t) for i, (_n, t) in enumerate(COLUMNS)],
        names=[n for n, _t in COLUMNS])


def ref_q1(rows: Rows, delta: int) -> pa.Table:
    """Q1 (specification 2.4.1.2) with ``DELTA`` days: the pricing summary of
    the rows shipped on or before 1998-12-01 less ``delta`` days, exact."""
    return _table(_sums(rows, delta, exact))


def ref_q1_float_sums(rows: Rows, delta: int) -> pa.Table:
    """The same with every sum accumulated in float64 and converted back,
    the arithmetic of a lower precision than the decimal the configuration
    states. A float64 holds integers up to 2^53 = 9.0e15: a group's
    ``sum_charge`` passes it from about 250,000 rows on (3.7e10 units a
    row) and is 5e17 at SF10."""
    return _table(_sums(rows, delta, in_float64))
