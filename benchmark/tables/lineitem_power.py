"""TPC-H ``LINEITEM`` under the power test's stream: RF1, the stream's
queries, RF2, on one table. The table and its refresh sets are
:mod:`benchmark.tables.lineitem_refresh`'s (generator, ``Refresher``,
``State`` and the comparison, imported, not copied); Q6's reference is
:mod:`benchmark.tables.lineitem`'s and Q1's sums
:mod:`benchmark.tables.lineitem_pricing`'s. What is new here is the
reference that follows the table's state through a stream of statements of
both kinds (:func:`ref_power`).

Everything here is numpy and pyarrow; nothing is imported from the engine.

A query's answer at a point of the stream is the answer over the rows the
table holds there: the loaded rows no acknowledged RF2 has named, and every
row an acknowledged RF1 inserted (and no RF2 named since). Q6's sum and
Q1's sums and counts are additive over rows, so

    answer(state) = answer(loaded) - answer(loaded rows deleted)
                    + answer(inserted rows held)

in exact integers: the 60M loaded rows are passed over once a parameter
value (the two references keep that, :func:`lineitem._shipped_in`,
:func:`lineitem_pricing._prepared`), and a state costs a pass over the few
hundred thousand rows it differs by. Q1's means are formed last, from the
combined sums and counts (``lineitem_pricing._table``); a group the state
holds no row of is not in the answer.
"""
from __future__ import annotations

from decimal import Decimal
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np
import pyarrow as pa

from benchmark.tables.lineitem import ref_q6
from benchmark.tables.lineitem_pricing import (_add_into, _sums, _sums_upto,
                                               _table, exact)
from benchmark.tables.lineitem_pricing import LAST_SHIPDATE
# Generator, Rows and to_arrow are what the harness takes from a table
# module; the rest is what the refresh half of the comparison and the
# control take
from benchmark.tables.lineitem_refresh import (KEY, NAMES, RF2_KEY,  # noqa: F401
                                               Generator, Part, Refresher,
                                               Rows, State, diff_rows,
                                               in_threads, key_index,
                                               part_from_arrow, part_of,
                                               ref_refresh, to_arrow)

#: the lanes the two queries read
QUERY_LANES = ("l_shipdate", "l_quantity", "l_extendedprice", "l_discount",
               "l_tax", "l_returnflag", "l_linestatus")

#: one statement of a stream: ``("rf1", Rows)``, ``("rf2", order keys)``,
#: ``("q6", (year, discount, quantity))`` or ``("q1", delta)``
Step = Tuple[str, Any]


def _cut(lanes, at) -> Dict[str, np.ndarray]:
    return {n: lanes[n][at] for n in QUERY_LANES}


class StreamRef:
    """The loaded rows under a stream of refresh functions and queries, one
    statement at a time: a :class:`Refresher`, and each query answered over
    the state it finds. ``honours_deletes=False`` is the control's: its
    queries count every loaded row, deleted or not."""

    def __init__(self, base: Rows, part: Optional[Part] = None,
                 honours_deletes: bool = True):
        self.base = base
        self.ref = Refresher(part_of(base) if part is None else part)
        self.honours_deletes = honours_deletes
        self._differs: Optional[Tuple[Rows, Rows]] = None

    def rf1(self, new: Part) -> int:
        self._differs = None
        return self.ref.rf1(new)

    def rf2(self, keys: np.ndarray) -> int:
        self._differs = None
        return self.ref.rf2(np.asarray(keys))

    def state(self) -> State:
        return self.ref.state()

    def differs(self) -> Tuple[Rows, Rows]:
        """``(gone, new)``: the loaded rows the state no longer holds, and
        the inserted rows it holds; cut once a state."""
        if self._differs is None:
            ref = self.ref
            gone = _cut(ref.parts[0].lanes, np.flatnonzero(~ref.alive[0]))
            held = [_cut(p.lanes, live)
                    for p, live in zip(ref.parts[1:], ref.alive[1:])]
            new = {n: np.concatenate([h[n] for h in held]) if held
                   else gone[n][:0] for n in QUERY_LANES}
            self._differs = Rows(gone), Rows(new)
        return self._differs

    def q6(self, year: int, discount: Decimal, quantity: int) -> Decimal:
        gone, new = self.differs()
        total = ref_q6(self.base, year, discount, quantity) \
            + ref_q6(new, year, discount, quantity)
        if self.honours_deletes:
            total -= ref_q6(gone, year, discount, quantity)
        return total

    def q1(self, delta: int) -> pa.Table:
        gone, new = self.differs()
        cutoff = LAST_SHIPDATE - int(delta)
        total = {g: list(v) for g, v in _sums(self.base, delta, exact).items()}
        for rows, sign in ((new, 1), (gone, -1 if self.honours_deletes else 0)):
            if not sign or not len(rows):
                continue
            for g, (count, *sums) in _sums_upto(rows, cutoff, exact).items():
                _add_into(total, g, sign * count, [sign * s for s in sums])
        return _table({g: v for g, v in total.items() if v[0]})

    def answer(self, kind: str, what) -> Any:
        if kind == "q6":
            year, discount, quantity = what
            return self.q6(int(year), Decimal(discount), int(quantity))
        return self.q1(int(what))


def ref_power(base: Rows, steps: Sequence[Step],
              ) -> Tuple[List[Any], State, List[Tuple[int, int]]]:
    """The loaded rows under ``steps`` in order. Returns every query's
    answer in order (Q6: the revenue, a ``Decimal`` of scale 4; Q1: the
    whole Arrow table), the state after the last step, and each refresh
    function's (rows inserted, rows deleted)."""
    stream = StreamRef(base)
    answers: List[Any] = []
    counts: List[Tuple[int, int]] = []
    for kind, what in steps:
        if kind == "rf1":
            counts.append((stream.rf1(part_of(what)), 0))
        elif kind == "rf2":
            counts.append((0, stream.rf2(what)))
        else:
            answers.append(stream.answer(kind, what))
    return answers, stream.state(), counts


def same_answer(kind: str, got, want) -> bool:
    """Q6 to the last digit; Q1 as a whole table: schema, row order, every
    value."""
    if kind == "q6":
        return got == want
    return isinstance(got, pa.Table) and got.schema.equals(want.schema) \
        and got.equals(want)
