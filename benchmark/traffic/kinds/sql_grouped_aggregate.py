"""Traffic kind ``sql_grouped_aggregate``: one client, closed loop, each
request one grouped aggregate query sent as SQL text through the engine's
public entry, ``delta_tpu.sql.parser.execute_sql``, against the table's path.

Parameters (the mix's file): ``query`` (the text, with ``{table}`` and one
substitution parameter in braces), ``parameter`` (its ``name`` and the
integers ``least``..``most`` it is drawn from, uniformly and anew for every
request from ``--seed``), ``warm_up`` (the values set-up sends),
``route_probe`` (a value that prunes every file: see :func:`warm_up`),
``reference`` (the function of the configuration's table module that answers
the query for a value, as a whole Arrow table), ``column_bytes`` (the
published widths of the columns the query reads), ``aggregates`` and
``groups`` (for the kernel's least bytes), ``control``. The reference
computes each distinct value once, after the window, and an answer is
compared with it as a whole table: schema, row order, every value.

A system under test that offers ``sql(text)`` is asked through it (the
control does); the engine's table is asked through ``execute_sql``.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark.metrics.bytes_group_aggregate import group_aggregate_least_bytes
from benchmark.traffic.kinds.sql_aggregate import (COMPILES, ROUTE_DECLINED,
                                                   ROUTE_DEVICE, _file_rows)

BLOCK = 4096  # requests whose parameters are drawn at once


class RouteMissing(RuntimeError):
    """The program answered without its device route: the cell measures
    that route and nothing else, so set-up ends here."""


def prepare(ctx) -> Dict[str, Any]:
    p = ctx.cell.traffic
    least = group_aggregate_least_bytes(_file_rows(ctx), p["column_bytes"],
                                        p["aggregates"], p["groups"])
    return {"blocks": {}, "least_bytes": least, "want": {}, "counters0": None}


def _value(ctx, state, i: int) -> int:
    """The parameter of request ``i``: from ``--seed``, block by block."""
    b = i // BLOCK
    if b not in state["blocks"]:
        p = ctx.cell.traffic["parameter"]
        rng = np.random.default_rng([ctx.seed, 4, b])
        state["blocks"][b] = rng.integers(p["least"], p["most"] + 1,
                                          BLOCK).tolist()
    return state["blocks"][b][i % BLOCK]


def _ask(ctx, value: int):
    p = ctx.cell.traffic
    text = p["query"].format(table=f"delta.`{ctx.sut.path}`",
                             **{p["parameter"]["name"]: value})
    if hasattr(ctx.sut, "sql"):
        return ctx.sut.sql(text)
    from delta_tpu.sql.parser import execute_sql

    return execute_sql(text)


def warm_up(ctx, state) -> None:
    """The route first, on a query that reads no file: a program that has no
    grouped device route answers a real one on the host, decoding seven
    columns of every row (and, where a product passes Arrow's 38 digits,
    evaluating it row by row), which at 60M rows is not a set-up anyone
    waits for. Then the values of the mix's ``warm_up``, the route checked
    once more after the first."""
    p = ctx.cell.traffic
    for n, value in enumerate([p["route_probe"]] + list(p["warm_up"])):
        before = ctx.sut.counters().get(ROUTE_DEVICE, 0)
        _ask(ctx, int(value))
        if n < 2 and ctx.sut.counters().get(ROUTE_DEVICE, 0) == before:
            raise RouteMissing(
                f"{p['parameter']['name']}={value} was not answered on the "
                f"device route ({ROUTE_DEVICE} did not move)")


def request(ctx, state, i: int) -> Dict[str, Any]:
    if state["counters0"] is None:
        state["counters0"] = ctx.sut.counters()
    value = _value(ctx, state, i)
    table = _ask(ctx, value)
    return {"rows": table.num_rows, "result": table,
            "info": {"value": value, "least_bytes": state["least_bytes"]}}


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """Every answer the window returned against the reference's for its
    value, as a whole table: schema, row order, every value to the last
    digit; every request answered on the device route; nothing compiled."""
    reference = getattr(ctx.table, ctx.cell.traffic["reference"])
    done = [r for r in requests if r.ok]
    wrong = 0
    for r in done:
        value = r.info["value"]
        if value not in state["want"]:
            state["want"][value] = reference(ctx.base, value)
        want = state["want"][value]
        wrong += not (r.result.schema.equals(want.schema)
                      and r.result.equals(want))
    before, after = state["counters0"] or {}, ctx.sut.counters()

    def moved(name: str) -> int:
        return after.get(name, 0) - before.get(name, 0)

    return {"aggregates_wrong": {"value": wrong, "limit": 0},
            "requests_off_device": {"value": len(done) - moved(ROUTE_DEVICE),
                                    "limit": 0},
            "route_declined": {"value": moved(ROUTE_DECLINED), "limit": 0},
            "compiles_in_window": {"value": moved(COMPILES), "limit": 0}}
