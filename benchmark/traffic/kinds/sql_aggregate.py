"""Traffic kind ``sql_aggregate``: one client, closed loop, each request one
aggregate query sent as SQL text through the engine's public entry,
``delta_tpu.sql.parser.execute_sql``, against the table's path.

Parameters (the mix's file): ``query`` (the text, with ``{table}``,
``{date}``, ``{discount}`` and ``{quantity}`` for the substitution
parameters), ``result_column``, ``years``, ``discounts``, ``quantities``
(each parameter's domain; one value of each is drawn uniformly and anew for
every request from ``--seed``), ``warm_up`` (the triples set-up sends),
``predicate_column_bytes`` (the published widths of the columns the query
reads, for the kernel's least bytes), ``control``. The reference
(``ref_q6`` of the configuration's table module) computes each distinct
triple once, after the window.

A system under test that offers ``sql(text)`` is asked through it (the
control does); the engine's table is asked through ``execute_sql``.
"""
from __future__ import annotations

from decimal import Decimal
from typing import Any, Dict, Tuple

import numpy as np

from benchmark.metrics.bytes_aggregate import aggregate_least_bytes

BLOCK = 4096  # requests whose parameters are drawn at once
# counters of the program a run is held to, over the window
ROUTE_DEVICE, ROUTE_DECLINED, COMPILES = (
    "scan.aggregate.device", "scan.aggregate.declined", "device.compiles")

Triple = Tuple[int, str, int]


def _file_rows(ctx):
    per = int(next(v for k, v in ctx.sut.config["layout"]["write_confs"].items()
                   if k.endswith("targetFileRows")))
    rows = len(ctx.base)
    return [min(per, rows - start) for start in range(0, rows, per)]


def prepare(ctx) -> Dict[str, Any]:
    p = ctx.cell.traffic
    least = aggregate_least_bytes(_file_rows(ctx), p["predicate_column_bytes"])
    return {"blocks": {}, "least_bytes": least, "want": {}, "counters0": None}


def _triple(ctx, state, i: int) -> Triple:
    """The parameters of request ``i``: from ``--seed``, block by block."""
    b = i // BLOCK
    if b not in state["blocks"]:
        p = ctx.cell.traffic
        rng = np.random.default_rng([ctx.seed, 3, b])
        state["blocks"][b] = (rng.choice(p["years"], BLOCK).tolist(),
                              rng.choice(p["discounts"], BLOCK).tolist(),
                              rng.choice(p["quantities"], BLOCK).tolist())
    years, discounts, quantities = state["blocks"][b]
    k = i % BLOCK
    return years[k], discounts[k], quantities[k]


def _ask(ctx, triple: Triple) -> Decimal:
    year, discount, quantity = triple
    p = ctx.cell.traffic
    text = p["query"].format(table=f"delta.`{ctx.sut.path}`",
                             date=f"{year}-01-01", discount=discount,
                             quantity=quantity)
    if hasattr(ctx.sut, "sql"):
        table = ctx.sut.sql(text)
    else:
        from delta_tpu.sql.parser import execute_sql

        table = execute_sql(text)
    return table.column(p["result_column"])[0].as_py()


def warm_up(ctx, state) -> None:
    for year, discount, quantity in ctx.cell.traffic["warm_up"]:
        _ask(ctx, (int(year), str(discount), int(quantity)))


def request(ctx, state, i: int) -> Dict[str, Any]:
    if state["counters0"] is None:
        state["counters0"] = ctx.sut.counters()
    triple = _triple(ctx, state, i)
    return {"rows": 1, "result": _ask(ctx, triple),
            "info": {"triple": triple, "least_bytes": state["least_bytes"]}}


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """Every revenue the window returned against the reference's for its
    triple, to the last digit; every request answered on the device route;
    nothing compiled."""
    done = [r for r in requests if r.ok]
    wrong = 0
    for r in done:
        triple = tuple(r.info["triple"])
        if triple not in state["want"]:
            year, discount, quantity = triple
            state["want"][triple] = ctx.table.ref_q6(
                ctx.base, year, Decimal(discount), quantity)
        wrong += r.result != state["want"][triple]
    before, after = state["counters0"] or {}, ctx.sut.counters()

    def moved(name: str) -> int:
        return after.get(name, 0) - before.get(name, 0)

    return {"revenue_wrong": {"value": wrong, "limit": 0},
            "requests_off_device": {"value": len(done) - moved(ROUTE_DEVICE),
                                    "limit": 0},
            "route_declined": {"value": moved(ROUTE_DECLINED), "limit": 0},
            "compiles_in_window": {"value": moved(COMPILES), "limit": 0}}
