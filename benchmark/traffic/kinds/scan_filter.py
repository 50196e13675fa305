"""Traffic kind ``scan_filter``: one reader, closed loop, each request one
filtered, projected scan: a window of sale dates and a band of quantities.

Parameters (the mix's file): ``windows`` (how many queries the pool holds),
``pool_seed``, ``window_days``, ``quantity_buckets``, ``quantity_span``,
``columns``, ``control``. Each query of the pool draws its first day
uniformly over the sale dates that leave room for the window, and its
quantity bucket uniformly, as TPC-DS substitutes a query's parameters; the
draw is from ``pool_seed``, so the pool is the same for every ``--seed``.
``--seed`` draws the order: requests go through the pool in rounds, each
round a fresh random permutation of it, so every seed sends the same queries
equally often in another order, and nothing about reuse is built in.

Why the pool is fixed: the program builds one XLA program for every set of
literals (``ops/column_cache._mask_kernel`` keys on the expression). Literals
drawn from ``--seed`` would compile in every run of every check and inside
the window, which the benchmark's contract forbids; so set-up runs each query
of the pool once (a checkout's first run compiles them). What a reader with
fresh literals pays is therefore in no cell yet: PERF.md, Open questions.
"""
from __future__ import annotations

from typing import Any, Dict, List

import numpy as np

from benchmark.metrics.bytes import mask_least_bytes


def _pool(ctx) -> List[Dict[str, Any]]:
    p = ctx.cell.traffic
    first, count = ctx.cell.config["table"]["domains"]["date"]
    days, n = int(p["window_days"]), int(p["windows"])
    buckets, span = p["quantity_buckets"], int(p["quantity_span"])
    rng = np.random.default_rng(int(p["pool_seed"]))
    starts = first + rng.integers(0, count - days + 1, n)
    lows = rng.choice(buckets, n)
    pool = []
    for d, q in zip(starts.tolist(), lows.tolist()):
        terms = [("ss_sold_date_sk", ">=", d),
                 ("ss_sold_date_sk", "<=", d + days - 1),
                 ("ss_quantity", ">=", q),
                 ("ss_quantity", "<=", q + span - 1)]
        pool.append({"terms": terms,
                     "filters": [" AND ".join(f"{c} {op} {v}"
                                              for c, op, v in terms)]})
    return pool


def _file_date_ranges(ctx):
    """(rows, first date, last date) of each file, from the way the load
    cuts the rows: in order, ``targetFileRows`` to a file."""
    per = int(next(v for k, v in ctx.sut.config["layout"]["write_confs"].items()
                   if k.endswith("targetFileRows")))
    dates = ctx.base.lanes["ss_sold_date_sk"]
    out = []
    for start in range(0, len(dates), per):
        d = dates[start:start + per]
        d = d[d != ctx.table.NULL]
        out.append((min(per, len(dates) - start), int(d.min()), int(d.max())))
    return out


def prepare(ctx) -> Dict[str, Any]:
    pool = _pool(ctx)
    files = _file_date_ranges(ctx)
    for q in pool:
        lo, hi = q["terms"][0][2], q["terms"][1][2]
        rows = [n for n, a, b in files if a <= hi and b >= lo]
        q["least_bytes"] = mask_least_bytes(rows, [4, 4])  # two int32 columns
    return {"pool": pool, "rounds": {}, "want": {}}


def _round(ctx, state, r: int) -> np.ndarray:
    """The order of round ``r``: a permutation of the pool, from the seed."""
    if r not in state["rounds"]:
        state["rounds"][r] = np.random.default_rng(
            [ctx.seed, 2, r]).permutation(len(state["pool"]))
    return state["rounds"][r]


def _scan(ctx, q) -> Any:
    return ctx.sut.scan(q["filters"], ctx.cell.traffic["columns"])


def warm_up(ctx, state) -> None:
    """Every query once, in an order of its own: each compiles its mask and
    brings its lanes to the device."""
    for k in np.random.default_rng([ctx.seed, 1]).permutation(len(state["pool"])):
        _scan(ctx, state["pool"][int(k)])


def request(ctx, state, i: int) -> Dict[str, Any]:
    n = len(state["pool"])
    k = int(_round(ctx, state, i // n)[i % n])
    q = state["pool"][k]
    got = _scan(ctx, q)
    return {"rows": got.num_rows, "result": got,
            "info": {"query": k, "least_bytes": q["least_bytes"]}}


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """Every scan the window finished against the reference's rows for its
    query."""
    total = {"rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}
    wrong = 0
    for r in requests:
        if not r.ok:
            continue
        k = r.info["query"]
        if k not in state["want"]:
            want = ctx.table.ref_filter(ctx.base, state["pool"][k]["terms"],
                                        ctx.cell.traffic["columns"])
            state["want"][k] = (want, ctx.table.key_index(want))
        diff = ctx.table.diff_rows(r.result, *state["want"][k])
        wrong += any(diff.values())
        for key, v in diff.items():
            total[key] += v
    out = {k: {"value": v, "limit": 0} for k, v in total.items()}
    out["scans_wrong"] = {"value": wrong, "limit": 0}
    return out
