"""Traffic kind ``merge_refresh``: one writer, closed loop, each request one
refresh pair of TPC-H (specification 2.5-2.7) sent as two MERGE statements
through the engine's public API, ``DeltaTable.merge(...).execute()``:

    RF1  MERGE ON t.l_orderkey = s.l_orderkey WHEN NOT MATCHED THEN INSERT *
    RF2  MERGE ON t.l_orderkey = s.o_orderkey WHEN MATCHED THEN DELETE

RF1's source is the new orders' lines, RF2's a one-column table of order
keys; both come from the configuration's table module
(``Generator.refresh_set``). A request is a pair and not a function, so that
a window never ends between the two and rows per second does not move with
the parity of its last request; its ``rows`` are the two sources' rows.

Parameters (the mix's file): ``orders_per_function``, ``rf1`` and ``rf2``
(each statement's ``condition``; the clauses are the kind's), ``warmup_pairs``
(sent during set-up, so that the window finds every shape it uses compiled),
``pairs`` (how many sets are made, all during set-up), ``control``. The
window ends with ``--seconds`` or with the last set, whichever comes first
(``max_requests``). Set-up ends with :class:`RouteMissing` unless the first
pair's RF2, and both statements of every later warm-up pair, took the
resident pairs-only route.

A system under test that offers ``refresh(function, source)`` is asked
through it (the control does); the engine's table (``sut.table``) is asked
through its MERGE builder.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

from benchmark.harness.preflight import host_memory, log, release_memory
from benchmark.metrics.bytes import probe_least_bytes

# counters of the program a run is held to, over the window
ROUTE, DECLINED, FALLBACK = ("merge.resident.pairsOnly",
                             "merge.resident.pairsOnly.declined",
                             "merge.device.fallback")
# a fetch from the persistent cache is a shape set-up did not warm
COMPILES = ("device.compiles", "device.cacheFetches")
# the columns read back and compared together, each group with the key: five
# small groups, because a group's Arrow table, its lanes and the next group's
# read stand side by side with the reference in the host's 40 GiB
GROUPS = (("l_partkey", "l_suppkey", "l_quantity"),
          ("l_extendedprice", "l_discount", "l_tax"),
          ("l_returnflag", "l_linestatus", "l_shipinstruct", "l_shipmode"),
          ("l_shipdate", "l_commitdate", "l_receiptdate"),
          ("l_comment",))


class RouteMissing(RuntimeError):
    """A warm refresh pair did not take the resident pairs-only route: the
    cell measures that route and nothing else, so set-up ends here."""


def prepare(ctx) -> Dict[str, Any]:
    p = ctx.cell.traffic
    orders = max(int(p["orders_per_function"] * ctx.scale), 4)
    sets = ctx.table.in_threads(
        range(int(p["pairs"])),
        lambda k: ctx.gen.refresh_set(ctx.base, k, orders))
    made = [(s, ctx.table.to_arrow(s.rf1), s.rf2_arrow()) for s in sets]
    return {"made": made, "sent": [], "reports": [], "slab_rows": len(ctx.base),
            "counters0": None, "counters1": {}}


def _statement(ctx, function: str, source) -> Dict[str, Any]:
    if hasattr(ctx.sut, "refresh"):
        return ctx.sut.refresh(function, source)
    merge = ctx.sut.table.alias("t").merge(
        source, ctx.cell.traffic[function]["condition"], source_alias="s")
    if function == "rf1":
        return merge.when_not_matched_insert_all().execute()
    return merge.when_matched_delete().execute()


def _add(into: Dict[str, float], more: Dict[str, Any]) -> None:
    for k, v in more.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            into[k] = into.get(k, 0) + v


def _pair(ctx, state) -> Dict[str, Any]:
    index = len(state["sent"]) // 2
    rset, rf1, rf2 = state["made"][index]
    state["made"][index] = None  # the Arrow copies are not needed again
    info: Dict[str, Any] = {"metrics": {}, "phases": {}, "decisions": [],
                            "least_bytes": 0}
    for function, rows, source in (("rf1", rset.rf1, rf1),
                                   ("rf2", rset.rf2, rf2)):
        state["sent"].append(rows)  # sent, or failed half-way: see check()
        metrics = _statement(ctx, function, source)
        report = (int(metrics["numTargetRowsInserted"]),
                  int(metrics["numTargetRowsDeleted"]))
        state["reports"].append(report)
        _add(info["metrics"], metrics)
        if hasattr(ctx.sut, "merge_phases"):
            _add(info["phases"], ctx.sut.merge_phases())
            info["decisions"].append(ctx.sut.merge_decision())
        # RF1 fetches no pair (insert-only); RF2 one for each row it deletes
        info["least_bytes"] += probe_least_bytes(
            state["slab_rows"], source.num_rows, report[1])
        state["slab_rows"] += report[0]
    return {"rows": rf1.num_rows + rf2.num_rows, "info": info}


def _settle(seconds: float = 120.0) -> None:
    """Wait for what the program left running in the background (a build of
    the key slab started after a commit), so that a set-up that gives up
    ends the process with nothing in flight on the device."""
    import threading

    deadline = time.perf_counter() + seconds
    for th in threading.enumerate():
        if th.daemon and th.name.startswith("delta-merge"):
            th.join(max(deadline - time.perf_counter(), 0.0))


def warm_up(ctx, state) -> None:
    """The first RF1 builds the slab on the decode route, so the RF2 after
    it is the first statement on the resident pairs-only route; the second
    pair is the first whose RF1 builds the inverse permutation and whose RF2
    re-sorts a slab that held one. A program that does not keep the table's
    slab after the first statement ends here by itself, after one pair, and
    does not crawl through a window on the host."""
    for i in range(int(ctx.cell.traffic["warmup_pairs"])):
        before = ctx.sut.counters().get(ROUTE, 0)
        out = _pair(ctx, state)
        moved = ctx.sut.counters().get(ROUTE, 0) - before
        log(f"warm-up pair {i}: decisions {out['info']['decisions']}, phases "
            f"{out['info']['phases']}")
        if moved < min(i + 1, 2):
            _settle()
            raise RouteMissing(
                f"warm-up pair {i}: {moved} of its statements took the "
                f"resident pairs-only route ({ROUTE}), not {min(i + 1, 2)}")


def max_requests(ctx, state) -> int:
    """How many requests the window may hold: the sets set-up has left."""
    return len(state["made"]) - len(state["sent"]) // 2


def request(ctx, state, i: int) -> Dict[str, Any]:
    if state["counters0"] is None:
        state["counters0"] = ctx.sut.counters()
    out = _pair(ctx, state)
    # the window's last reading: the read-back that follows it may compile
    state["counters1"] = ctx.sut.counters()
    return out


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """The table read back through a fresh handle against the reference's
    refresh of every function sent, warm-up included; the rows each
    statement said it inserted and deleted against the reference's; the
    history: one commit for the load and one for each function; every
    statement of the window on the resident pairs-only route, none declined;
    nothing compiled in the window."""
    sent: List[Any] = state["sent"]
    state["made"] = []
    t0 = time.perf_counter()
    want, counts = ctx.table.ref_refresh(ctx.base, sent)
    index = ctx.table.key_index(want)
    log(f"check: reference refresh of {len(sent)} functions "
        f"{time.perf_counter() - t0:.1f} s; {len(want)} rows; {host_memory()}")
    # read back and compared a few columns at a time, each time with the
    # key. The next group is read while this one is compared.
    groups = [list(ctx.table.KEY) + list(g) for g in GROUPS]
    diff = {"rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}
    memo: Dict[str, Any] = {}
    with ThreadPoolExecutor(max_workers=1) as reader:
        pending = reader.submit(ctx.sut.read_all, groups[0])
        for g, cols in enumerate(groups):
            t0 = time.perf_counter()
            got = pending.result()
            t1 = time.perf_counter()
            if g + 1 < len(groups):
                pending = reader.submit(ctx.sut.read_all, groups[g + 1])
            d = ctx.table.diff_rows(got, want, index, memo)
            del got
            release_memory()
            log(f"check: columns {cols[2]}.. waited for the read "
                f"{t1 - t0:.1f} s, compared {time.perf_counter() - t1:.1f} s; "
                f"{host_memory()}")
            diff["cells_wrong"] += d["cells_wrong"]
            for k in ("rows_missing", "rows_extra"):
                diff[k] = max(diff[k], d[k])
    reports = state["reports"]
    counts_wrong = sum(1 for c, r in zip(counts, reports) if r != c) \
        + abs(len(counts) - len(reports))
    expect = [(0, None)] + [(v, "MERGE") for v in range(1, len(sent) + 1)]
    got = [(h["version"], h["operation"] if h["version"] else None)
           for h in ctx.sut.versions()]
    commits_wrong = sum(1 for a, b in zip(expect, got) if a != b) \
        + abs(len(expect) - len(got))
    before, after = state["counters0"] or {}, state["counters1"]

    def moved(*names: str) -> int:
        return sum(after.get(n, 0) - before.get(n, 0) for n in names)

    statements = 2 * sum(1 for r in requests if r.ok)
    out = {k: {"value": v, "limit": 0} for k, v in diff.items()}
    out["merge_counts_wrong"] = {"value": counts_wrong, "limit": 0}
    out["commits_wrong"] = {"value": commits_wrong, "limit": 0}
    out["statements_off_route"] = {
        "value": abs(statements - moved(ROUTE)) + moved(DECLINED, FALLBACK),
        "limit": 0}
    out["compiles_in_window"] = {"value": moved(*COMPILES), "limit": 0}
    return out
