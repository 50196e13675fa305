"""Traffic kind ``merge_upsert``: one writer, closed loop, each request one
``MERGE ... WHEN MATCHED UPDATE * WHEN NOT MATCHED INSERT *`` of a source
made from the seed.

Parameters (the mix's file): ``source_rows``, ``existing_share``,
``condition``, ``warmup_merges`` (merged during set-up, so that the window
finds the shapes of a table that has been merged into compiled), ``sources``
(how many are made, all during set-up), ``control``.

The window ends with ``--seconds`` or with the last source, whichever comes
first (``max_requests``): ``sources`` less ``warmup_merges`` MERGEs at the
most, at any speed. The mix's file sets the two so that the key slab's next
regrow lies beyond the last source, and says so.
"""
from __future__ import annotations

import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List

from benchmark.harness.preflight import log
from benchmark.metrics.bytes import probe_least_bytes


def _source(ctx, index: int):
    p = ctx.cell.traffic
    rows = max(int(p["source_rows"] * ctx.scale), 20)
    src = ctx.gen.upsert_source(ctx.base, index, rows, p["existing_share"])
    return src, ctx.table.to_arrow(src)


def prepare(ctx) -> Dict[str, Any]:
    p = ctx.cell.traffic
    state = {"made": [], "merged": [], "reports": []}
    state["made"] = ctx.table.in_threads(
        range(int(p["sources"])), lambda i: _source(ctx, i))
    return state


def _merge(ctx, state) -> Dict[str, Any]:
    index = len(state["merged"])
    rows, arrow = state["made"][index]
    state["made"][index] = None  # its Arrow copy is not needed again
    state["merged"].append(rows)  # merged, or failed half-way: see check()
    metrics = ctx.sut.merge(arrow, ctx.cell.traffic["condition"])
    del arrow
    report = {"updated": int(metrics["numTargetRowsUpdated"]),
              "inserted": int(metrics["numTargetRowsInserted"])}
    state["reports"].append(report)
    info = {"metrics": {k: v for k, v in metrics.items()
                        if isinstance(v, (int, float))},
            "phases": ctx.sut.merge_phases(),
            "decision": ctx.sut.merge_decision()}
    slab_rows = len(ctx.base) + sum(r["inserted"]
                                    for r in state["reports"][:-1])
    info["least_bytes"] = probe_least_bytes(slab_rows, len(rows),
                                            report["updated"])
    return {"rows": len(rows), "info": info}


def warm_up(ctx, state) -> None:
    for _ in range(int(ctx.cell.traffic["warmup_merges"])):
        out = _merge(ctx, state)
        log(f"warm-up merge: decision {out['info']['decision']}, phases "
            f"{out['info']['phases']}")


def max_requests(ctx, state) -> int:
    """How many requests the window may hold: the sources set-up has left."""
    return len(state["made"]) - len(state["merged"])


def request(ctx, state, i: int) -> Dict[str, Any]:
    return _merge(ctx, state)


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """The table read back through a fresh handle against the reference's
    upsert of every source merged, warm-up included; the rows each MERGE
    said it updated and inserted against the reference's count; and the
    history: one commit for the load and one for each MERGE, in order."""
    merged: List[Any] = state["merged"]
    state["made"] = []
    t0 = time.perf_counter()
    want, counts = ctx.table.ref_upsert([ctx.base] + merged)
    index = ctx.table.key_index(want)
    log(f"check: reference upsert of {len(merged)} sources "
        f"{time.perf_counter() - t0:.1f} s")
    # read back and compared a few columns at a time, each time with the
    # key: the whole table as Arrow beside its reference does not fit the
    # host's memory. The next group is read while this one is compared.
    key = list(ctx.table.KEY)
    rest = [n for n in ctx.table.NAMES if n not in key]
    groups = [key + rest[i:i + 7] for i in range(0, len(rest), 7)]
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
            d = ctx.table.diff_rows(got, want.select(cols), index, memo)
            del got
            log(f"check: columns {cols[2]}.. waited for the read "
                f"{t1 - t0:.1f} s, compared {time.perf_counter() - t1:.1f} s")
            diff["cells_wrong"] += d["cells_wrong"]
            for k in ("rows_missing", "rows_extra"):
                diff[k] = max(diff[k], d[k])
    reports = state["reports"]
    counts_wrong = sum(
        1 for c, r in zip(counts, reports)
        if (r["updated"], r["inserted"]) != c) + abs(len(counts) - len(reports))
    versions = ctx.sut.versions()
    expect = [(0, None)] + [(v, "MERGE") for v in range(1, len(merged) + 1)]
    got = [(h["version"], h["operation"] if h["version"] else None)
           for h in versions]
    commits_wrong = sum(1 for a, b in zip(expect, got) if a != b) \
        + abs(len(expect) - len(got))
    out = {k: {"value": v, "limit": 0} for k, v in diff.items()}
    out["merge_counts_wrong"] = {"value": counts_wrong, "limit": 0}
    out["commits_wrong"] = {"value": commits_wrong, "limit": 0}
    return out
