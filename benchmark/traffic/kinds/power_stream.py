"""Traffic kind ``power_stream``: one client, closed loop, each request one
stream of TPC-H's power test (specification 5.3.3, as remembered) on the one
table the benchmark holds, every statement through the engine's public API:

    RF1   MERGE ON t.l_orderkey = s.l_orderkey WHEN NOT MATCHED THEN INSERT *
    22 queries as SQL text through ``execute_sql``: Q6, Q1, Q6, Q1, ...
    RF2   MERGE ON t.l_orderkey = s.o_orderkey WHEN MATCHED THEN DELETE

The two MERGEs are kind ``merge_refresh``'s (its statements, its route, its
read-back of the whole table afterwards: imported, not copied); the queries
are the texts of the mixes ``q6`` and ``q1``, each execution with
substitution parameters of its own, drawn from ``--seed`` and the stream's
number. A request is a whole stream, so a window never ends inside one and
every query of it meets the state the stream's RF1 left; its ``rows`` are
the two MERGEs' source rows, so ``merge_rows_per_s`` is refresh rows through
the whole stream a second.

Parameters (the mix's file): ``orders_per_function``, ``rf1`` and ``rf2``
(each statement's ``condition``), ``queries`` (the sequence of templates a
stream runs, by name), ``q6`` and ``q1`` (each template's ``query`` text and
its parameters' domains), ``warmup_streams`` (sent during set-up),
``streams`` (how many refresh sets are made, all during set-up),
``control``; ``q1.route_probe`` is a value of Q1's parameter that prunes
every file, sent first. The window ends with ``--seconds`` or with the last set
(``max_requests``). Set-up ends with :class:`RouteMissing` as soon as a
query is answered off the device route (a program without it would run Q1 on
the host over 60M rows), or unless the first stream's RF2 and both MERGEs of
every later warm-up stream took the resident pairs-only route. The run ends
with :class:`ProgramsCold`, and no result, when the window's first stream
compiles a program.

A system under test that offers ``refresh(function, source)`` and
``sql(text)`` is asked through them (the control does).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmark.harness.preflight import host_memory, log
from benchmark.traffic.kinds import merge_refresh
from benchmark.traffic.kinds.merge_refresh import (COMPILES, ROUTE,
                                                   RouteMissing, _add,
                                                   _settle, _statement)
from benchmark.traffic.kinds.sql_aggregate import ROUTE_DECLINED, ROUTE_DEVICE

Query = Tuple[str, Any]  # ("q6", (year, discount, quantity)) | ("q1", delta)


class ProgramsCold(RuntimeError):
    """The window's first stream compiled a program, or fetched one from the
    compile cache: set-up's two streams have to leave every program of the
    window compiled, and only a commit beyond them shows a program that is
    keyed on what a stream changes (the table's file count, say). Such a
    program compiles at every stream and would spend the window's 30 s on a
    fraction of its streams: not what the cell measures, so the run ends
    here, as with :class:`RouteMissing`, and prints no result."""


def prepare(ctx) -> Dict[str, Any]:
    p = ctx.cell.traffic
    orders = max(int(p["orders_per_function"] * ctx.scale), 4)
    sets = ctx.table.in_threads(
        range(int(p["streams"])),
        lambda k: ctx.gen.refresh_set(ctx.base, k, orders))
    made = [(s, ctx.table.to_arrow(s.rf1), s.rf2_arrow()) for s in sets]
    # sent, reports, counters0, counters1: what `merge_refresh.check` reads;
    # steps: every statement in order, for the reference
    return {"made": made, "next": 0, "sent": [], "reports": [], "steps": [],
            "queries_sent": 0, "counters0": None, "counters1": {},
            "cold": None}


def parameters(ctx, stream: int) -> List[Query]:
    """The substitution parameters of stream ``stream``'s queries, each
    execution's drawn anew: from ``--seed`` and the stream's number."""
    p = ctx.cell.traffic
    rng = np.random.default_rng([ctx.seed, 5, stream])
    out: List[Query] = []
    for name in p["queries"]:
        q = p[name]
        if name == "q6":
            out.append((name, (int(rng.choice(q["years"])),
                               str(rng.choice(q["discounts"])),
                               int(rng.choice(q["quantities"])))))
        else:
            lo, hi = q["parameter"]["least"], q["parameter"]["most"]
            out.append((name, int(rng.integers(lo, hi + 1))))
    return out


def _ask(ctx, query: Query):
    name, what = query
    q = ctx.cell.traffic[name]
    table = f"delta.`{ctx.sut.path}`"
    if name == "q6":
        year, discount, quantity = what
        text = q["query"].format(table=table, date=f"{year}-01-01",
                                 discount=discount, quantity=quantity)
    else:
        text = q["query"].format(table=table, **{q["parameter"]["name"]: what})
    if hasattr(ctx.sut, "sql"):
        answer = ctx.sut.sql(text)
    else:
        from delta_tpu.sql.parser import execute_sql

        answer = execute_sql(text)
    return answer.column(q["result_column"])[0].as_py() if name == "q6" \
        else answer


def _function(ctx, state, info, function: str, rows, source) -> None:
    state["sent"].append(rows)  # sent, or failed half-way
    state["steps"].append((function, rows))
    metrics = _statement(ctx, function, source)
    state["reports"].append((int(metrics["numTargetRowsInserted"]),
                             int(metrics["numTargetRowsDeleted"])))
    _add(info["metrics"], metrics)
    if hasattr(ctx.sut, "merge_phases"):
        _add(info["phases"], ctx.sut.merge_phases())
        info["decisions"].append(ctx.sut.merge_decision())


def _stream(ctx, state,
            after_query: Optional[Callable[[Query], None]] = None) -> Dict[str, Any]:
    index = state["next"]
    state["next"] += 1
    rset, rf1, rf2 = state["made"][index]
    state["made"][index] = None  # the Arrow copies are not needed again
    queries = parameters(ctx, index)
    info: Dict[str, Any] = {"metrics": {}, "phases": {}, "decisions": [],
                            "stream": index, "queries": queries,
                            "first_query": state["queries_sent"]}
    _function(ctx, state, info, "rf1", rset.rf1, rf1)
    answers = []
    for query in queries:
        state["steps"].append(query)
        state["queries_sent"] += 1
        answers.append(_ask(ctx, query))
        if after_query is not None:
            after_query(query)
    _function(ctx, state, info, "rf2", rset.rf2, rf2)
    return {"rows": rf1.num_rows + rf2.num_rows, "result": answers,
            "info": info}


def warm_up(ctx, state) -> None:
    """Two streams. The first RF1 builds the slab on the decode route, its
    queries load the lanes of every file (the fifteen loaded and RF1's small
    one) and compile both programs at both lane shapes, its RF2 writes the
    first file's first deletion vector; the second stream's queries are the
    first to meet a vector, a keep mask and a seventeenth file, and its
    MERGEs the first resident pair. A query answered off the device route
    ends set-up at once."""
    def device() -> int:
        return ctx.sut.counters().get(ROUTE_DEVICE, 0)

    # the grouped route first, as mix ``q1`` does: a cutoff that prunes every
    # file costs milliseconds on any route
    probe, before = ("q1", int(ctx.cell.traffic["q1"]["route_probe"])), device()
    _ask(ctx, probe)
    if device() == before:
        raise RouteMissing(f"{probe[0]} {probe[1]} was not answered on the "
                           f"device route ({ROUTE_DEVICE} did not move)")
    for i in range(int(ctx.cell.traffic["warmup_streams"])):
        merges, answered = ctx.sut.counters().get(ROUTE, 0), [device()]

        def on_device(query: Query) -> None:
            answered.append(device())
            if answered[-1] == answered[-2]:
                _settle()
                raise RouteMissing(
                    f"warm-up stream {i}: {query[0]} {query[1]} was not "
                    f"answered on the device route ({ROUTE_DEVICE} did not "
                    f"move)")

        t0 = time.perf_counter()
        out = _stream(ctx, state, on_device)
        moved = ctx.sut.counters().get(ROUTE, 0) - merges
        log(f"warm-up stream {i}: {time.perf_counter() - t0:.3f} s; decisions "
            f"{out['info']['decisions']}, phases {out['info']['phases']}")
        if moved < min(i + 1, 2):
            _settle()
            raise RouteMissing(
                f"warm-up stream {i}: {moved} of its MERGEs took the "
                f"resident pairs-only route ({ROUTE}), not {min(i + 1, 2)}")


def max_requests(ctx, state) -> int:
    """How many requests the window may hold: the sets set-up has left."""
    return len(state["made"]) - state["next"]


def request(ctx, state, i: int) -> Dict[str, Any]:
    if state["cold"]:
        raise ProgramsCold(state["cold"])  # the window's other requests
    if state["counters0"] is None:
        state["counters0"] = ctx.sut.counters()
    out = _stream(ctx, state)
    # the window's last reading: the read-back that follows it may compile
    state["counters1"] = ctx.sut.counters()
    if i == 0:
        before, after = state["counters0"], state["counters1"]
        cold = sum(after.get(n, 0) - before.get(n, 0) for n in COMPILES)
        if cold:
            state["cold"] = (f"the window's first stream compiled or fetched "
                             f"{cold} programs ({', '.join(COMPILES)})")
            raise ProgramsCold(state["cold"])
    return out


def check(ctx, state, requests) -> Dict[str, Dict[str, int]]:
    """Every query of the window against the reference's answer over the
    reference's own state at that point of the stream (Q6's revenue to the
    last digit, Q1 as a whole Arrow table); every query on the device route,
    none declined; and all of kind ``merge_refresh``'s comparison: the
    table read back against the reference's refresh of every function sent,
    every statement's counts, one commit a function, every MERGE of the
    window on the resident pairs-only route, nothing compiled."""
    if state["cold"]:
        _settle()
        raise ProgramsCold(state["cold"])
    t0 = time.perf_counter()
    want, _state, _counts = ctx.table.ref_power(ctx.base, state["steps"])
    done = [r for r in requests if r.ok]
    wrong = asked = 0
    for r in done:
        first = r.info["first_query"]
        for k, (query, got) in enumerate(zip(r.info["queries"], r.result)):
            asked += 1
            wrong += not ctx.table.same_answer(query[0], got, want[first + k])
    log(f"check: reference stream of {len(state['steps'])} statements, "
        f"{len(want)} queries, {time.perf_counter() - t0:.1f} s; "
        f"{host_memory()}")
    del want, _state
    before, after = state["counters0"] or {}, state["counters1"]

    def moved(name: str) -> int:
        return after.get(name, 0) - before.get(name, 0)

    out = {"aggregates_wrong": {"value": wrong, "limit": 0},
           "queries_off_device": {"value": abs(asked - moved(ROUTE_DEVICE)),
                                  "limit": 0},
           "route_declined": {"value": moved(ROUTE_DECLINED), "limit": 0}}
    out.update(merge_refresh.check(ctx, state, requests))
    return out
