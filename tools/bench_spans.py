#!/usr/bin/env python3
"""Run one traced cell of the benchmark and say where a request's time went.

    python3 tools/bench_spans.py [--decode-route] --workload <cell> --seed <n> --seconds <s> --trace 1

The arguments and the result line are `benchmark/run.py`'s. Besides, on
standard error: the mean milliseconds a request of every span of the program
(and how many of them a request opened), each MERGE's route, the same means a
statement split by the root span's ``clauses`` (a refresh pair's RF1 and RF2
apart), where a request holds MERGEs and queries (a power stream) the same
means a query with the first query after each MERGE apart from the rest and
the grouped apart from the ungrouped, each with the bytes it sent up the link
and its keep masks (``cached`` or built), what the resident probe's spans and counters say of how widely it
engaged, every sort of the slab split by ``tier`` (the tail run alone, or the
whole slab; ``rows``, ``cause``) and every search of its big sorted run for
flipped rows (``rows``, ``flips``, ``steps``) with the counts of flips
searched for, of flips that re-sorted instead, of tail sorts and of folds,
where the written files' statistics came from (the counters
``write.stats.footer`` / ``.decoded`` and ``source`` on ``delta.write.stats``)
and how many MERGEs wrote their vectors beside their data file
(``merge.dv.overlapped``, ``overlapped`` on the ``.deletionVectors`` span),
whether each row-group plan found its file's footer cached and how many
leaves' statistics it read (``footerCached``, ``statsColumns`` on
``delta.scan.decode.open.plan``), how many decimal columns each of the
window's reads decoded from fixed-length byte arrays (``flbaDecimals`` on
``delta.scan.decode.rowGroups``), the
routes and group counts of the aggregate queries with the program that answered the
grouped ones (``tiled`` or ``wide``) and the tiled share, under each leaf span
that has stages inside it (a MERGE's ``.write`` and ``.apply``, a decode's
``.open``, an aggregate query's launches, its own span and its root) the
span's mean, each stage's, their sum and the span's cover, what a launch of an
aggregate query costs the host split into its arguments and its dispatch, the
interpreter's collections in the window (count, total, every pause of a
millisecond or more with the request it fell in, and the slowest requests
with the pause inside each), and the device's time in the window by XLA
module, each module with its longest operations and the arguments its
operations name. A builder's instrument for PERF.md; nothing of the benchmark
reads it.

``--decode-route`` makes `MergeIntoCommand._pairs_only_shape` read false, so
that a resident MERGE decodes the target as before PR 26: the same program
with one observable turned off, for the before-and-after of that route.
"""
import bisect
import collections
import importlib.util
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def report(run) -> None:
    done = run.done
    total, count = collections.Counter(), collections.Counter()
    routes = []
    for r in done:
        for s in r.spans:
            total[s["name"]] += s["duration_us"] or 0
            count[s["name"]] += 1
            if s["name"] == "delta.merge.router":
                routes.append(s["data"].get("route"))
    means = {n: [round(us / 1e3 / len(done), 3), round(count[n] / len(done), 2)]
             for n, us in total.most_common()}
    print("span means a request [ms, spans]:", json.dumps(means),
          file=sys.stderr)
    print("merge routes:", json.dumps(routes), file=sys.stderr)
    split = by_clauses(done)
    if split:
        print("span means a statement, by the root span's clauses "
              "[statements, {span: ms}]:", json.dumps(split), file=sys.stderr)

    apart = queries_apart(done)
    if apart:
        from delta_tpu.utils import telemetry

        print("span means a query of a stream, the first after a MERGE apart "
              "[queries, {span: ms}, h2d bytes a query, keep masks "
              "{cached: n}]:", json.dumps(apart), "keep masks, the process's "
              "[hits, misses]:", [telemetry.counters("columnCache.keep").get(
                  f"columnCache.keep.{k}", 0) for k in ("hits", "misses")],
              file=sys.stderr)

    def span_data(name):
        return [s["data"] for r in done for s in r.spans if s["name"] == name]

    probes = span_data("delta.merge.deviceProbe")
    if probes:
        from delta_tpu.utils import telemetry

        # the counters are the process's: set-up's MERGEs count too
        slab = telemetry.counters("merge.keyCache")
        print("device probes:", json.dumps(probes), "overflows:",
              telemetry.counters("merge.resident.probe").get(
                  "merge.resident.probe.overflow", 0), "flip searches:",
              slab.get("merge.keyCache.flipSearches", 0), "flip re-sorts:",
              slab.get("merge.keyCache.flipResorts", 0), "tail sorts:",
              slab.get("merge.keyCache.tailSorts", 0), "folds:",
              slab.get("merge.keyCache.folds", 0), file=sys.stderr)
        # what a pair paid the device for: each sort of the slab's tail run
        # alone and each sort of the whole slab (`cause`: a key append, a
        # flip too large to search for, or `fold`, an append that found the
        # tail full), and each search of the live big run for flipped rows
        # (`flips`: the rows of it whose validity the advance flipped)
        sorts = span_data("delta.keyCache.sort")
        for tier in ("tail", "all"):
            print(f"slab sorts, tier={tier}:", json.dumps(
                [d for d in sorts if d.get("tier") == tier]), file=sys.stderr)
        print("slab searches:", json.dumps(span_data("delta.keyCache.locate")),
              file=sys.stderr)
    stats = span_data("delta.write.stats")
    if stats:
        # where each written file's statistics came from, and how many of
        # the window's MERGEs wrote their vectors beside their data file
        print("written files' statistics, in the window [footer, decoded]:",
              [run.counters.get(f"write.stats.{k}", 0)
               for k in ("footer", "decoded")], "by the span's source:",
              json.dumps(collections.Counter(
                  str(d.get("source")) for d in stats)),
              "MERGEs whose vectors ran beside the write:",
              run.counters.get("merge.dv.overlapped", 0),
              "of", sum(1 for r in done for s in r.spans
                        if s["name"] == "delta.dml.merge"),
              "by the .deletionVectors span's overlapped:",
              json.dumps(collections.Counter(
                  str(d.get("overlapped"))
                  for d in span_data("delta.dml.merge.deletionVectors"))),
              file=sys.stderr)
    plans = span_data("delta.scan.decode.open.plan")
    if plans:
        # whether each file's footer was cached, and how many leaves'
        # statistics its row-group plan read (a parent lacks the key: None)
        print("row-group plans [footerCached, statsColumns: plans]:",
              json.dumps(collections.Counter(
                  f"{d.get('footerCached')}, {d.get('statsColumns')}"
                  for d in plans)),
              file=sys.stderr)
    reads = span_data("delta.scan.decode.rowGroups")
    if reads:
        # a decimal is an INT32 / INT64 column where its precision allows;
        # a file written before that decodes its decimals from byte arrays
        print("reads by their byte-array decimals [flbaDecimals: reads]:",
              json.dumps(collections.Counter(
                  str(d.get("flbaDecimals")) for d in reads)),
              file=sys.stderr)
    aggregates = span_data("delta.scan.deviceAggregate")
    if aggregates:
        # grouped queries also open delta.scan.deviceAggregate.groups (the
        # merge of the files' partials by value), whose mean is above
        grouped = run.counters.get("scan.aggregate.grouped", 0)
        tiled = run.counters.get("scan.aggregate.grouped.tiled", 0)
        print("aggregate routes:", json.dumps(collections.Counter(
            d.get("route") for d in aggregates)), "groups a query:",
            json.dumps(collections.Counter(
                str(d.get("groups")) for d in aggregates)),
            "grouped, in the window:", grouped,
            # which formulation of the grouped program answered (PR 33)
            "programs:", json.dumps(collections.Counter(
                str(d.get("program"))
                for d in span_data("delta.columnCache.aggregate"))),
            "tiled share of grouped, %:",
            round(100 * tiled / grouped, 2) if grouped else None,
            file=sys.stderr)
        # small files' lanes end to end (PR 40): packs built and launches
        # over a pack in the window, and what planning and the launches read
        print("packs built, in the window:", run.counters.get("columnCache.packs", 0),
              "launches over a pack:",
              run.counters.get("scan.aggregate.packedLaunches", 0),
              "packs a query:", json.dumps(collections.Counter(
                  str(d.get("packs")) for d in aggregates)),
              "partition pruning [tier, files, kept]:", json.dumps(
                  collections.Counter(str((d.get("tier"), d.get("files"), d.get("kept")))
                                      for d in span_data("delta.scan.partitions"))),
              file=sys.stderr)
    print("stages inside the leaf spans [span: its mean ms, {stage: ms}, "
          "their sum, the span's cover %]:", json.dumps(insides(run)),
          file=sys.stderr)
    launches = launch_split(done)
    if launches:
        print("an aggregate query's launches:", json.dumps(launches),
              file=sys.stderr)
    print("the interpreter's collections:", json.dumps(gc_pauses(run)),
          file=sys.stderr)
    if run.trace is not None:
        print("device ms a request by module:",
              json.dumps(module_split(run.trace, len(done))), file=sys.stderr)


# the leaf spans that have stages inside them, each with its stages
_WRITE, _APPLY = "delta.dml.merge.write", "delta.dml.merge.apply"
_OPEN, _LAUNCHES = "delta.scan.decode.open", "delta.columnCache.aggregate"
_QUERY, _SELECT = "delta.scan.deviceAggregate", "delta.sql.select"
STAGES = {
    _WRITE: [_WRITE + ".concat", "delta.write.prepare", "delta.write.encode",
             "delta.write.stats"],
    _APPLY: [_APPLY + ".multiMatch", _APPLY + ".matched",
             _APPLY + ".notMatched"],
    _OPEN: [_OPEN + ".plan", _OPEN + ".survivors", _OPEN + ".file"],
    _LAUNCHES: [_LAUNCHES + ".launch", _LAUNCHES + ".fetch"],
    _QUERY: [_QUERY + ".resolve", "delta.scan.planning", _QUERY + ".lanes",
             _LAUNCHES, _QUERY + ".groups"],
    _SELECT: [_SELECT + ".resolve", _QUERY, "delta.scan", _SELECT + ".order"],
}


def insides(run):
    """For each leaf span of `STAGES` that the window's requests opened: its
    mean milliseconds a request, each stage's (the benchmark's own reader
    `child_span_mean_ms`, so a listed metric reads the same), the stages'
    sum, and the span's cover (`span_cover_pct`)."""
    from benchmark.metrics.readers import (child_span_mean_ms, span_cover_pct,
                                           span_mean_ms)

    out = {}
    for parent, stages in STAGES.items():
        mean = span_mean_ms.read(run, {"spans": [parent]})
        if mean is None:
            continue
        each = {s: round(child_span_mean_ms.read(
            run, {"parent": parent, "spans": [s]}), 4) for s in stages}
        cover = span_cover_pct.read(run, {"root": parent})
        out[parent] = [round(mean, 4), each, round(sum(each.values()), 4),
                       None if cover is None else round(cover, 3)]
    return out


def launch_split(done):
    """What the host pays a launch: the ``.launch`` stages' length over their
    ``launches``, split into the time inside the calls of the jitted function
    (``dispatchUs``) and the rest, which made the arguments; and the fetch's
    wait a query; and the files and runs of rows (``ranges``) a query's
    launches read (PR 40: a launch over a pack reads many files). None where
    no request ran an aggregate query's launches."""
    def spans(name):
        return [s for r in done for s in r.spans if s["name"] == name]

    stages = spans(_LAUNCHES + ".launch")
    if not stages:
        return None
    n = sum(s["data"]["launches"] for s in stages)
    stage_us = sum(s["duration_us"] for s in stages)
    dispatch_us = sum(s["data"]["dispatchUs"] for s in stages)
    fetch_us = sum(s["duration_us"] for s in spans(_LAUNCHES + ".fetch"))
    return {"launches a query": round(n / len(stages), 2),
            "host us a launch": round(stage_us / n, 2),
            "of it dispatch": round(dispatch_us / n, 2),
            "of it arguments": round((stage_us - dispatch_us) / n, 2),
            "fetch ms a query": round(fetch_us / 1e3 / len(stages), 4),
            "files a query": round(sum(s["data"].get("files", 0)
                                       for s in stages) / len(stages), 2),
            "ranges a query": round(sum(s["data"].get("ranges", 0)
                                        for s in stages) / len(stages), 2)}


def gc_pauses(run):
    """The interpreter's collections in the window: how many and how long by
    the counters (every generation), the pauses long enough to be events
    (``host.gc``) each with the request it fell in, and the slowest requests
    with the pause inside each: whether a request that stalled stalled
    there."""
    def pauses(r):
        return [s for s in r.spans if s["name"] == "host.gc"]

    events = [{"request": r.index, "ms": round(s["duration_us"] / 1e3, 3),
               "generation": s["data"].get("generation"),
               "collected": s["data"].get("collected"),
               "request_ms": round((r.end - r.start) * 1e3, 3)}
              for r in run.requests for s in pauses(r)]
    slowest = sorted(run.requests, key=lambda r: r.start - r.end)[:5]
    walls = sorted(r.end - r.start for r in run.requests)
    count = run.counters.get("host.gc.collections", 0)
    return {"collections": count,
            "pause_ms": round(run.counters.get("host.gc.pauseUs", 0) / 1e3, 3),
            "a request": round(count / max(len(run.requests), 1), 2),
            "events": len(events),
            "events_ms": round(sum(e["ms"] for e in events), 3),
            "longest": sorted(events, key=lambda e: -e["ms"])[:8],
            "median_request_ms": round(walls[len(walls) // 2] * 1e3, 3)
            if walls else None,
            "slowest_requests": [
                {"request": r.index,
                 "ms": round((r.end - r.start) * 1e3, 3),
                 "gc_ms": round(sum(s["duration_us"] for s in pauses(r)) / 1e3,
                                3)} for r in slowest]}


def by_clauses(done):
    """Every span's mean milliseconds a statement, the statements told apart
    by the ``clauses`` their root span carries (``insert``: RF1's
    de-duplicating insert; ``delete``: RF2's keyed delete; ``update,insert``:
    the star upsert): a span belongs to the MERGE whose root span was open
    when it started, on whatever thread. Empty where no request ran a
    MERGE."""
    total = collections.defaultdict(collections.Counter)
    count = collections.Counter()
    for r in done:
        for root in r.spans:
            if root["name"] != "delta.dml.merge" or not root["duration_us"]:
                continue
            key = root["data"].get("clauses", "?")
            count[key] += 1
            lo, hi = root["start_us"], root["start_us"] + root["duration_us"]
            for s in r.spans:
                if s["duration_us"] is not None and lo <= s["start_us"] < hi:
                    total[key][s["name"]] += s["duration_us"]
    return {key: [n, {name: round(us / 1e3 / n, 3)
                      for name, us in total[key].most_common()}]
            for key, n in count.items()}


def queries_apart(done):
    """Where a request holds MERGEs and queries (a power stream): every
    span's mean milliseconds a query, a span belonging to the query whose
    root span ``delta.sql.select`` was open when it started. The queries are
    told apart by whether the statement before was a MERGE (``first``: the
    query that meets the new version, loads the new file's lanes and builds
    the keep mask of a new vector) and by whether the answer was grouped.
    Empty where no request holds both kinds of root."""
    total = collections.defaultdict(collections.Counter)
    count, up = collections.Counter(), collections.Counter()
    masks = collections.defaultdict(collections.Counter)
    for r in done:
        roots = sorted((s for s in r.spans if s["duration_us"] and s["name"]
                        in ("delta.dml.merge", _SELECT)),
                       key=lambda s: s["start_us"])
        if len({s["name"] for s in roots}) < 2:
            continue
        for before, root in zip([None] + roots, roots):
            if root["name"] != _SELECT:
                continue
            lo, hi = root["start_us"], root["start_us"] + root["duration_us"]
            inside = [s for s in r.spans if s["duration_us"] is not None
                      and lo <= s["start_us"] < hi]
            grouped = any(s["name"] == _QUERY and "groups" in s["data"]
                          for s in inside)
            key = ("rest" if before and before["name"] == _SELECT else "first") \
                + (" grouped" if grouped else " ungrouped")
            count[key] += 1
            for s in inside:
                total[key][s["name"]] += s["duration_us"]
                up[key] += s["data"].get("h2dBytes", 0)
                if s["name"] == "delta.columnCache.keepMask":
                    masks[key][str(s["data"].get("cached"))] += 1
    return {key: [n, {name: round(us / 1e3 / n, 3)
                      for name, us in total[key].most_common()},
                  round(up[key] / n, 1), dict(masks[key])]
            for key, n in sorted(count.items())}


_NAME = re.compile(r"%([A-Za-z_][A-Za-z_0-9]*?)(?:\.\d+)*(?![\w.\-])")
_HLO_WORDS = re.compile(
    r"^(fusion|copy|bitcast|sort|constant|param|tuple|slice|reshape|"
    r"transpose|broadcast|iota|convert|select|compare|reduce|gather|scatter|"
    r"concatenate|pad|while|add|subtract|and|or|not|clamp|maximum|minimum|"
    r"cumsum|cummax|lt|le|eq|ne|gt|ge|region|wrapped|input|loop)")


def module_split(trace, requests: int, longest: int = 5):
    """Device milliseconds a request of every XLA module that ran in the
    window, longest first: how often it ran, its longest operations (HLO
    name and first shape) and the names its operations' texts carry that
    are no HLO words, which are the jitted function's arguments."""
    from benchmark.harness.trace import short_name

    out = {}
    for dev in trace.devices.values():
        ops = sorted(dev.ops, key=lambda e: e.start)
        starts = [e.start for e in ops]
        for m in dev.modules:
            if not trace.window[0] <= m.start < trace.window[1]:
                continue
            rec = out.setdefault(m.name, {"runs": 0, "ns": 0,
                                          "ops": collections.Counter(),
                                          "names": set()})
            rec["runs"] += 1
            rec["ns"] += m.end - m.start
            lo = bisect.bisect_left(starts, m.start)
            hi = bisect.bisect_left(starts, m.end)
            for o in ops[lo:hi]:
                rec["ops"][short_name(o.name)] += o.end - o.start
                if rec["runs"] == 1:
                    rec["names"].update(
                        n for n in _NAME.findall(o.name)
                        if not _HLO_WORDS.match(n))
    rows = sorted(out.items(), key=lambda kv: -kv[1]["ns"])
    return [{"module": name, "runs": r["runs"],
             "ms": round(r["ns"] / 1e6 / max(requests, 1), 3),
             "names": sorted(r["names"])[:12],
             "ops": [[n, round(ns / 1e6 / max(requests, 1), 3)]
                     for n, ns in r["ops"].most_common(longest)]}
            for name, r in rows if r["ns"] / 1e6 / max(requests, 1) >= 0.05]


def main() -> int:
    decode_route = "--decode-route" in sys.argv
    argv = [a for a in sys.argv[1:] if a != "--decode-route"]
    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(ROOT, "benchmark", "run.py"))
    bench_run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_run)  # standard output is the result's now

    from benchmark.harness import runner

    if decode_route:
        from delta_tpu.commands.merge import MergeIntoCommand

        MergeIntoCommand._pairs_only_shape = lambda self, *a: False

    breakdown = runner._breakdown

    def with_span_means(run):
        report(run)
        return breakdown(run)

    runner._breakdown = with_span_means
    return bench_run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
