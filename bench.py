"""Benchmarks for the 5 BASELINE.md harness configs, end to end.

Every number is wall-clock through the public engine APIs — Parquet IO,
expression evaluation, log commit and all — not kernel-only. Baselines are
honest same-machine host implementations, labeled per config:

  1 batch overwrite + filtered read      vs raw pyarrow parquet write+read
  2 MERGE upsert 1M→10M store_sales      vs the engine's own host-Arrow join
    (headline: GB/sec)                      path (devicePath.enabled=false)
  3 Z-ORDER OPTIMIZE + point query       vs the same query pre-OPTIMIZE
  4 streaming tail of a 1k-commit log    vs snapshot-rebuild-per-batch
  5 checkpoint replay, 10k versions      vs sequential dict replay (both
    (JSON decode included)                  including JSON action decode)
  6/6p hot-table batched scan planning    vs batched numpy over resident
    (1M files x 256 queries; 6p = the       float64 mirrors (strongest host)
    partitioned variant)
  7 replay winner scale probe            vs host numpy scatter
  8 steady-state resident MERGE probe    vs strongest host membership path
    (10M/30M/100M target keys)             on resident key mirrors
  2x north-star-scale MERGE              cold vs steady-state engine merge
    (100M rows, 10 GB class)               (resident-lane CDC shape)
  12 device-resident residual scan        vs the Arrow host residual path
    (host/cold/warm legs, identity          (deviceResidual.mode=off); CPU-
    asserted per query)                     only hosts skip-record the claim
  13 shadow optimizer end to end          first-round absolute numbers; the
    (journal->trace, 2-candidate what-if   scorecard verdicts (confirmed
     scorecard, 10x/100x SLO capacity)     winner, refuted loser) and the
                                           fired SLO objective are asserted
                                           in-config
  14 sharded execution plane 1-vs-8       plan leg in an 8-device subprocess
    (shard_map scan planning, workers=8    ("14w"); identity asserted per
     OPTIMIZE, probe-restricted MERGE)     leg; CPU-only hosts skip-record
                                           the throughput claim but keep the
                                           measured numbers + LPT skew gate

Prints ONE JSON line: the headline metric (config 2 MERGE GB/sec) with the
required {metric, value, unit, vs_baseline} keys plus an ``all`` field
holding every config's numbers. BENCH_SCALE (default 1.0) scales row counts
for quick local runs.

Budget discipline (ISSUE 6): the run must exit rc=0 inside the driver's
wall. BENCH_BUDGET_S (default 3000s) is the soft total; each config also
runs under a SIGALRM deadline (BENCH_CONFIG_DEADLINE_S, default 480s;
headline config 2 gets 900s, 2x 540s, 8 600s) — a breach records a skip
entry and the run continues, so every completed config's artifact is
always captured. Config errors likewise record-and-continue.
"""
import json
import os

# must precede the first pyarrow import: jemalloc (the default) returns
# freed pages to the OS aggressively, so every bench phase re-faults its
# working set; mimalloc retains, giving steadier wall-clock
os.environ.setdefault("ARROW_DEFAULT_MEMORY_POOL", "mimalloc")

import shutil
import sys
import tempfile
import time

import numpy as np

from delta_tpu.utils.jaxcompat import enable_x64

SCALE = float(os.environ.get("BENCH_SCALE", "1.0"))


def _rows(n):
    return max(int(n * SCALE), 1000)


def _dir_bytes(path):
    total = 0
    for root, _dirs, files in os.walk(path):
        if "_delta_log" in root:
            continue
        for f in files:
            if f.endswith(".parquet"):
                total += os.path.getsize(os.path.join(root, f))
    return total


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return time.perf_counter() - t0, out


# -- config 1: batch overwrite + filtered read -------------------------------


def bench_overwrite_read(workdir):
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu import DeltaLog

    n = _rows(2_000_000)
    rng = np.random.RandomState(3)
    data = pa.table({
        "id": np.arange(n, dtype=np.int64),
        "v": rng.randint(0, 1000, n).astype(np.int64),
        "name": pa.array(np.char.add("u", rng.randint(0, 99999, n).astype(str))),
    })
    path = os.path.join(workdir, "c1")
    log = DeltaLog.for_table(path)
    # Fault layer is strictly zero-overhead when no plan is configured:
    # maybe_wrap must return the store UNCHANGED (no wrapper object at all),
    # and the bench must never accidentally run with injection enabled.
    from delta_tpu.storage import faults as _faults

    assert _faults.plan_from_conf() is None, (
        "bench must run without a fault plan (delta.tpu.faults.plan is set)")
    assert _faults.maybe_wrap(log._base_store) is log._base_store, (
        "fault layer must install NO wrapper when delta.tpu.faults.plan is unset")
    assert not isinstance(getattr(log.store, "base", log.store),
                          _faults.FaultInjectingLogStore), (
        "DeltaLog store stack must not contain a fault injector by default")
    WriteIntoDelta(log, "append", data).run()

    def engine_roundtrip():
        WriteIntoDelta(log, "overwrite", data).run()
        t = DeltaTable.for_path(path)
        out = t.to_arrow(filters=["v < 100"])
        return out.num_rows

    engine_roundtrip()  # warm device kernel compiles (XLA caches per shape)
    eng_s, eng_rows = _timed(engine_roundtrip)

    # baseline: raw pyarrow — the floor any engine pays for the same IO
    raw = os.path.join(workdir, "c1_raw.parquet")

    def raw_roundtrip():
        pq.write_table(data, raw)
        t = pq.read_table(raw)
        return t.filter(pc.less(t.column("v"), 100)).num_rows

    trials = [_timed(raw_roundtrip) for _ in range(2)]
    raw_s, raw_rows = min(trials, key=lambda x: x[0])
    assert eng_rows == raw_rows, (eng_rows, raw_rows)

    # publish table.health.* gauges so this config's telemetry snapshot
    # carries layout health (small-file debt, stats coverage) per round
    from delta_tpu.obs.doctor import doctor

    doctor(path)
    # run the workload-journal advisor once: journal.* counters land in the
    # snapshot and the --compare gate prices journaling overhead on the
    # scan path of THIS config against the prior round
    from delta_tpu.obs.advisor import advise

    advise(path)
    return {
        "metric": "overwrite_plus_filtered_read_2M_rows",
        "value": round(eng_s, 3),
        "unit": "s",
        "vs_baseline": round(raw_s / eng_s, 2),
        "baseline": "raw pyarrow parquet write+read+filter (no log, no txn)",
    }


# -- config 2: MERGE upsert (headline) ---------------------------------------


def _store_sales(n, rng):
    import pyarrow as pa

    keys = rng.permutation(n * 2)[:n].astype(np.int64)
    return pa.table({
        "ss_item_sk": keys,
        "ss_customer_sk": rng.randint(0, 1_000_000, n).astype(np.int64),
        "ss_sold_date_sk": rng.randint(2450000, 2452000, n).astype(np.int64),
        "ss_store_sk": rng.randint(0, 500, n).astype(np.int64),
        "ss_quantity": rng.randint(1, 100, n).astype(np.int64),
        "ss_sales_price": rng.rand(n).astype(np.float64) * 100,
        "ss_ext_discount_amt": rng.rand(n).astype(np.float64) * 10,
        "ss_net_paid": rng.rand(n).astype(np.float64) * 90,
    })


def bench_merge_upsert(workdir):
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.utils.config import conf

    n_target, n_source = _rows(10_000_000), _rows(1_000_000)
    rng = np.random.RandomState(7)
    target = _store_sales(n_target, rng)
    path = os.path.join(workdir, "c2")
    log = DeltaLog.for_table(path)
    WriteIntoDelta(log, "append", target).run()
    # the engine's default MERGE policy on this table: deletion vectors
    # (rows marked, only changed rows written). The baseline mode pins the
    # reference-shaped full-rewrite path via the session kill switch below.
    from delta_tpu.commands.alter import set_table_properties

    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})

    # source: half updates (existing keys), half inserts (fresh keys)
    existing = np.asarray(target.column("ss_item_sk"))[
        rng.choice(n_target, n_source // 2, replace=False)
    ]
    fresh = np.arange(n_target * 2, n_target * 2 + (n_source - n_source // 2),
                      dtype=np.int64)
    src_keys = np.concatenate([existing, fresh])
    rng.shuffle(src_keys)
    source = _store_sales(n_source, np.random.RandomState(11))
    source = source.set_column(0, "ss_item_sk", pa.array(src_keys))

    copies = {
        name: os.path.join(workdir, f"c2_{name}")
        for name in ("warm", "dev2", "host1", "host2", "forced")
    }
    for p in copies.values():
        # hardlink copies: delta table files are immutable (writes always
        # create new files), so linking shares the data without queuing
        # ~2GB of writeback that would pollute the timed trials below
        shutil.copytree(path, p, copy_function=os.link)
    gb = (_dir_bytes(path) + source.nbytes) / 1e9

    def run_merge(table_path, mode, src_tab=None, resident=False):
        from delta_tpu import DeltaLog as DL

        DL.clear_cache()
        lg = DL.for_table(table_path)
        # baseline ("off") = the reference's algorithm on this host: Arrow
        # hash join + whole-file rewrite (MergeIntoCommand.scala:456-561).
        # Engine modes keep the deletion-vector policy (changed rows only).
        with conf.set_temporarily(**{
            "delta.tpu.merge.devicePath.mode": mode,
            "delta.tpu.deletionVectors.enabled": mode != "off",
            # the resident-key lane is exercised by its own legs below; the
            # cold trials stay cold (no background build skewing them)
            "delta.tpu.merge.residentKeys.enabled": resident,
        }):
            cmd = MergeIntoCommand(
                lg, source if src_tab is None else src_tab,
                "t.ss_item_sk = s.ss_item_sk",
                [MergeClause("update", assignments=None)],
                [MergeClause("insert", assignments=None)],
                source_alias="s", target_alias="t",
            )
            cmd.run()
        assert cmd.metrics["numTargetRowsUpdated"] == n_source // 2
        assert cmd.metrics["numTargetRowsInserted"] == n_source - n_source // 2
        return cmd

    run_merge(copies["warm"], "force")  # warm the device-kernel compiles
    # headline: auto mode (the engine's link-aware executor routing) vs the
    # host-pinned baseline. Trials INTERLEAVE modes (auto, host, auto, host)
    # so page-cache/writeback drift hits both modes equally; min of 2 per
    # mode damps the allocator/page-fault noise single trials show here.
    def drain():
        # drain page-cache writeback so each trial starts from a quiet
        # disk — otherwise earlier trials' dirty pages throttle later ones
        os.sync()

    auto_trials, host_trials = [], []
    drain(); auto_trials.append(_timed(lambda: run_merge(path, "auto")))
    drain(); host_trials.append(_timed(lambda: run_merge(copies["host1"], "off")))
    drain(); auto_trials.append(_timed(lambda: run_merge(copies["dev2"], "auto")))
    drain(); host_trials.append(_timed(lambda: run_merge(copies["host2"], "off")))
    auto_s, auto_cmd = min(auto_trials, key=lambda x: x[0])
    host_s, host_cmd = min(host_trials, key=lambda x: x[0])

    # per-round sources against an evolving table: updates hit original
    # keys (always present), inserts use disjoint fresh ranges per round
    import pyarrow as _pa

    def mk_source(round_i):
        ex = np.asarray(target.column("ss_item_sk"))[
            np.random.RandomState(17 + round_i).choice(
                n_target, n_source // 2, replace=False)]
        fr = np.arange(n_target * (3 + round_i),
                       n_target * (3 + round_i) + (n_source - n_source // 2),
                       dtype=np.int64)
        keys = np.concatenate([ex, fr])
        np.random.RandomState(23 + round_i).shuffle(keys)
        s = _store_sales(n_source, np.random.RandomState(29 + round_i))
        return s.set_column(0, "ss_item_sk", _pa.array(keys))

    # the fused device pipeline, cold then warm on ONE table copy:
    # device_cold = first forced merge (per-file key decode streams onto the
    # slab while later files decode, probe, and the slab REGISTERS in the
    # KeyCache); device_forced = second forced merge against the now-hot
    # table (cache hit: tail advance + probe, no upload, no key decode) —
    # the steady state the fused MERGE tentpole targets
    drain()
    cold_s, cold_cmd = _timed(lambda: run_merge(
        copies["forced"], "force", resident=True))
    assert cold_cmd._device_join is not None, "forced device join did not run"
    drain()
    forced_s, forced_cmd = _timed(lambda: run_merge(
        copies["forced"], "force", src_tab=mk_source(8), resident=True))
    assert forced_cmd._device_join is not None, "warm forced join did not run"
    warm_cache_hit = forced_cmd._join_path == "resident"

    # resident-key steady state (the CDC loop): the warm copy was merged
    # once already; build its key lane (reported separately — in production
    # it builds in the background after the first eligible merge), then a
    # second merge probes from HBM, shipping only source keys
    from delta_tpu import DeltaLog as DL
    from delta_tpu.commands.merge import MergeIntoCommand as MIC
    from delta_tpu.expr import ir as _ir
    from delta_tpu.ops.key_cache import KeyCache

    DL.clear_cache()
    lg = DL.for_table(copies["warm"])
    snapw = lg.update()
    t_exprs = [_ir.Column("ss_item_sk")]
    sig = MIC._key_signature(t_exprs)
    build_s, entry = _timed(lambda: KeyCache.instance().get(
        snapw, sig, ["ss_item_sk"], t_exprs))
    assert entry is not None
    up_s, _ = _timed(entry.ensure_resident)
    build_s += up_s
    # rounds 1-2 warm the kernel compiles for this shape bucket (probe +
    # tail-advance scatters; first machine contact — the persistent XLA
    # cache makes later processes skip them); rounds 3-4 are the steady
    # state being measured
    run_merge(copies["warm"], "force", src_tab=mk_source(0), resident=True)
    run_merge(copies["warm"], "force", src_tab=mk_source(1), resident=True)
    res_trials = []
    for i in (2, 3):
        drain()
        res_trials.append(_timed(lambda i=i: run_merge(
            copies["warm"], "force", src_tab=mk_source(i), resident=True)))
    resident_s, res_cmd = min(res_trials, key=lambda x: x[0])
    assert res_cmd._join_path == "resident", res_cmd._join_path
    # what auto picks with the lane resident (honest link-model verdict)
    drain()
    res_auto_s, res_auto_cmd = _timed(lambda: run_merge(
        copies["warm"], "auto", src_tab=mk_source(4), resident=True))

    from delta_tpu.parallel import link
    from delta_tpu.utils import telemetry as _tel

    lp = link.profile()
    return {
        "metric": "tpcds_store_sales_merge_upsert_1M_into_10M",
        "value": round(gb / auto_s, 3),
        "unit": "GB/s",
        "vs_baseline": round(host_s / auto_s, 2),
        "baseline": "reference-shaped path on the same machine: host Arrow "
                    "hash-join + whole-file rewrite (deletion vectors off)",
        "auto_s": round(auto_s, 2),
        "host_s": round(host_s, 2),
        "gb": round(gb, 3),
        "auto_used_device": auto_cmd._device_join is not None,
        "auto_join_path": auto_cmd._join_path,
        "auto_router": dict(auto_cmd._router),
        "auto_phases": dict(auto_cmd.phase_ms),
        "host_phases": dict(host_cmd.phase_ms),
        # the pinned-device legs on ONE copy: cold = fused slab pipeline
        # (decode streams onto HBM, probe, slab registers); forced = the
        # second merge against the hot table (KeyCache hit — no upload, no
        # key decode). The auto router engages the same path when the link
        # prices it; the cold leg pays the slab upload.
        "device_cold_s": round(cold_s, 2),
        "device_cold_phases": dict(cold_cmd.phase_ms),
        "device_cold_path": cold_cmd._join_path,
        "device_forced_s": round(forced_s, 2),
        "device_forced_phases": dict(forced_cmd.phase_ms),
        "device_forced_cache_hit": warm_cache_hit,
        # steady-state CDC legs: target key lane HBM-resident, probe ships
        # only source keys (ops/key_cache)
        "device_resident_s": round(resident_s, 2),
        "device_resident_phases": dict(res_cmd.phase_ms),
        "resident_build_s": round(build_s, 2),
        "resident_auto_s": round(res_auto_s, 2),
        "resident_auto_path": res_auto_cmd._join_path,
        "resident_auto_router": dict(res_auto_cmd._router),
        # the production observables for the same decisions
        # (delta.merge.router events feed these counters)
        "router_counters": {
            **_tel.counters("merge.device"), **_tel.counters("merge.keyCache"),
        },
        "link_MBps": {"up": round(lp.up_mbps, 1), "down": round(lp.down_mbps, 1),
                      "latency_ms": round(lp.latency_s * 1000, 1)},
    }


# -- config 3: Z-ORDER OPTIMIZE + data-skipping point query ------------------


def bench_zorder_point_query(workdir):
    from delta_tpu import DeltaLog
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.exec.scan import scan_files

    n = _rows(4_000_000)
    rng = np.random.RandomState(5)
    data = _store_sales(n, rng)
    path = os.path.join(workdir, "c3")
    log = DeltaLog.for_table(path)
    # write in 8 chunks → 8 files with interleaved key ranges (worst case)
    step = n // 8
    for i in range(8):
        WriteIntoDelta(log, "append", data.slice(i * step, step)).run()

    key = int(np.asarray(data.column("ss_item_sk"))[12345])
    date = int(np.asarray(data.column("ss_sold_date_sk"))[12345])
    pred = f"ss_item_sk = {key} AND ss_sold_date_sk = {date}"

    def point_query():
        DeltaLog.clear_cache()
        t = DeltaTable.for_path(path)
        scan = scan_files(t.delta_log.update(), [pred])
        out = t.to_arrow(filters=[pred])
        return len(scan.files), out.num_rows

    point_query()  # warm pruning-kernel compiles
    pre_s, (pre_files, pre_rows) = _timed(point_query)
    opt_s, _ = _timed(
        OptimizeCommand(log, z_order_by=["ss_item_sk", "ss_sold_date_sk"],
                        target_rows=step).run
    )
    point_query()  # re-warm: the post-OPTIMIZE file count is a new shape
    post_s, (post_files, post_rows) = _timed(point_query)
    assert pre_rows == post_rows
    return {
        "metric": "zorder_point_query_4M_rows",
        "value": round(post_s * 1000, 1),
        "unit": "ms",
        "vs_baseline": round(pre_s / post_s, 2),
        "baseline": "same point query before Z-ORDER OPTIMIZE (files scanned "
                    f"{pre_files}->{post_files})",
        "optimize_s": round(opt_s, 2),
    }


# -- config 10: predicate pushdown synthesis ---------------------------------


def bench_pushdown(workdir):
    """2M-row table, arithmetic + string + cast predicate suite: files and
    row groups pruned, bytes skipped, and planning ms with predicate
    synthesis ON vs OFF (`delta.tpu.read.predicateSynthesis`), result
    identity asserted on every query. Headline: planning-bytes-skipped
    (file tier + row-group tier) ratio on/off — these shapes paid full
    scans before the synthesis layer, so OFF skips ~nothing."""
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.obs import scan_report
    from delta_tpu.utils.config import conf as _c

    n = _rows(2_000_000)
    ids = np.arange(n, dtype=np.int64)
    rng = np.random.RandomState(11)
    regions = np.array(["us-w", "us-e", "eu-c", "eu-w",
                        "ap-s", "ap-n", "sa-e", "af-s"])
    # region index correlates with row order → prefixes cluster per file,
    # like a region-loaded ingest; prices sorted → tight per-file bounds
    region_ix = (ids * len(regions)) // n
    sym = np.char.add(np.char.add(regions[region_ix], "-"),
                      np.char.zfill(ids.astype("U10"), 10))
    base_us = 1_600_000_000_000_000
    data = pa.table({
        "id": ids,
        "price": ids,
        "qty": rng.randint(1, 8, n).astype(np.int64),
        "sym": pa.array(sym),
        "ts": pa.array(base_us + ids * 60_000_000, pa.timestamp("us")),
    })
    path = os.path.join(workdir, "c10")
    log = DeltaLog.for_table(path)
    with _c.set_temporarily(**{
        "delta.tpu.write.targetFileRows": max(n // 16, 1000),
        "delta.tpu.write.rowGroupRows": max(n // 128, 500),
    }):
        WriteIntoDelta(log, "append", data).run()
    total_bytes = _dir_bytes(path)
    hi = int(0.97 * n)
    day = (base_us + int(0.98 * n) * 60_000_000) // 86_400_000_000
    import datetime as _dt

    day_s = (_dt.date(1970, 1, 1) + _dt.timedelta(days=int(day))).isoformat()
    queries = [
        ("arith_mul", f"price * qty > {hi * 7}"),
        ("arith_chain", f"price * 2 + 10 >= {2 * hi}"),
        ("arith_div", f"(price - {n // 2}) / 4 >= {int(0.115 * n)}"),
        ("string_substr", "substr(sym, 1, 4) = 'af-s'"),
        ("string_like", "sym like 'us-w000000%'"),
        ("cast_double", f"cast(price as double) * 1.5 >= {1.5 * hi}"),
        ("temporal_to_date", f"to_date(ts) = '{day_s}'"),
        ("not_cmp", f"not (price < {hi})"),
    ]
    t = DeltaTable.for_path(path)
    t.to_arrow(filters=[queries[0][1]])  # warm footers + compiles

    def run_suite(enabled):
        out = {}
        with _c.set_temporarily(**{
            "delta.tpu.read.predicateSynthesis": enabled,
        }):
            for name, q in queries:
                t0 = time.perf_counter()
                result = t.to_arrow(filters=[q])
                wall_s = time.perf_counter() - t0
                rep = scan_report.last_scan_report()
                out[name] = {
                    "rows": result.num_rows,
                    "id_sum": int(np.asarray(result.column("id")).sum()),
                    "files_pruned": rep.files_pruned,
                    "rowgroups_pruned": rep.row_groups_pruned,
                    "rowgroups_late_skipped": rep.row_groups_late_skipped,
                    # planning-skipped = file tier (compressed bytes never
                    # read) + row-group PLANNER tier (groups never opened);
                    # late materialization is decode-time, not planning
                    "bytes_skipped": (total_bytes - rep.bytes_read)
                    + rep.bytes_skipped_planned,
                    "planning_ms": rep.phase_ms.get("planning", 0),
                    "wall_ms": round(wall_s * 1000, 1),
                    "rewrites_fired": len(rep.rewrites_fired),
                }
        return out

    off = run_suite(False)
    on = run_suite(True)
    for name, _q in queries:
        # result identity on every query: synthesis may only change what
        # decodes, never what returns
        assert on[name]["rows"] == off[name]["rows"], name
        assert on[name]["id_sum"] == off[name]["id_sum"], name
    skipped_on = sum(v["bytes_skipped"] for v in on.values())
    skipped_off = sum(v["bytes_skipped"] for v in off.values())
    ratio = skipped_on / max(skipped_off, 1)
    plan_on = sorted(v["planning_ms"] for v in on.values())
    plan_off = sorted(v["planning_ms"] for v in off.values())
    return {
        "metric": "pushdown_synthesis_bytes_skipped_ratio",
        "value": round(ratio, 1),
        "unit": "x",
        "vs_baseline": round(ratio, 1),
        "baseline": "same suite with delta.tpu.read.predicateSynthesis="
                    "false (pre-synthesis engine: these shapes never prune)",
        "rows": n,
        "bytes_skipped_on": skipped_on,
        "bytes_skipped_off": skipped_off,
        "files_pruned_on": sum(v["files_pruned"] for v in on.values()),
        "files_pruned_off": sum(v["files_pruned"] for v in off.values()),
        "rowgroups_pruned_on": sum(v["rowgroups_pruned"] for v in on.values()),
        "rowgroups_pruned_off": sum(v["rowgroups_pruned"]
                                    for v in off.values()),
        "rewrites_fired": sum(v["rewrites_fired"] for v in on.values()),
        "planning_ms_on_p50": plan_on[len(plan_on) // 2],
        "planning_ms_off_p50": plan_off[len(plan_off) // 2],
        "queries": {name: {"on": on[name], "off": off[name]}
                    for name, _q in queries},
        # direction-aware sub-metrics for the --compare gate
        "gate": {
            "bytes_skipped_ratio": {"value": round(ratio, 1), "unit": "x"},
            "files_pruned_on": {
                "value": sum(v["files_pruned"] for v in on.values()),
                "unit": "files"},
            "rowgroups_pruned_on": {
                "value": sum(v["rowgroups_pruned"] for v in on.values()),
                "unit": "rowgroups"},
            "planning_ms_on_p50": {
                "value": plan_on[len(plan_on) // 2], "unit": "ms"},
        },
    }


# -- config 12: device-resident hot-column scan cache ------------------------


def bench_device_scan(workdir):
    """2M-row table, residual-only predicate suite (every value scattered so
    footer stats prune NOTHING — the hot-column residual shape): three legs
    over the same queries, result identity asserted per query across all of
    them.

      host  — deviceResidual.mode=off: the Arrow host residual path
      cold  — mode=force on an empty ColumnCache: pays predicate-column
              decode + device upload + first-shape jit compiles
      warm  — mode=force again: every lane resident (columnCache.hits > 0,
              misses == 0), mask is one jitted pass per file

    Headline: warm-device speedup vs the host leg. On a CPU-only host
    (JAX_PLATFORMS=cpu, no accelerator) the speedup claim is skip-recorded
    (value -1, unit "skipped") — the legs still run so identity and the
    columnCache.* counter story are captured in the artifact."""
    import jax
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.obs import scan_report
    from delta_tpu.ops.column_cache import ColumnCache
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf as _c

    n = _rows(2_000_000)
    ids = np.arange(n, dtype=np.int64)
    A = 982_451_653  # prime > n: (i*A) % n is a permutation → scattered
    scattered = (ids * A) % n
    cats = np.array(["us-w", "us-e", "eu-c", "eu-w",
                     "ap-s", "ap-n", "sa-e", "af-s"])
    rng = np.random.RandomState(23)
    base_us = 1_577_836_800_000_000  # 2020-01-01 UTC
    span_us = 4 * 365 * 86_400_000_000  # ~4 years of timestamps
    data = pa.table({
        "id": ids,
        "price": scattered,
        "qty": rng.randint(1, 9, n).astype(np.int64),
        "cat": pa.array(cats[ids % len(cats)]),
        "ts": pa.array(base_us + scattered * (span_us // n),
                       pa.timestamp("us")),
    })
    path = os.path.join(workdir, "c12")
    log = DeltaLog.for_table(path)
    with _c.set_temporarily(**{
        "delta.tpu.write.targetFileRows": max(n // 8, 1000),
        "delta.tpu.write.rowGroupRows": max(n // 64, 500),
    }):
        WriteIntoDelta(log, "append", data).run()
    queries = [
        ("string_eq", "cat = 'eu-c'"),
        ("string_in", "cat in ('us-w', 'ap-s', 'af-s')"),
        ("num_scatter", f"price >= {int(0.9 * n)}"),
        ("arith", f"price * 2 + qty > {int(1.8 * n)}"),
        ("conj", "cat = 'us-w' and qty >= 6"),
        ("temporal_year", "year(ts) = 2021"),
        ("low_sel", f"price < {max(n // 100, 1)}"),
    ]
    tab = DeltaTable.for_path(path)
    with _c.set_temporarily(**{"delta.tpu.read.deviceResidual.mode": "off"}):
        tab.to_arrow(filters=[queries[0][1]])  # warm footers for every leg

    def run_leg(mode):
        out = {}
        c0 = telemetry.counters("columnCache")
        d0 = telemetry.counters("scan.device")
        t_leg = time.perf_counter()
        with _c.set_temporarily(**{
            "delta.tpu.read.deviceResidual.mode": mode,
        }):
            for name, q in queries:
                t0 = time.perf_counter()
                result = tab.to_arrow(filters=[q])
                wall_s = time.perf_counter() - t0
                rep = scan_report.last_scan_report()
                out[name] = {
                    "rows": result.num_rows,
                    "id_sum": int(np.asarray(result.column("id")).sum()),
                    "wall_ms": round(wall_s * 1000, 1),
                    "device_residual": rep.device_residual,
                    "bytes_device_survivor": rep.bytes_device_survivor,
                    "rowgroups_device_skipped": rep.row_groups_device_skipped,
                }
        total_s = time.perf_counter() - t_leg
        c1 = telemetry.counters("columnCache")
        d1 = telemetry.counters("scan.device")
        counters = {k: c1.get(k, 0) - c0.get(k, 0)
                    for k in set(c0) | set(c1)}
        counters.update({k: d1.get(k, 0) - d0.get(k, 0)
                         for k in set(d0) | set(d1)})
        return {"total_s": round(total_s, 3), "queries": out,
                "counters": {k: v for k, v in sorted(counters.items()) if v}}

    host = run_leg("off")
    ColumnCache.reset()  # cold leg starts from an empty cache, honestly
    cold = run_leg("force")
    warm = run_leg("force")
    for name, _q in queries:
        # identity on every query, every leg: the device mask may only
        # change where rows decode, never what returns
        for leg, tag in ((cold, "cold"), (warm, "warm")):
            assert leg["queries"][name]["rows"] == \
                host["queries"][name]["rows"], (name, tag)
            assert leg["queries"][name]["id_sum"] == \
                host["queries"][name]["id_sum"], (name, tag)
        assert warm["queries"][name]["device_residual"] == "device", name
    # the cache story the headline rests on: cold decodes, warm serves
    assert cold["counters"].get("columnCache.misses", 0) > 0
    assert warm["counters"].get("columnCache.hits", 0) > 0
    assert warm["counters"].get("columnCache.misses", 0) == 0
    assert warm["counters"].get("scan.device.engaged", 0) == len(queries)
    speedup = host["total_s"] / max(warm["total_s"], 1e-9)
    platform = jax.devices()[0].platform
    accelerated = platform not in ("cpu",)
    result = {
        "metric": "device_scan_warm_speedup",
        "value": round(speedup, 2) if accelerated else -1,
        "unit": "x" if accelerated else "skipped",
        "vs_baseline": round(speedup, 2) if accelerated else 0,
        "baseline": "same suite with delta.tpu.read.deviceResidual.mode=off "
                    "(the Arrow host residual path)",
        "rows": n,
        "platform": platform,
        "warm_speedup_measured": round(speedup, 2),
        "legs": {"host": host, "cold": cold, "warm": warm},
        "gate": {
            "host_total_s": {"value": host["total_s"], "unit": "s"},
            "warm_total_s": {"value": warm["total_s"], "unit": "s"},
            "warm_cache_hits": {
                "value": warm["counters"].get("columnCache.hits", 0),
                "unit": "hits"},
        },
    }
    if not accelerated:
        result["note"] = (
            f"no accelerator (platform={platform}): warm-device speedup "
            "claim skip-recorded; all three legs still ran with per-query "
            "result identity asserted and columnCache.* counters captured")
    else:
        result["gate"]["warm_speedup"] = {"value": round(speedup, 2),
                                          "unit": "x"}
    return result


# -- config 4: streaming tail of a 1k-commit log -----------------------------


def bench_streaming_tail(workdir):
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.streaming.source import DeltaSource

    n_commits = max(int(1000 * SCALE), 100)
    path = os.path.join(workdir, "c4")
    log = DeltaLog.for_table(path)
    rng = np.random.RandomState(9)
    for i in range(n_commits):
        WriteIntoDelta(log, "append", pa.table({
            "id": np.arange(i * 10, i * 10 + 10, dtype=np.int64),
            "v": rng.randint(0, 100, 10).astype(np.int64),
        })).run()

    def tail_all():
        DeltaLog.clear_cache()
        src = DeltaSource(DeltaLog.for_table(path), max_files_per_trigger=100,
                          starting_version=0)
        off = src.initial_offset()
        total = batches = 0
        while True:
            end = src.latest_offset(off)
            if end is None:
                break
            total += src.get_batch(off, end).num_rows
            off = end
            batches += 1
        return total, batches

    tail_s, (rows_read, n_batches) = _timed(tail_all)
    assert rows_read == n_commits * 10

    # baseline: rebuild the snapshot at each batch boundary (what a
    # non-incremental consumer pays), same batch count
    def naive():
        from delta_tpu.exec.scan import scan_to_table

        total = 0
        seen = 0
        for b in range(n_batches):
            DeltaLog.clear_cache()
            hi = min((b + 1) * 100, n_commits) - 1
            snap = DeltaLog.for_table(path).get_snapshot_at(hi)
            t = scan_to_table(snap)
            total += t.num_rows - seen
            seen = t.num_rows
        return total

    naive_s, naive_rows = min((_timed(naive) for _ in range(2)), key=lambda x: x[0])
    assert naive_rows == rows_read

    # CDC-tailing leg (the BASELINE config names it): the change feed of the
    # same 1k-commit log streamed through DeltaCDFSource
    def tail_cdf():
        from delta_tpu.streaming.source import DeltaCDFSource

        DeltaLog.clear_cache()
        src = DeltaCDFSource(DeltaLog.for_table(path),
                             max_files_per_trigger=100, starting_version=0)
        off = src.initial_offset()
        total = 0
        while True:
            end = src.latest_offset(off)
            if end is None:
                break
            total += src.get_batch(off, end).num_rows
            off = end
        return total

    cdf_s, cdf_rows = _timed(tail_cdf)
    assert cdf_rows == rows_read  # append-only log: every row is an insert
    return {
        "metric": "streaming_tail_1k_commit_log",
        "value": round(n_commits / tail_s, 1),
        "unit": "commits/s",
        "vs_baseline": round(naive_s / tail_s, 2),
        "baseline": "snapshot rebuild + full rescan per micro-batch",
        "cdf_commits_per_s": round(n_commits / cdf_s, 1),
    }


# -- config 5: checkpoint replay, 10k versions -------------------------------


def bench_checkpoint_replay(workdir):
    """End-to-end snapshot state reconstruction from a cold on-disk log:
    checkpoint Parquet at the midpoint + a JSON commit tail, both paths
    reading the same files. Device path = columnar decode (log/columnar.py)
    + the slim winner kernel; baseline = the reference-shaped sequential
    object replay (checkpoint rows + per-line JSON decode into a dict)."""
    from delta_tpu.log import checkpoints as ckpt_mod
    from delta_tpu.log.columnar import decode_segment
    from delta_tpu.ops import replay_kernel
    from delta_tpu.protocol import filenames
    from delta_tpu.protocol.actions import AddFile, action_from_json
    from delta_tpu.storage.logstore import get_log_store

    n_versions, per_commit, n_paths = max(int(10_000 * SCALE), 500), 20, 50_000
    ckpt_v = n_versions // 2
    rng = np.random.RandomState(7)
    log_path = os.path.join(workdir, "c5", "_delta_log")
    store = get_log_store(log_path)

    active = {}
    for v in range(n_versions):
        lines = []
        for _ in range(per_commit):
            p = f"part-{rng.randint(n_paths):05d}-{v}.parquet"
            if rng.rand() < 0.85:
                sz = int(rng.randint(1, 1 << 24))
                lines.append(json.dumps({"add": {
                    "path": p, "partitionValues": {}, "size": sz,
                    "modificationTime": v, "dataChange": True}}))
                active[p] = sz
            else:
                lines.append(json.dumps({"remove": {
                    "path": p, "deletionTimestamp": v * 1000, "dataChange": True}}))
                active.pop(p, None)
        store.write(f"{log_path}/{filenames.delta_file(v)}", lines)
        if v == ckpt_v:
            ckpt_actions = [AddFile(path=p, size=s, modification_time=0,
                                    data_change=False) for p, s in active.items()]
            ckpt_mod.write_checkpoint(store, log_path, v, ckpt_actions)

    ckpt_paths = [f"{log_path}/{filenames.checkpoint_file_single(ckpt_v)}"]
    deltas = [f"{log_path}/{filenames.delta_file(v)}" for v in range(ckpt_v + 1, n_versions)]

    def host_end_to_end():
        state = {}
        for a in ckpt_mod.read_checkpoint_actions(store, ckpt_paths):
            d = a.__class__.__name__
            if d == "AddFile":
                state[a.path] = a.size
        for p in deltas:
            for line in store.read_iter(p):
                a = action_from_json(line)
                d = a.__class__.__name__
                if d == "AddFile":
                    state[a.path] = a.size
                elif d == "RemoveFile":
                    state.pop(a.path, None)
        return len(state)

    host_s, host_n = min((_timed(host_end_to_end) for _ in range(2)), key=lambda x: x[0])
    assert host_n == len(active)

    phases = {}

    def device_end_to_end():
        t0 = time.perf_counter()
        cols = decode_segment(store, ckpt_paths, deltas)
        t1 = time.perf_counter()
        r = replay_kernel.replay_columns(cols, min_retention_ts=0, device=True)
        t2 = time.perf_counter()
        phases["decode_ms"] = round((t1 - t0) * 1000, 1)
        phases["device_winner_ms"] = round((t2 - t1) * 1000, 1)
        return int(r.stats.num_files)

    # warm the jit cache, then min-of-3 to damp dispatch-latency jitter
    device_end_to_end()
    runs = [_timed(device_end_to_end) for _ in range(3)]
    dev_s = min(s for s, _ in runs)
    dev_n = runs[0][1]
    assert host_n == dev_n, (host_n, dev_n)

    # host-winner variant (no device round trip) for the breakdown
    cols = decode_segment(store, ckpt_paths, deltas)
    hw_s = min(_timed(lambda: replay_kernel.replay_columns(
        cols, min_retention_ts=0, device=False))[0] for _ in range(3))
    return {
        "metric": "checkpoint_replay_10k_versions_200k_actions",
        "value": round(dev_s * 1000, 1),
        "unit": "ms",
        "vs_baseline": round(host_s / dev_s, 2),
        "baseline": "sequential object replay incl. checkpoint Parquet read "
                    "+ per-line JSON decode (reference Snapshot.scala shape)",
        "host_baseline_ms": round(host_s * 1000, 1),
        "phases": dict(phases, host_winner_ms=round(hw_s * 1000, 2)),
    }


# -- config 6: hot-table batched scan planning (device-resident state) -------


def bench_hot_plan(workdir, partitioned=False):
    """The query-server shape: a 1M-file table's scan lanes resident in HBM
    (`ops/state_cache`), serving batches of 256 point-range plans. Baseline =
    the strongest host implementation (vectorized numpy over the same float64
    mirrors, batched); the reference-shaped per-query path (materialize
    AddFiles + re-evaluate stats per query, `DataSkippingReader`'s shape) is
    also sampled for scale. The win condition VERDICT r3 set: the device
    engages under AUTO routing and beats the host."""
    import json as _json

    from delta_tpu import DeltaLog
    from delta_tpu.exec.scan import plan_scans
    from delta_tpu.log import checkpoints as ckpt_mod
    from delta_tpu.ops.state_cache import DeviceStateCache
    from delta_tpu.protocol import filenames
    from delta_tpu.protocol.actions import AddFile, Metadata, Protocol
    from delta_tpu.schema.types import DoubleType, LongType, StructType
    from delta_tpu.storage.logstore import get_log_store
    from delta_tpu.utils.config import conf

    n_files = max(int(1_000_000 * SCALE), 20_000)
    n_queries = 256
    rng = np.random.RandomState(13)
    table_path = os.path.join(workdir, "c6p" if partitioned else "c6")
    log_path = os.path.join(table_path, "_delta_log")
    store = get_log_store(log_path)

    schema = StructType()
    for c in range(4):
        schema = schema.add(f"c{c}", DoubleType() if c % 2 else LongType())
    part_cols = []
    days = []
    if partitioned:
        # the reference's primary pruning path: a date-partitioned layout
        # (DeltaLog.scala:500-547 rewritePartitionFilters shapes)
        from delta_tpu.schema.types import StringType

        schema = schema.add("day", StringType())
        part_cols = ["day"]
        import datetime as _dt

        n_days = 732
        day0 = _dt.date(2020, 1, 1)
        days = [(day0 + _dt.timedelta(days=d)).isoformat()
                for d in range(n_days)]
    meta = Metadata(schema_string=schema.to_json(),
                    partition_columns=part_cols)
    proto = Protocol(1, 2)
    store.write(f"{log_path}/{filenames.delta_file(0)}",
                [proto.json(), meta.json()])

    # 1M files, each covering a narrow range per column (a well-clustered
    # table: point queries match a handful of files)
    base = {f"c{c}": np.sort(rng.rand(n_files) * 1e6) if c % 2 else
            np.sort(rng.randint(0, 1 << 40, n_files).astype(np.int64))
            for c in range(4)}
    width = {f"c{c}": 1e6 / n_files * 8 if c % 2 else max((1 << 40) // n_files * 8, 1)
             for c in range(4)}
    adds = []
    for i in range(n_files):
        mins = {c: (float(v[i]) if c in ("c1", "c3") else int(v[i])) for c, v in base.items()}
        maxs = {c: (float(v[i] + width[c]) if c in ("c1", "c3") else int(v[i] + width[c]))
                for c, v in base.items()}
        stats = _json.dumps({"numRecords": 10000, "minValues": mins,
                             "maxValues": maxs,
                             "nullCount": {c: 0 for c in base}})
        pv = {"day": days[i * len(days) // n_files]} if partitioned else {}
        adds.append(AddFile(path=f"part-{i:07d}.parquet", size=1 << 20,
                            modification_time=0, data_change=False, stats=stats,
                            partition_values=pv))
    ckpt_mod.write_checkpoint(store, log_path, 0, [proto, meta] + adds)

    DeltaLog.clear_cache()
    DeviceStateCache.reset()
    log = DeltaLog.for_table(table_path)
    t0 = time.perf_counter()
    snap = log.update()
    snap.num_of_files  # force state reconstruction
    decode_s = time.perf_counter() - t0

    # queries: point ranges on 2 columns (a dashboard's WHERE shapes);
    # partitioned tables mix partition equality/ranges with stat ranges
    qs = []
    for k in range(n_queries):
        i = rng.randint(n_files)
        lo0 = int(base["c0"][i])
        lo1 = float(base["c1"][i])
        if partitioned and k % 2 == 0:
            d = days[i * len(days) // n_files]
            if k % 4 == 0:
                qs.append([f"day = '{d}' AND c0 >= {lo0}"])
            else:
                qs.append([f"day >= '{d}' AND day <= '{days[min(i * len(days) // n_files + 3, len(days) - 1)]}'"])
        else:
            qs.append([f"c0 >= {lo0} AND c0 <= {lo0 + int(width['c0'])} "
                       f"AND c1 >= {lo1:.6f} AND c1 <= {lo1 + width['c1']:.6f}"])

    from delta_tpu.parallel import link

    link.profile()  # backend + link warm-up: not a per-table cost
    t0 = time.perf_counter()
    entry = DeviceStateCache.instance().get(snap)
    assert entry is not None
    parse_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    entry.ensure_resident()
    upload_s = time.perf_counter() - t0
    build_s = parse_s + upload_s

    def run(mode):
        with conf.set_temporarily(**{"delta.tpu.stateCache.devicePlan.mode": mode}):
            return plan_scans(snap, qs, k=256)

    from delta_tpu.parallel import link

    link.profile()  # process-wide calibration, not a per-batch cost
    run("force")  # warm the plan-kernel compile
    dev_s = min(_timed(lambda: run("force"))[0] for _ in range(3))
    host_s = min(_timed(lambda: run("off"))[0] for _ in range(3))
    auto_s, auto_plans = min(
        (_timed(lambda: run("auto")) for _ in range(2)), key=lambda x: x[0])
    auto_via = auto_plans[0].via

    # parity spot-check: the device's f32 verdict may keep an extra boundary
    # file (conservative rounding) but never drop one the host keeps
    dev_plans, host_plans = run("force"), run("off")
    for d, h in zip(dev_plans[:16], host_plans[:16]):
        assert set(h.paths) <= set(d.paths)
        assert d.count <= h.count + 4, (d.count, h.count)

    # reference-shaped per-query sample: files_for_scan on materialized
    # AddFiles (the all_files dataclass path), 2 queries, extrapolated
    from delta_tpu.exec.scan import scan_files

    sample_n = 2
    with conf.set_temporarily(**{"delta.tpu.stateCache.enabled": False,
                                 "delta.tpu.stateCache.serveScans": False}):
        ref_s, _ = _timed(lambda: [scan_files(snap, q) for q in qs[:sample_n]])
    ref_extrapolated_s = ref_s / sample_n * n_queries

    # steady-state: a new commit tails in incrementally (no rebuild)
    new_add = AddFile(path="part-new.parquet", size=1 << 20, modification_time=1,
                      data_change=True,
                      partition_values={"day": days[-1]} if partitioned else {},
                      stats=_json.dumps({"numRecords": 1, "minValues": {"c0": 1},
                                         "maxValues": {"c0": 2},
                                         "nullCount": {c: 0 for c in base}}))
    store.write(f"{log_path}/{filenames.delta_file(1)}", [new_add.json()])
    DeviceStateCache.instance().get(log.update())  # first apply warms the jits
    from dataclasses import replace as _dc_replace

    new_add2 = _dc_replace(new_add, path="part-new2.parquet")
    store.write(f"{log_path}/{filenames.delta_file(2)}", [new_add2.json()])
    snap2 = log.update()
    tail_s, entry2 = _timed(lambda: DeviceStateCache.instance().get(snap2))
    assert entry2 is entry and entry2.version == 2, "tail must apply incrementally"

    # serving-envelope coverage: a MIXED workload (ranges, ORs, INs, null
    # tests, unknown columns, strings) — what fraction serves resident?
    mixed = []
    for j in range(64):
        i = rng.randint(n_files)
        lo0 = int(base["c0"][i])
        shapes = [
            [f"c0 >= {lo0} AND c0 <= {lo0 + int(width['c0'])}"],     # range
            [f"c0 = {lo0} OR c0 = {lo0 + 9999}"],                    # OR
            [f"c0 IN ({lo0}, {lo0 + 7}, {lo0 + 77})"],               # IN
            ["c1 IS NULL"],                                          # null test
            ["c3 >= 0.5 AND c1 >= 0.1"],                             # wide range
            ["c1 IS NOT NULL"],                              # null-count test
        ]
        mixed.append(shapes[j % len(shapes)])
    mixed_plans = plan_scans(log.update(), mixed, k=64)
    resident_served = sum(1 for p_ in mixed_plans if p_.via != "scan")
    per_q_device_ms = dev_s / n_queries * 1000
    return {
        "metric": ("hot_table_batched_scan_planning_1M_files_256_queries"
                   + ("_partitioned" if partitioned else "")),
        "value": round(dev_s * 1000, 1),
        "unit": "ms",
        "vs_baseline": round(host_s / dev_s, 2),
        "baseline": "strongest host path on the same machine: batched "
                    "vectorized numpy over resident float64 mirrors",
        "auto_used_device": auto_via == "device",
        "auto_ms": round(auto_s * 1000, 1),
        "host_resident_ms": round(host_s * 1000, 1),
        "device_ms": round(dev_s * 1000, 1),
        "per_query_device_ms": round(per_q_device_ms, 3),
        "reference_shaped_extrapolated_s": round(ref_extrapolated_s, 1),
        "vs_reference_shaped": round(ref_extrapolated_s / dev_s, 1),
        "state_decode_s": round(decode_s, 2),
        "cache_build_s": round(build_s, 2),
        "cache_build_parse_s": round(parse_s, 2),
        "cache_build_upload_s": round(upload_s, 2),
        "incremental_tail_apply_ms": round(tail_s * 1000, 1),
        "mixed_workload_resident_pct": round(100.0 * resident_served / len(mixed), 1),
        "n_files": n_files,
    }


# -- config 7: replay scale probe (device crossover calibration) -------------


def bench_replay_scale(workdir):
    """Where does the device replay winner kernel cross over the host
    scatter? Three legs per size, measured the same way (min of 3):

      host      — the numpy scatter winner (SegmentColumns.winner_mask)
      upload    — winner_mask_device: ship the path column, kernel, bits back
      resident  — the column already in HBM (ops/state_cache steady state):
                  kernel + live-prefix bits download only

    The honest record VERDICT r3 asked for: the routing thresholds in
    parallel/link.py are checked against live per-row numbers, and the
    crossover (or its absence, on a link where uploads dominate) is stated
    per leg rather than assumed."""
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops import replay_kernel

    rng = np.random.RandomState(3)
    sizes = [int(n * SCALE) for n in (1_000_000, 4_000_000, 16_000_000)]
    sizes = [max(s, 100_000) for s in sizes]
    results = []
    crossover_upload = crossover_resident = None
    for n in sizes:
        n_paths = max(n // 10, 1)
        path_id = rng.randint(0, n_paths, n).astype(np.int32)

        def host_winner():
            last = np.full(n_paths, -1, np.int64)
            last[path_id] = np.arange(n)
            mask = np.zeros(n, bool)
            mask[last[last >= 0]] = True
            return mask

        host_ms = min(_timed(host_winner)[0] for _ in range(3)) * 1000

        replay_kernel.winner_mask_device(path_id)  # warm compile per shape
        up_ms = min(
            _timed(lambda: replay_kernel.winner_mask_device(path_id))[0]
            for _ in range(3)
        ) * 1000

        cap = replay_kernel._next_pow2(n)
        padded = np.full(cap, -1, np.int32)
        padded[:n] = path_id
        dev = jax.device_put(padded)
        jax.block_until_ready(dev)

        def resident_winner():
            bits = replay_kernel._winner_bits_kernel(dev)
            return np.asarray(bits[: (n + 7) // 8])

        resident_winner()
        res_ms = min(_timed(resident_winner)[0] for _ in range(3)) * 1000
        del dev
        results.append({
            "actions": n,
            "host_ms": round(host_ms, 2),
            "device_upload_ms": round(up_ms, 1),
            "device_resident_ms": round(res_ms, 1),
        })
        if crossover_upload is None and up_ms < host_ms:
            crossover_upload = n
        if crossover_resident is None and res_ms < host_ms:
            crossover_resident = n

    from delta_tpu.parallel import link

    lp = link.profile()
    biggest = results[-1]
    return {
        "metric": "replay_winner_scale_probe",
        "value": biggest["device_resident_ms"],
        "unit": "ms",
        "vs_baseline": round(
            biggest["host_ms"] / biggest["device_resident_ms"], 2
        ),
        "baseline": f"host numpy scatter winner at {biggest['actions']} actions",
        "sweep": results,
        "crossover_actions_upload": crossover_upload,
        "crossover_actions_resident": crossover_resident,
        "link_MBps": {"up": round(lp.up_mbps, 1), "down": round(lp.down_mbps, 1),
                      "latency_ms": round(lp.latency_s * 1000, 1)},
        "note": "upload leg is link-bound (behind a slow link a crossover "
                "may not exist); the resident leg is the steady state the "
                "state cache serves",
    }


# -- config 2x: north-star-scale MERGE (10 GB class) -------------------------


def bench_merge_scale(workdir):
    """VERDICT r4 #3: push the MERGE bench toward BASELINE.json's stated
    shape (100 GB TPC-DS store_sales). Sized to fit the driver budget
    (ISSUE 6 satellite: r5's 100M-row leg was what blew the round to
    rc=124): default 40M rows ≈ 4 GB class, raisable via BENCH_2X_ROWS;
    a store_sales target merged with a 1/10th source through the engine's
    AUTO paths (deletion vectors + resident key lane). Two successive
    merges measure cold (builds the resident lane post-commit) and steady
    state (probes HBM residency, advances the tail). Timed once each —
    min-of-N would double a ~minutes-long config; the ±band is stated
    instead. The reference-shaped full-rewrite host baseline is NOT re-run
    at this scale; config 2 carries that comparison and config 8 carries
    the 100M-key host-vs-device probe."""
    import resource

    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.utils.config import conf

    base_rows = int(float(os.environ.get("BENCH_2X_ROWS", "40000000")))
    n_target = max(int(base_rows * SCALE), 2_000_000)
    n_source = max(n_target // 10, 200_000)
    rng = np.random.RandomState(17)
    path = os.path.join(workdir, "c2x")
    log = DeltaLog.for_table(path)
    t0 = time.perf_counter()
    target = _store_sales(n_target, rng)
    WriteIntoDelta(log, "append", target).run()
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    build_s = time.perf_counter() - t0
    gb = _dir_bytes(path) / 1e9
    target_keys = np.asarray(target.column("ss_item_sk"))
    del target

    def mk_source(seed, fresh_base):
        r = np.random.RandomState(seed)
        existing = target_keys[r.choice(n_target, n_source // 2, replace=False)]
        fresh = np.arange(fresh_base, fresh_base + (n_source - n_source // 2),
                          dtype=np.int64)
        keys = np.concatenate([existing, fresh])
        r.shuffle(keys)
        src = _store_sales(n_source, np.random.RandomState(seed + 1))
        return src.set_column(0, "ss_item_sk", pa.array(keys))

    def run_merge(src):
        DeltaLog.clear_cache()
        lg = DeltaLog.for_table(path)
        with conf.set_temporarily(**{
            "delta.tpu.merge.devicePath.mode": "auto",
            "delta.tpu.deletionVectors.enabled": True,
            "delta.tpu.merge.residentKeys.enabled": True,
        }):
            cmd = MergeIntoCommand(
                lg, src, "t.ss_item_sk = s.ss_item_sk",
                [MergeClause("update", assignments=None)],
                [MergeClause("insert", assignments=None)],
                source_alias="s", target_alias="t",
            )
            cmd.run()
        assert cmd.metrics["numTargetRowsUpdated"] == n_source // 2
        assert cmd.metrics["numTargetRowsInserted"] == n_source - n_source // 2
        return cmd

    src1 = mk_source(31, n_target * 4)
    cold_s, cold = _timed(lambda: run_merge(src1))
    del src1

    # steady state needs the resident key lane UP: wait for the background
    # build the cold merge kicked off (a projected read of every file's
    # keys — ~a minute of IO at this scale), then ship it to HBM and sort
    # it explicitly so the timed leg measures the steady probe, not the
    # one-time residency cost (reported separately here)
    import jax

    from delta_tpu.ops.key_cache import KeyCache

    with conf.set_temporarily(**{
            "delta.tpu.keyCache.maxBytes": str(8 << 30)}):
        t0 = time.perf_counter()
        entry = None
        while time.perf_counter() - t0 < 300:
            with KeyCache.instance()._lock:
                cands = [e for (k, e) in KeyCache.instance()._entries.items()
                         if k[0] == log.log_path]
            if cands:
                entry = cands[0]
                break
            time.sleep(2)
        build_wait_s = time.perf_counter() - t0
        residency_upload_s = probe_warm_s = None
        if entry is not None:
            t0 = time.perf_counter()
            entry.ensure_resident()
            with entry._lock:
                entry._ensure_sorted()
            jax.block_until_ready(entry._dev["sorted_keys"])
            np.asarray(entry._dev["sorted_keys"][:8])  # force completion
            residency_upload_s = time.perf_counter() - t0
            # absorb the per-shape probe compile outside the timed leg
            t0 = time.perf_counter()
            warm = entry.probe_async(
                np.zeros(n_source, np.int64), np.ones(n_source, bool))
            if warm is not None:
                try:
                    warm.result()
                except Exception:
                    pass
            probe_warm_s = time.perf_counter() - t0
            # a slow link's bandwidth can degrade under sustained traffic
            # and recover after idle; the residency ship is
            # a one-time event in the steady state being measured, so let
            # the link recover before the timed leg rather than charging
            # its hangover to every subsequent merge (bounded: the
            # per-config deadline is the hard stop)
            time.sleep(20)
        src2 = mk_source(37, n_target * 5)
        steady_s, steady = _timed(lambda: run_merge(src2))
        src_gb = src2.nbytes / 1e9
        del src2
    peak_gb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1e6
    return {
        "metric": "merge_upsert_100M_rows_10GB_class",
        "value": round((gb + src_gb) / cold_s, 3),
        "unit": "GB/s",
        "vs_baseline": round(steady_s / cold_s, 2),
        "baseline": "the second (steady-state) engine merge on the same "
                    "table — an honest scale record, not a win claim: on "
                    "a 1-vCPU host behind a slow link the 100M-row "
                    "merge is bound by host decode/apply and the one-time "
                    "residency ship, so the steady leg can measure SLOWER "
                    "than cold (see notes; config 8 isolates the probe "
                    "itself, which does win at this scale)",
        "rows_target": n_target,
        "rows_source": n_source,
        "table_gb": round(gb, 2),
        "table_build_s": round(build_s, 1),
        "cold_merge_s": round(cold_s, 1),
        "steady_merge_s": round(steady_s, 1),
        "resident_build_wait_s": round(build_wait_s, 1),
        "residency_upload_s": (round(residency_upload_s, 1)
                               if residency_upload_s is not None else None),
        "probe_compile_warm_s": (round(probe_warm_s, 1)
                                 if probe_warm_s is not None else None),
        "cold_join_path": cold._join_path,
        "steady_join_path": steady._join_path,
        "cold_phases_ms": {k: round(v, 0) for k, v in cold.phase_ms.items()},
        "steady_phases_ms": {k: round(v, 0) for k, v in steady.phase_ms.items()},
        "peak_rss_gb": round(peak_gb, 1),
        "note": "timed once per leg (~minutes each at this scale; host "
                "noise band ±30% applies); the reference-shaped host "
                "baseline is carried at 1/10th scale by config 2 and the "
                "100M-key probe comparison by config 8. Where time goes at "
                "10x scale: the join/decode/apply phases are host-bound "
                "(1 vCPU) and grow superlinearly once the working set "
                "passes the page cache; the ~0.5 GB residency ship "
                "(int32-narrowed) costs minutes behind a slow link and "
                "can degrade it for the leg that follows, so AUTO routing "
                "keeps later merges on the host there",
    }


# -- config 8: steady-state resident MERGE membership probe ------------------


def bench_resident_probe(workdir):
    """The data-plane shape VERDICT r4 demanded: the MERGE membership probe
    from warm HBM residency (`ops/key_cache` sorted-slab steady state),
    isolated — source keys up, head + compacted O(matched) pairs down (the
    fused join) — swept over target sizes, with a full phase breakdown and
    the attached-chip extrapolation.

    Baselines are the STRONGEST host paths on the same machine, both given
    resident decoded key mirrors for free (no Parquet decode charged):
      host_searchsorted — sort the 1M source, binary-search all N targets
      host_isin_table   — np.isin(kind='table') bool-lookup over the range
    The engine's real host join additionally pays a per-merge key decode
    (link.HOST_KEY_DECODE_S_PER_ROW, measured); reported as a modeled line.

    Honesty notes: the 10M entry pays the real tiled upload (build_s);
    larger slabs are materialized device-side from the same congruential
    permutation the host mirrors use (identical content, skipping a
    multi-GB upload — a one-time cost in production, reported at the 10M
    point)."""
    import jax
    import jax.numpy as jnp

    from delta_tpu.ops.join_kernel import _bucket
    from delta_tpu.ops.key_cache import ResidentJoinKeys
    from delta_tpu.parallel import link

    M_SRC = max(int(1_000_000 * SCALE), 100_000)
    sizes = sorted({max(int(n * SCALE), 1_000_000)
                    for n in (10_000_000, 30_000_000, 100_000_000)})
    A = 982_451_653  # prime > any n here: (i*A) % n is a permutation

    def keyfn_host(n):
        return ((np.arange(n, dtype=np.int64) * A) % n) * 2

    def mk_entry(n, real_upload):
        e = ResidentJoinKeys("bench", "mid", 0, f"bench-{n}", ["k"])
        keys = keyfn_host(n)
        e.h_keys = keys
        e.h_valid = np.ones(n, bool)
        e.h_nullok = np.ones(n, bool)
        e.h_min, e.h_max = 0, 2 * (n - 1)
        e.num_rows, e.capacity = n, _bucket(n)
        step = 2_097_152
        e.slabs = {f"f{i}": (off, min(step, n - off))
                   for i, off in enumerate(range(0, n, step))}
        build_s = None
        if real_upload:
            t0 = time.perf_counter()
            e.ensure_resident()
            build_s = time.perf_counter() - t0
        else:
            cap = e.capacity
            with enable_x64():
                iota = jnp.arange(cap, dtype=jnp.int64)
                dk = jnp.where(iota < n, ((iota * A) % n) * 2, 0)
                dvv = iota < n
                jax.block_until_ready((dk, dvv))
            e._dev = {"keys": dk, "valid": dvv}
            e._sort_stale = True
        with e._lock:  # first sort: absorbs the per-shape compile
            e._ensure_sorted()
        jax.block_until_ready(e._dev["sorted_keys"])
        t0 = time.perf_counter()  # steady-state re-sort (the advance cost)
        with e._lock:
            e._sort_stale = True
            e._dev.pop("sorted_keys", None)
            e._dev.pop("perm", None)
            e._ensure_sorted()
        jax.block_until_ready(e._dev["sorted_keys"])
        sort_s = time.perf_counter() - t0
        return e, keys, build_s, sort_s

    def sources(n, keys):
        half = M_SRC // 2
        rng = np.random.RandomState(41)
        # clustered: hits form a contiguous KEY range (a CDC upsert touching
        # one id band) — the shape the coarse-fine hot-block download serves;
        # misses are odd keys (absent). The slab holds every even key < 2n.
        k0 = (n // 3) * 2
        hits_c = np.arange(k0, k0 + 2 * half, 2, dtype=np.int64)
        miss = rng.randint(0, n, M_SRC - half).astype(np.int64) * 2 + 1
        clustered = np.concatenate([hits_c, miss])
        rng.shuffle(clustered)
        # uniform: hits scattered over the whole key space (dense blocks,
        # the device-unsort + full-mask download path)
        rows_u = rng.choice(n, half, replace=False)
        uniform = np.concatenate([keys[rows_u], miss])
        rng.shuffle(uniform)
        return {"clustered": clustered, "uniform": uniform}

    lp = link.profile()
    sweep = []
    for n in sizes:
        real_upload = n <= 12_000_000
        try:
            e, keys, build_s, sort_s = mk_entry(n, real_upload)
        except Exception as ex:  # HBM/link failure: record and continue
            sweep.append({"targets": n, "skipped": str(ex)[:120]})
            continue
        srcs = sources(n, keys)
        entry_res = {"targets": n, "m_source": M_SRC,
                     "build_upload_s": round(build_s, 2) if build_s else None,
                     "device_sort_s": round(sort_s, 3)}
        for label, s_keys in srcs.items():
            s_ok = np.ones(len(s_keys), bool)
            trials = 3 if n <= 40_000_000 else 2

            # host winners on resident mirrors
            def host_ss():
                ss = np.sort(s_keys)
                ix = np.searchsorted(ss, keys)
                ix[ix == len(ss)] = len(ss) - 1
                return ss[ix] == keys

            def host_tab():
                return np.isin(keys, s_keys, kind="table")

            h_ss = min(_timed(host_ss)[0] for _ in range(trials))
            try:
                h_tab = min(_timed(host_tab)[0] for _ in range(trials))
            except TypeError:  # numpy without kind=
                h_tab = float("inf")
            host_best = min(h_ss, h_tab)

            # device steady state through the public API (warm first)
            e.probe_async(s_keys, s_ok).result()
            dev_total = min(
                _timed(lambda: e.probe_async(s_keys, s_ok).result())[0]
                for _ in range(trials))

            # the engine's real host join additionally decodes target keys
            host_engine_modeled = host_best + n * link.HOST_KEY_DECODE_S_PER_ROW
            # the MERGE router's decision for this shape (the cost model
            # in commands/merge.py:_launch_resident_probe, live link terms)
            auto_device_s = link.resident_probe_device_s(n, len(s_keys), lp)
            auto_host_s = ((n + len(s_keys)) * link.HOST_JOIN_S_PER_ROW
                           + n * link.HOST_KEY_DECODE_S_PER_ROW)
            entry_res[label] = {
                "auto_routes_device": bool(auto_device_s < auto_host_s),
                "host_best_ms": round(host_best * 1000, 1),
                "host_searchsorted_ms": round(h_ss * 1000, 1),
                "host_isin_table_ms": round(h_tab * 1000, 1)
                if h_tab != float("inf") else None,
                "host_engine_modeled_ms": round(host_engine_modeled * 1000, 1),
                "device_total_ms": round(dev_total * 1000, 1),
                "device_beats_host_resident": bool(dev_total < host_best),
            }
        del e
        sweep.append(entry_res)

    # headline: the largest measured shape's clustered leg
    top = next((s for s in reversed(sweep) if "clustered" in s), None)
    if top is None:
        return {"metric": "resident_merge_probe_steady_state", "value": -1,
                "unit": "ms", "vs_baseline": 0, "sweep": sweep}
    c = top["clustered"]
    return {
        "metric": "resident_merge_probe_steady_state",
        "value": c["device_total_ms"],
        "unit": "ms",
        "vs_baseline": round(c["host_best_ms"] / c["device_total_ms"], 2),
        "baseline": f"strongest host membership path on resident mirrors at "
                    f"{top['targets']} target keys (clustered hits)",
        "sweep": sweep,
        "link_MBps": {"up": round(lp.up_mbps, 1),
                      "down": round(lp.down_mbps, 1),
                      "latency_ms": round(lp.latency_s * 1000, 1)},
        "note": "device_total is the public probe_async round trip (source "
                "upload + probe kernel + head + pair kernel + O(matched) "
                "pair fetch)",
    }


# -- config 11: fleet observability plane ------------------------------------


def bench_fleet(workdir):
    """Config 11: K registered tables x a skewed (one-hot-table) commit +
    scan workload. Measures what the fleet plane costs and what it serves:

    * scraper steady-state overhead — the same workload with the
      ``delta-obs-scraper`` daemon OFF vs ON (hot 100ms interval, SLO
      evaluation riding every scrape), and the same pair again under a
      telemetry blackout, where the ON leg must cost ≈0 (the blackout
      guarantee: a ticking scraper does no registry work);
    * /fleet and /slo route latency (p50/p95 over N GETs) with the rings
      warm and a live doctor sweep per /fleet request.
    """
    import http.client

    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.obs import fleet, slo, timeseries
    from delta_tpu.obs.server import ObsServer
    from delta_tpu.utils.config import conf

    K = 6
    ops_per_leg = max(int(400 * min(SCALE, 2.0)), 40)
    base = os.path.join(workdir, "fleet")
    rng = np.random.RandomState(7)

    def ids(n, start=0):
        import pyarrow as pa

        return pa.table({"id": np.arange(start, start + n).astype("int64")})

    tables = []
    for i in range(K):
        path = f"{base}/t{i}"
        tables.append(DeltaTable.create(path, data=ids(2000)))

    # skew: table 0 takes ~half the traffic (the hot-table case the SLO
    # attribution exists for)
    picks = np.where(rng.rand(ops_per_leg) < 0.5, 0,
                     rng.randint(1, K, ops_per_leg))

    def leg():
        # overwrite, not append: a leg must not grow the tables and bias
        # the next leg's scan/commit cost (the on-vs-off comparison needs
        # identical work per leg)
        for j, i in enumerate(picks):
            t = tables[int(i)]
            if j % 3 == 0:
                t.write(ids(50, start=10_000 + 50 * j), mode="overwrite")
            else:
                t.to_arrow(filters=[f"id < {50 + (j % 200)}"])

    leg()  # warm caches/JITs so the off leg isn't paying one-time costs
    timeseries.reset()
    slo.reset()
    # interleaved min-of-2 per leg (config 9's idiom): off/on/off/on, so
    # drift affects both legs alike and host noise is floored by the min
    def on_leg():
        with conf.set_temporarily(
                **{"delta.tpu.obs.scrape.intervalMs": 100}):
            timeseries.start_scraper()
            try:
                return _timed(leg)[0]
            finally:
                timeseries.stop_scraper()

    # ABBA order: the log tail grows a little every leg, so a fixed
    # off-then-on order would bill that drift entirely to the ON side
    offs, ons = [], []
    offs.append(_timed(leg)[0]); ons.append(on_leg())
    ons.append(on_leg()); offs.append(_timed(leg)[0])
    off_s, on_s = min(offs), min(ons)
    scrapes_on = timeseries.scrape_count()
    overhead_pct = (on_s / off_s - 1.0) * 100.0

    # blackout pair: the scraper daemon ticking over a disabled registry.
    # Rings reset first so the leg's own counts are what gets asserted —
    # the ON leg above legitimately filled them
    timeseries.reset()
    slo.reset()
    with conf.set_temporarily(delta__tpu__telemetry__enabled=False):
        dark_offs, dark_ons = [], []
        dark_offs.append(_timed(leg)[0]); dark_ons.append(on_leg())
        dark_ons.append(on_leg()); dark_offs.append(_timed(leg)[0])
        dark_off_s, dark_on_s = min(dark_offs), min(dark_ons)
        dark_scrapes = timeseries.scrape_count()
        dark_series = len(timeseries.series_snapshot()["counters"])
    blackout_overhead_pct = (dark_on_s / dark_off_s - 1.0) * 100.0

    # route latency with the rings warm and the registry full
    with conf.set_temporarily(
            **{"delta.tpu.obs.scrape.intervalMs": 100}):
        timeseries.start_scraper()
        srv = ObsServer(port=0)
        try:
            def get(route):
                c = http.client.HTTPConnection("127.0.0.1", srv.port,
                                               timeout=30)
                try:
                    c.request("GET", route)
                    r = c.getresponse()
                    assert r.status == 200, route
                    return r.read()
                finally:
                    c.close()

            get("/fleet")  # warm the sweep path once
            n_req = 30
            fleet_ms = sorted(
                _timed(lambda: get("/fleet"))[0] * 1000
                for _ in range(n_req))
            slo_ms = sorted(
                _timed(lambda: get("/slo"))[0] * 1000
                for _ in range(n_req))
            fleet_doc = json.loads(get("/fleet"))
        finally:
            srv.stop()
            timeseries.stop_scraper()

    assert fleet_doc["tables"] >= K
    ranked = fleet_doc["sweep"]["entries"]

    def pct(samples, q):
        # upper-rounded index: p95 over 30 samples is the 29th, not ~p91
        import math

        return round(samples[min(len(samples) - 1,
                                 math.ceil(q * len(samples)) - 1)], 2)

    p50 = pct(fleet_ms, 0.50)
    return {
        "metric": "fleet_route_p50_ms",
        "value": p50,
        "unit": "ms",
        "vs_baseline": 0,
        "baseline": "no prior fleet plane: first-round absolute numbers",
        "tables": K,
        "ops_per_leg": ops_per_leg,
        "route_fleet_ms": {"p50": p50, "p95": pct(fleet_ms, 0.95)},
        "route_slo_ms": {"p50": pct(slo_ms, 0.50),
                         "p95": pct(slo_ms, 0.95)},
        "scraper": {
            "off_s": round(off_s, 3), "on_s": round(on_s, 3),
            "overhead_pct": round(overhead_pct, 2),
            "scrapes_during_leg": scrapes_on,
        },
        "blackout": {
            "off_s": round(dark_off_s, 3), "on_s": round(dark_on_s, 3),
            "overhead_pct": round(blackout_overhead_pct, 2),
            "scrapes": dark_scrapes, "series": dark_series,
            "inert": _assert_blackout_inert(dark_scrapes, dark_series),
        },
        "sweep_ranked_tables": len(ranked),
        "gate": {
            "route_slo_p50_ms": {
                "value": pct(slo_ms, 0.50), "unit": "ms"},
            "sweep_tables": {"value": len(ranked), "unit": "tables"},
        },
        "note": "overhead legs share one warmed workload fn in ABBA "
                "order (min-of-2 per side) and run the scraper at 100ms "
                "— 100x hotter than the 10s default; measured on/off "
                "deltas land within this host's ±15% wall-clock noise "
                "band in BOTH directions across rounds, i.e. the "
                "steady-state cost is not distinguishable from zero at "
                "this cadence (and is ~1/100th of whatever it is at the "
                "default 10s). blackout inert=true is the structural "
                "assertion: zero scrapes recorded AND zero series "
                "retained while the daemon ticked through the dark leg",
    }


def _assert_blackout_inert(scrapes, series):
    # the blackout guarantee is ASSERTED, not just recorded: a scraper that
    # does registry work under blackout must fail the config (the wall-
    # clock delta stays recorded-only — it is host-noise-bound)
    assert scrapes == 0 and series == 0, (
        f"blackout leg not inert: scrapes={scrapes} series={series}")
    return True


# -- config 13: shadow optimizer — what-if replay + SLO capacity burn --------


def bench_shadow(workdir):
    """Config 13: the shadow optimizer end to end at bench scale.

    Journals a clustered-vs-unclustered workload (files clustered on
    ``a``, ``v`` permuted inside every file; selective ``v`` point scans
    plus file-pruned ``a`` range scans), reconstructs the trace from the
    journal (every literal rehydrated from the reservoir — zero
    synthesis), then times one ``shadow_run`` over two candidates:

    * ``ZORDER:v`` under fine row groups — the rewrite that genuinely wins
      (point scans prune nearly every group) → must score ``confirmed``;
    * ``ROW_GROUP_ROWS:4194304`` — recoarsen/compact, which destroys the
      file-tier ``a`` clustering for zero gain → must score ``refuted``
      on the measured read-side loss.

    Both verdicts are ASSERTED, not just recorded: a scoring regression
    that lets the bad rewrite through (or refutes the good one) fails the
    config. The capacity leg replays the zipf hot-key storm scenario at
    10x and 100x against the live scraper/SLO plane and asserts the
    ``scanPlanningP99`` objective fires at BOTH compressions, then resets
    the rings. Headline = shadow_run wall (trace replay x 3: baseline +
    2 sandboxed candidate rewrites)."""
    import pyarrow as pa

    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.obs import journal, slo, timeseries
    from delta_tpu.replay import (Candidate, build_trace, capacity_replay,
                                  shadow_run, zipf_hot_key_storm)
    from delta_tpu.utils.config import conf

    rows_total = _rows(2_000_000)
    per_file = max(rows_total // 4, 2000)
    rng = np.random.RandomState(5)
    path = os.path.join(workdir, "shadow_t")

    def part(base):
        return pa.table({
            "id": np.arange(base, base + per_file).astype("int64"),
            "a": np.arange(base, base + per_file).astype("int64"),
            "v": rng.permutation(per_file).astype("int64"),
        })

    # every scan keeps its own literal (the default 3-sample reservoir
    # would collapse later same-shape scans onto the first literal)
    with conf.set_temporarily(**{"delta.tpu.journal.literalSamples": 16}):
        t = DeltaTable.create(path, data=part(0))
        for i in range(1, 4):
            t.write(part(i * per_file), mode="append")
        for i in range(6):
            t.to_arrow(filters=[f"v = {i * 13}"])  # selective: 1 hit/file
        for _ in range(4):
            t.to_arrow(filters=[f"a < {per_file // 20}"])  # file-pruned
    journal.flush()

    build_s, trace = _timed(lambda: build_trace(t.delta_log))
    # every literal must come out of the reservoir — a synthesis fallback
    # here means the reservoir stamping regressed
    assert trace.synthesized_literals == 0, trace.to_dict()
    assert trace.counts()["scan"] == 10

    sandbox_root = os.path.join(workdir, "shadow_sandboxes")
    os.makedirs(sandbox_root, exist_ok=True)
    cands = [Candidate("ZORDER", {"columns": ["v"]}),
             Candidate("ROW_GROUP_ROWS", {"rows": 4_194_304})]
    # candidate rewrites land under fine row groups; the baseline clone
    # keeps the live table's coarse layout — the granularity the ZORDER
    # win is measured against
    with conf.set_temporarily(**{
            "delta.tpu.write.rowGroupRows": 8192,
            "delta.tpu.replay.sandboxDir": sandbox_root}):
        shadow_s, card = _timed(lambda: shadow_run(
            t.delta_log, trace=trace, candidates=cands))

    top = card.top
    assert (top["candidate"]["label"] == "ZORDER:v"
            and top["verdict"] == "confirmed" and top["score"] > 0), card.to_dict()
    [bad] = [r for r in card.candidates
             if r["candidate"]["label"] == "ROW_GROUP_ROWS:4194304"]
    assert bad["verdict"] == "refuted" and bad["score"] < 0, bad
    assert os.listdir(sandbox_root) == []  # sandbox never leaks clones

    # capacity leg: same storm, two compressions, same objective fired.
    # The replay deliberately writes into the live rings; reset after.
    storm = zipf_hot_key_storm(path=path)
    caps = {}
    with conf.set_temporarily(**{"delta.tpu.obs.slo.minObservations": 4}):
        for speed in (10.0, 100.0):
            slo.reset()
            timeseries.reset()
            wall, rep = _timed(lambda s=speed: capacity_replay(
                storm, speed=s, now_ms=1_000_000_000_000))
            assert rep["objectives"] == ["scanPlanningP99"], rep
            caps[f"{int(speed)}x"] = {
                "wall_s": round(wall, 3),
                "events": rep["events"],
                "scrapes": rep["scrapes"],
                "simulated_ms": rep["simulatedMs"],
                "original_ms": rep["originalMs"],
                "objectives": rep["objectives"],
            }
    slo.reset()
    timeseries.reset()

    return {
        "metric": "shadow_run_s",
        "value": round(shadow_s, 3),
        "unit": "s",
        "vs_baseline": 0,
        "baseline": "no prior shadow optimizer: first-round absolute numbers",
        "rows": rows_total,
        "files": 4,
        "scans_journaled": 10,
        "trace": {"build_s": round(build_s, 3),
                  "scans": trace.counts()["scan"],
                  "synthesized_literals": trace.synthesized_literals},
        "scorecard": {
            "top": top["candidate"]["label"],
            "top_verdict": top["verdict"],
            "top_score": top["score"],
            "top_deltas": top["deltas"],
            "bad": bad["candidate"]["label"],
            "bad_verdict": bad["verdict"],
            "bad_score": bad["score"],
            "candidates": len(card.candidates),
        },
        "capacity": caps,
        "gate": {
            "trace_build_ms": {"value": round(build_s * 1000, 1),
                               "unit": "ms"},
            "capacity_10x_ms": {"value": round(caps["10x"]["wall_s"] * 1000,
                                               1), "unit": "ms"},
            "confirmed_candidates": {
                "value": sum(1 for r in card.candidates
                             if r["verdict"] == "confirmed"),
                "unit": "candidates"},
        },
        "note": "shadow_run wall covers trace replay x3 (baseline clone + "
                "2 candidate rewrites: a full ZORDER of the table under "
                "8192-row groups and a full recoarsen compaction) in a "
                "throwaway sandbox. Verdicts are structural assertions: "
                "ZORDER:v confirmed on measured bytes no longer read + "
                "newly skipped, the recoarsen refuted on the measured "
                "file-pruning loss, and the 10x/100x capacity replays "
                "must fire scanPlanningP99 — any flip fails the config",
    }


# -- config 9: sustained-contention commit path (group commit) ---------------


def bench_commit_contention(workdir):
    """Config 9: K writer threads x M commits each against one table —
    mostly blind appends plus a conflicting-DML fraction (non-blind
    read-then-add txns) — three interleaved trials of grouping + async
    incremental checkpointing OFF (the baseline leg) then ON, latency
    samples pooled per leg. Records throughput and pooled p50/p99 commit
    latency per leg; headline = p99 commit-latency improvement (higher is
    better). The ungrouped leg pays the per-writer list/read-tail/CAS
    cycle and the every-10th-commit synchronous checkpoint stall that
    ISSUE 9 targets."""
    import threading

    from delta_tpu import DeltaLog
    from delta_tpu.commands import operations as ops_mod
    from delta_tpu.log import checkpointer
    from delta_tpu.protocol.actions import AddFile, Metadata
    from delta_tpu.schema.types import LongType, StructType
    from delta_tpu.utils import errors as errors_mod
    from delta_tpu.utils.config import conf

    K = int(os.environ.get("BENCH_CONTENTION_WRITERS", "16"))
    M = int(os.environ.get("BENCH_CONTENTION_COMMITS", "40"))
    conflict_every = 5  # every 5th commit per writer is non-blind

    schema = StructType().add("id", LongType()).add("v", LongType())

    # contention is a LOCK/LISTING/BATCHING phenomenon: on a shared CI
    # filesystem (virtio-9p here) other tenants' fsync bursts inject
    # multi-second stalls into random commits of either leg, swamping the
    # leg comparison with noise that has nothing to do with the commit
    # path. A RAM-backed dir keeps the measured tail the engine's own.
    base = workdir
    if os.access("/dev/shm", os.W_OK):
        base = tempfile.mkdtemp(prefix="delta_tpu_bench_c9_", dir="/dev/shm")

    def _leg(name, grouped):
        path = os.path.join(base, f"c9_{name}")
        log = DeltaLog.for_table(path)
        txn = log.start_transaction()
        txn.update_metadata(Metadata(schema_string=schema.to_json()))
        txn.commit([], ops_mod.ManualUpdate())

        latencies = [[] for _ in range(K)]
        conflicts = [0] * K
        barrier = threading.Barrier(K + 1)

        def writer(w):
            barrier.wait()
            for i in range(M):
                try:
                    t = log.start_transaction()
                    add = AddFile(
                        f"w{w}-{i:05d}.parquet", {}, 4096, 1, True,
                        stats='{"numRecords":128,"minValues":{"id":0},'
                              '"maxValues":{"id":127},"nullCount":{"id":0}}',
                    )
                    if i % conflict_every == conflict_every - 1:
                        t.filter_files()  # records the read: non-blind txn
                    # time the commit() call only — the list/read-tail/
                    # conflict-check/CAS cycle grouping amortizes; the
                    # read-side snapshot listing in start_transaction is
                    # identical in both legs and would only dilute the leg
                    # comparison with shared noise
                    t0 = time.perf_counter()
                    t.commit([add], ops_mod.Write("Append"))
                    latencies[w].append(time.perf_counter() - t0)
                except errors_mod.DeltaConcurrentModificationException:
                    conflicts[w] += 1

        overrides = {
            "delta.tpu.commit.group.enabled": grouped,
            "delta.tpu.commit.group.maxWaitMs": 3,
            "delta.tpu.checkpoint.async": grouped,
            "delta.tpu.checkpoint.incremental": grouped,
        }
        with conf.set_temporarily(**overrides):
            threads = [threading.Thread(target=writer, args=(w,))
                       for w in range(K)]
            for t in threads:
                t.start()
            barrier.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            # async builds drain OUTSIDE the timed window: that is the
            # design (they are off the commit's critical path), but the
            # work must still complete inside this config's deadline
            checkpointer.flush()
        return {"lats": [x for per in latencies for x in per],
                "conflicts": sum(conflicts), "wall_s": wall}

    def _pooled(runs):
        """Aggregate one leg's interleaved trials: percentiles over the
        POOLED latency samples (a single trial's p99 rides on ~3 tail
        samples and is noisy on a shared box; pooling triples the tail),
        throughput over the summed walls."""
        lats = sorted(x for r in runs for x in r["lats"])
        ok = len(lats)
        wall = sum(r["wall_s"] for r in runs)

        def _pct(p):
            return lats[min(ok - 1, int(p * ok))] * 1000 if ok else -1.0

        def _trial_p99(r):
            s = sorted(r["lats"])
            return round(s[min(len(s) - 1, int(0.99 * len(s)))] * 1000, 2) \
                if s else -1.0

        return {
            "commits_ok": ok,
            "conflicts": sum(r["conflicts"] for r in runs),
            "wall_s": round(wall, 3),
            "throughput_cps": round(ok / wall, 1) if wall > 0 else -1.0,
            "p50_ms": round(_pct(0.50), 2),
            "p99_ms": round(_pct(0.99), 2),
            "trial_p99_ms": [_trial_p99(r) for r in runs],
        }

    # three interleaved off/on trials: interleaving decorrelates machine
    # drift from the leg comparison, pooling stabilizes the tail estimate
    try:
        trials = [(_leg(f"off{i}", grouped=False),
                   _leg(f"on{i}", grouped=True))
                  for i in range(3)]
    finally:
        if base is not workdir:
            shutil.rmtree(base, ignore_errors=True)
    ungrouped = _pooled([t[0] for t in trials])
    grouped = _pooled([t[1] for t in trials])
    speedup = (round(ungrouped["p99_ms"] / grouped["p99_ms"], 2)
               if grouped["p99_ms"] > 0 else -1.0)
    return {
        "metric": f"commit_p99_speedup_grouped_vs_ungrouped_{K}w",
        "value": speedup,
        "unit": "x",
        "vs_baseline": speedup,
        "baseline": "same workload, grouping + async checkpointing off",
        "writers": K,
        "commits_per_writer": M,
        "conflict_fraction": round(1.0 / conflict_every, 2),
        "ungrouped": ungrouped,
        "grouped": grouped,
        # sub-metrics the --compare gate walks direction-aware
        # (tools/bench_diff): p99 regresses when it GROWS, throughput when
        # it SHRINKS
        "gate": {
            "grouped_p99_ms": {"value": grouped["p99_ms"], "unit": "ms"},
            "grouped_throughput": {"value": grouped["throughput_cps"],
                                   "unit": "commits/s"},
            "p99_speedup": {"value": speedup, "unit": "x"},
        },
    }


# -- config 14: sharded scan planning + distributed OPTIMIZE/MERGE -----------


def bench_sharded_scan_worker():
    """Hidden worker for config 14 (``14w`` — subprocess only, the full
    sweep skips ``*w`` keys): 256-query batched scan planning on resident
    lanes, single-device vs shard_map-sharded over the mesh, identity vs the
    host planner asserted per query. Runs in its OWN process because the
    device count is fixed at first backend init — the parent forces an
    8-virtual-device CPU mesh via XLA_FLAGS without perturbing its own
    topology (or real accelerators, where the flag is inert)."""
    import jax

    from delta_tpu.expr.parser import parse_expression
    from delta_tpu.ops import pruning
    from delta_tpu.ops.state_cache import ResidentState, extract_ranges
    from delta_tpu.utils.config import conf as _c

    n_files = 6000  # capacity 8192: lanes shard into whole 1024-file blocks
    n_q = 256
    reps = 5
    rng = np.random.RandomState(14)
    cols = ["a", "b", "c", "d"]
    lo = rng.rand(len(cols), n_files) * 1000.0
    hi = lo + rng.rand(len(cols), n_files) * 50.0
    entry = ResidentState(
        "bench://c14", "mid", 0, cols, [f"f{i}" for i in range(n_files)],
        {"min": lo, "max": hi, "size": np.ones(n_files, np.int64)},
    )
    ranges = []
    for i in range(n_q):
        c = cols[i % len(cols)]
        a0 = (i * 37) % 950
        pred = pruning.skipping_predicate(
            parse_expression(f"{c} >= {a0} AND {c} <= {a0 + 40}"),
            frozenset())
        r = extract_ranges(pred, cols)
        assert r is not None
        ranges.append(r)
    host = entry.plan_ranges(ranges, k=n_files, use_device=False)

    def leg(enabled):
        # existing residency wins shard planning, so re-place per leg
        entry.drop_device()
        with _c.set_temporarily(**{
            "delta.tpu.distributed.plan.enabled": enabled,
            "delta.tpu.distributed.plan.mode": "force",
            "delta.tpu.stateCache.devicePlan.mode": "force",
        }):
            plans = entry.plan_ranges(ranges, k=n_files, use_device=True)
            shards = entry.resident_shards
            t0 = time.perf_counter()
            for _ in range(reps):
                plans = entry.plan_ranges(ranges, k=n_files, use_device=True)
            wall = (time.perf_counter() - t0) / reps
        # identity per query: the sharded coarse cull + host fine pass must
        # return EXACTLY the single-route plan rows
        for hp, dp in zip(host, plans):
            assert list(dp.rows) == list(hp.rows), "sharded plan != host"
        return wall, shards

    single_s, s1 = leg(False)
    sharded_s, s8 = leg(True)
    assert s1 == 1, s1
    ratio = single_s / max(sharded_s, 1e-9)
    platform = jax.devices()[0].platform
    accelerated = platform not in ("cpu",)
    return {
        "metric": "sharded_plan_throughput_vs_single",
        "value": round(ratio, 2) if accelerated else -1,
        "unit": "x" if accelerated else "skipped",
        "vs_baseline": round(ratio, 2),
        "platform": platform,
        "n_devices": len(jax.devices()),
        "shards": s8,
        "plan_single_s": round(single_s, 4),
        "plan_sharded_s": round(sharded_s, 4),
        "throughput_ratio": round(ratio, 3),
        "efficiency": round(ratio / max(s8, 1), 4),
        "queries": n_q,
        "files": n_files,
        "identity": True,
    }


def bench_sharded_scan(workdir):
    """Config 14 — the sharded execution plane, 1-vs-8 (ISSUE 18).

    Three legs, each under its own deadline, record-and-continue:

      plan     — subprocess (``bench.py 14w``) on a forced 8-virtual-device
                 mesh: batched scan planning single-device vs shard_map-
                 sharded lanes, identity vs the host planner asserted
      optimize — in-process: the same partitioned table compacted with
                 workers=1 vs workers=8 (LPT seed + work stealing), row
                 identity and file-topology identity asserted, per-worker
                 timings and steals recorded
      merge    — in-process: probe-restricted MERGE vs probe-off on clone
                 tables, result identity asserted, probe speedup measured

    Headline: sharded-vs-single planning throughput at 8 shards. On a
    CPU-only host the 8 "devices" are one physical CPU, so the throughput
    claim is skip-recorded (value -1, unit "skipped") — the measured
    numbers and the deterministic LPT zipf-balance gate still ride the
    artifact, and ``--compare`` walks the gate sub-metrics direction-aware.
    """
    import subprocess

    import jax
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.exec.scan import scan_to_table
    from delta_tpu.parallel.distributed import bytes_skew, lpt_assign
    from delta_tpu.utils.config import conf as _c

    legs = {}

    def _leg(name, budget_s, fn):
        t0 = time.perf_counter()
        try:
            legs[name] = fn(budget_s)
            legs[name]["wall_s"] = round(time.perf_counter() - t0, 3)
        except subprocess.TimeoutExpired:
            legs[name] = {"skipped": f"leg deadline {budget_s:.0f}s breached"}
        except Exception as e:  # noqa: BLE001 — per-leg record-and-continue
            legs[name] = {"error": f"{type(e).__name__}: {e}"[:300]}

    # plan leg runs in a subprocess: the forced 8-device mesh must not leak
    # into the parent's jax (device count is fixed at first backend init)
    def _plan(budget_s):
        env = dict(os.environ)
        # a virtual 8-device CPU identity leg: it must never ask for the
        # chip this parent already holds (one process per chip)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        proc = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "14w"],
            capture_output=True, text=True, timeout=budget_s, env=env)
        if proc.returncode != 0:
            return {"error": (proc.stderr or proc.stdout)[-300:]}
        return json.loads(proc.stdout.strip().splitlines()[-1])

    _leg("plan", 240, _plan)

    rows_per = max(_rows(400_000) // 32, 1000)

    def _mk(path, rng):
        log = DeltaLog.for_table(path)
        for p in range(8):
            for f in range(4):
                base = (p * 4 + f) * rows_per
                WriteIntoDelta(log, "append", pa.table({
                    "id": np.arange(base, base + rows_per, dtype=np.int64),
                    "part": pa.array([f"p{p}"] * rows_per),
                    "v": rng.rand(rows_per),
                }), partition_columns=["part"]).run()
        return log

    def _optimize(budget_s):
        seq = _mk(os.path.join(workdir, "c14_seq"), np.random.RandomState(3))
        par = _mk(os.path.join(workdir, "c14_par"), np.random.RandomState(3))
        c1 = OptimizeCommand(seq, min_file_size=1 << 30, workers=1)
        t1, _ = _timed(c1.run)
        c8 = OptimizeCommand(par, min_file_size=1 << 30, workers=8)
        t8, _ = _timed(c8.run)
        # worker count must be invisible: same rows, same file topology
        a = scan_to_table(seq.update()).sort_by("id")
        b = scan_to_table(par.update()).sort_by("id")
        assert a.equals(b), "parallel OPTIMIZE diverged from sequential"
        assert c1.metrics["numRemovedFiles"] == \
            c8.metrics["numRemovedFiles"] == 32
        assert c1.metrics["numAddedFiles"] == c8.metrics["numAddedFiles"]
        rep = c8.shard_report
        return {
            "rows": 32 * rows_per,
            "workers1_s": round(t1, 3),
            "workers8_s": round(t8, 3),
            "speedup": round(t1 / max(t8, 1e-9), 2),
            "groups": len(rep.results),
            "steals": rep.steals,
            "skew": round(rep.skew, 4),
            "per_worker": rep.timings(),
        }

    _leg("optimize", 150, _optimize)

    def _merge(budget_s):
        mrows = max(_rows(160_000) // 32, 1000)

        def mk(path):
            log = DeltaLog.for_table(path)
            for i in range(32):
                base = i * mrows
                WriteIntoDelta(log, "append", pa.table({
                    "id": np.arange(base, base + mrows, dtype=np.int64),
                    "v": np.arange(base, base + mrows, dtype=np.float64),
                })).run()
            return log

        # 2 updates landing in 2 of the 32 files + 1 insert past the range
        src = pa.table({
            "id": pa.array([7, 3 * mrows + 11, 32 * mrows + 5], pa.int64()),
            "v": pa.array([-1.0, -2.0, -3.0]),
        })
        up = MergeClause("update", assignments=None)
        ins = MergeClause("insert", assignments=None)
        off_log = mk(os.path.join(workdir, "c14_moff"))
        with _c.set_temporarily(
            **{"delta.tpu.distributed.merge.probe.enabled": False}
        ):
            m_off = MergeIntoCommand(off_log, src, "t.id = s.id", [up], [ins],
                                     source_alias="s", target_alias="t")
            t_off, _ = _timed(m_off.run)
        on_log = mk(os.path.join(workdir, "c14_mon"))
        m_on = MergeIntoCommand(on_log, src, "t.id = s.id", [up], [ins],
                                source_alias="s", target_alias="t")
        t_on, _ = _timed(m_on.run)
        a = scan_to_table(off_log.update()).sort_by("id")
        b = scan_to_table(on_log.update()).sort_by("id")
        assert a.to_pylist() == b.to_pylist(), "probe changed MERGE results"
        assert m_on.metrics["numTargetRowsUpdated"] == 2
        assert m_on.metrics["numTargetRowsInserted"] == 1
        assert m_on.metrics["numTargetFilesRemoved"] <= 2
        return {
            "files": 32,
            "probe_off_s": round(t_off, 3),
            "probe_on_s": round(t_on, 3),
            "probe_speedup": round(t_off / max(t_on, 1e-9), 2),
            "files_removed": m_on.metrics["numTargetFilesRemoved"],
            "probe_ms": m_on.phase_ms.get("probe_ms"),
        }

    _leg("merge", 90, _merge)

    # the LPT balance gate is deterministic (pure function of the zipf
    # population), so --compare can hold it to the skew unit regardless of
    # host speed: growth past threshold = a load-balance regression
    zipf = [1_000_000 // (i + 1) + 1 for i in range(100_000)]
    lpt_skew = bytes_skew(zipf, lpt_assign(zipf, 8))
    strided_skew = bytes_skew(
        zipf, [list(range(h, 100_000, 8)) for h in range(8)])

    plan = legs.get("plan", {})
    ratio = plan.get("throughput_ratio")
    ok = isinstance(ratio, (int, float)) and ratio > 0
    platform = jax.devices()[0].platform
    accelerated = platform not in ("cpu",)
    if accelerated and ok:
        # the scaling-efficiency acceptance where hardware allows it
        assert ratio >= 2.0, f"8-shard planning only {ratio:.2f}x single"
    result = {
        "metric": "sharded_plan_throughput_8shard_vs_single",
        "value": round(ratio, 2) if (accelerated and ok) else -1,
        "unit": "x" if (accelerated and ok) else "skipped",
        "vs_baseline": round(ratio, 2) if ok else 0,
        "platform": platform,
        "legs": legs,
        "lpt_zipf": {"strided_skew": round(strided_skew, 3),
                     "lpt_skew": round(lpt_skew, 5)},
        "gate": {
            "lpt_zipf_skew": {"value": round(lpt_skew, 5), "unit": "skew"},
            "scaling_efficiency": {
                "value": (round(plan.get("efficiency", -1.0), 4)
                          if (accelerated and ok) else -1),
                "unit": "x" if (accelerated and ok) else "skipped",
            },
        },
    }
    if not accelerated:
        result["note"] = (
            "skipped: CPU-only host — the 8-shard mesh is one physical CPU, "
            "so the throughput claim needs real devices; measured numbers "
            "and the balance gate are recorded in legs/gate")
    return result


def bench_trace_overhead(workdir):
    """Config 15 — distributed-tracing overhead on the sharded OPTIMIZE leg
    (ISSUE 19).

    The same partitioned compaction (pool path: job/worker/item spans) runs
    under three postures, reps interleaved so clock drift lands on every
    variant equally:

      sampled    — ``trace.sampleRate=1`` + a spool dir: every span is
                   serialized and appended to the JSONL spool
      unsampled  — ``trace.sampleRate=0`` + a spool dir: head sampling says
                   no; the claim is the sink never runs AND the spool dir
                   is never created
      disabled   — telemetry off entirely: the floor the others compare to

    Headline: the tracing plane's marginal cost when sampled — the median
    of the per-rep ``sampled/unsampled`` wall ratios (pairing adjacent runs
    cancels the slow drift that dominates run-to-run noise at this scale).
    ``unsampled/disabled`` is the context number: the whole telemetry plane
    vs blackout, of which tracing-off must add nothing. The inertness
    claims are hard-asserted (rate 0 must write NOTHING); the timing claims
    ride a findings-style gate — ``0`` means both hold (sampled-on < 5%,
    unsampled-vs-disabled within the disabled variant's own rep spread),
    and any regression reads as new findings for ``--compare``.
    """
    import statistics

    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.obs import trace_store
    from delta_tpu.utils.config import conf as _c

    rows_per = max(_rows(240_000) // 24, 500)
    reps = 6

    def _mk(path):
        log = DeltaLog.for_table(path)
        for p in range(8):
            for f in range(3):
                base = (p * 3 + f) * rows_per
                WriteIntoDelta(log, "append", pa.table({
                    "id": np.arange(base, base + rows_per, dtype=np.int64),
                    "part": pa.array([f"p{p}"] * rows_per),
                    "v": np.arange(base, base + rows_per, dtype=np.float64),
                }), partition_columns=["part"]).run()
        return log

    spools = {v: os.path.join(workdir, f"c15_spool_{v}")
              for v in ("sampled", "unsampled")}
    variants = {
        "sampled": {"delta.tpu.trace.dir": spools["sampled"],
                    "delta.tpu.trace.sampleRate": 1.0},
        "unsampled": {"delta.tpu.trace.dir": spools["unsampled"],
                      "delta.tpu.trace.sampleRate": 0.0},
        "disabled": {"delta.tpu.telemetry.enabled": False},
    }
    times = {v: [] for v in variants}
    # rep -1 is an untimed warm-up sweep: the first compaction pays JIT and
    # first-touch caches, and must not land on whichever variant runs first
    for rep in range(-1, reps):
        for v, knobs in variants.items():
            log = _mk(os.path.join(workdir, f"c15_{v}_{rep}"))
            cmd = OptimizeCommand(log, min_file_size=1 << 30, workers=4)
            with _c.set_temporarily(**knobs):
                t, _ = _timed(cmd.run)
            if rep >= 0:
                times[v].append(t)
            assert cmd.metrics["numRemovedFiles"] == 24
    trace_store.reset()

    spooled = len(trace_store.read_spools(spools["sampled"]))
    # the knobs must be provably inert: rate 0 writes NOTHING — the sink
    # never ran, so the spool directory was never even created
    assert spooled > 0, "sampled variant spooled no spans"
    assert not os.path.exists(spools["unsampled"]), \
        "sampleRate=0 still touched the spool"

    med = {v: statistics.median(ts) for v, ts in times.items()}
    # paired ratios: within one rep the variants run back to back, so the
    # slow drift (freq scaling, background load) divides out of the ratio
    on_pct = (statistics.median(
        s / u for s, u in zip(times["sampled"], times["unsampled"])
    ) - 1.0) * 100.0
    off_pct = (statistics.median(
        u / d for u, d in zip(times["unsampled"], times["disabled"])
    ) - 1.0) * 100.0
    # noise floor: the disabled variant's own interquartile spread (≥ 2%)
    d_sorted = sorted(times["disabled"])
    q = max(reps // 4, 1)
    noise_pct = max((d_sorted[-1 - q] - d_sorted[q]) / med["disabled"]
                    * 100.0, 2.0)
    violations = int(on_pct >= 5.0) + int(abs(off_pct) > noise_pct)
    return {
        "metric": "trace_overhead_sampled_pct",
        "value": round(max(on_pct, 0.0), 2),
        "unit": "pct",
        "vs_baseline": round(on_pct, 2),
        "reps": reps,
        "rows": 24 * rows_per,
        "files_compacted": 24,
        "median_s": {v: round(t, 4) for v, t in med.items()},
        "times_s": {v: [round(t, 4) for t in ts]
                    for v, ts in times.items()},
        "sampled_on_overhead_pct": round(on_pct, 2),
        "sampled_off_overhead_pct": round(off_pct, 2),
        "noise_pct": round(noise_pct, 2),
        "spans_spooled_sampled": spooled,
        "gate": {
            "trace_overhead_claims_violated": {
                "value": violations, "unit": "findings"},
        },
    }


def bench_dist_faults(workdir):
    """Config 16 — the price of fault tolerance on the sharded plane
    (ISSUE 20).

    Three legs, each under its own deadline, record-and-continue:

      retry       — the same partitioned compaction clean vs under 4
                    scripted transient ``dist.itemExec`` faults: every
                    fault retries to success (zero quarantine), row and
                    file-topology identity asserted, the fault run's
                    overhead over clean measured
      speculation — a seeded straggler workload on ``run_sharded`` with
                    speculative re-dispatch on vs off: the supervisor's
                    rescue must beat waiting out the wedged attempt
                    (hard-asserted — this is the config's headline)
      recovery    — 2-host posed OPTIMIZE where host 1 crashes mid-slice
                    after publishing its lease; the coordinator reconciles
                    the orphan — end state identical to a single-process
                    run, recovery overhead over that solo run measured

    Headline: speculation speedup vs no-speculation on the straggler leg.
    The gate rides two sub-metrics: ``dist_fault_identity_violations``
    (findings — any leg that errors or diverges from its fault-free
    reference) and ``recovery_overhead_pct`` (pct — what the crash +
    lease recovery cost over the solo compaction).
    """
    import pyarrow as pa

    from delta_tpu import DeltaLog
    from delta_tpu.commands.optimize import OptimizeCommand
    from delta_tpu.commands.write import WriteIntoDelta
    from delta_tpu.exec.scan import scan_to_table
    from delta_tpu.parallel import distributed as dist_mod
    from delta_tpu.parallel import leases
    from delta_tpu.parallel.executor import run_sharded
    from delta_tpu.storage.faults import FaultPlan, SimulatedCrash
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf as _c

    legs = {}

    def _leg(name, budget_s, fn):
        t0 = time.perf_counter()
        try:
            legs[name] = fn(budget_s)
            legs[name]["wall_s"] = round(time.perf_counter() - t0, 3)
        except Exception as e:  # noqa: BLE001 — per-leg record-and-continue
            legs[name] = {"error": f"{type(e).__name__}: {e}"[:300]}

    rows_per = max(_rows(96_000) // 32, 500)

    def _mk(path, rng):
        log = DeltaLog.for_table(path)
        for p in range(8):
            for f in range(4):
                base = (p * 4 + f) * rows_per
                WriteIntoDelta(log, "append", pa.table({
                    "id": np.arange(base, base + rows_per, dtype=np.int64),
                    "part": pa.array([f"p{p}"] * rows_per),
                    "v": rng.rand(rows_per),
                }), partition_columns=["part"]).run()
        return log

    def _rows_files(log):
        snap = DeltaLog.for_table(log.data_path).update()
        return (sorted(scan_to_table(snap, [], ["id"])
                       .column("id").to_pylist()), snap.num_of_files)

    fast_retry = {"delta.tpu.distributed.retry.baseDelayMs": 1,
                  "delta.tpu.distributed.retry.maxDelayMs": 10}

    def _retry(budget_s):
        # untimed warm-up: the first compaction pays JIT and first-touch
        # caches, and must not land on the clean side of the overhead ratio
        warm = _mk(os.path.join(workdir, "c16_warm"),
                   np.random.RandomState(5))
        OptimizeCommand(warm, min_file_size=1 << 30, workers=4).run()
        clean = _mk(os.path.join(workdir, "c16_clean"),
                    np.random.RandomState(7))
        faulted = _mk(os.path.join(workdir, "c16_fault"),
                      np.random.RandomState(7))
        c_clean = OptimizeCommand(clean, min_file_size=1 << 30, workers=4)
        t_clean, _ = _timed(c_clean.run)
        plan = FaultPlan(script=[("dist.itemExec", "transient")] * 4)
        with _c.set_temporarily(**fast_retry,
                                **{"delta.tpu.faults.plan": plan}):
            c_fault = OptimizeCommand(faulted, min_file_size=1 << 30,
                                      workers=4, on_failure="quarantine")
            t_fault, _ = _timed(c_fault.run)
        assert not plan.script, "scripted faults never fired"
        # every transient retried to success: no quarantine, and the fault
        # run's table is indistinguishable from the clean run's
        assert c_fault.metrics["numQuarantinedGroups"] == 0
        rep = c_fault.shard_report
        assert rep.retried >= 4
        a, a_files = _rows_files(clean)
        b, b_files = _rows_files(faulted)
        assert a == b and a_files == b_files, \
            "faulted OPTIMIZE diverged from clean"
        return {
            "rows": 32 * rows_per,
            "faults_injected": 4,
            "retried": rep.retried,
            "quarantined": len(rep.quarantined),
            "clean_s": round(t_clean, 3),
            "faulted_s": round(t_fault, 3),
            "retry_overhead_pct": round(
                (t_fault / max(t_clean, 1e-9) - 1.0) * 100.0, 2),
            "identity_ok": True,
        }

    _leg("retry", 120, _retry)

    def _speculation(budget_s):
        # the straggler is an injected `slow` fault at dist.itemExec: one
        # scripted 1.2s stall inside whichever item attempt fires first,
        # well past the 60ms priced timeout. The script is consumed once,
        # so the speculative re-dispatch of the stuck item runs clean —
        # the same one-straggler schedule on both sides of the comparison.
        straggle_s = 1.2
        items = list(range(8))
        want = [i * 10 for i in items]

        def fn(i):
            time.sleep(0.02)
            return i * 10

        knobs = {"delta.tpu.distributed.itemTimeoutMs": 60,
                 "delta.tpu.distributed.supervisor.intervalMs": 5,
                 "delta.tpu.distributed.speculation.slackFactor": 1.0}

        def run_once(spec_on, lbl):
            plan = FaultPlan(script=[("dist.itemExec", "slow")],
                             slow_ms=straggle_s * 1e3)
            with _c.set_temporarily(
                    **knobs,
                    **{"delta.tpu.faults.plan": plan,
                       "delta.tpu.distributed.speculation.enabled": spec_on}):
                t, rep = _timed(
                    lambda: run_sharded(items, fn, workers=4, label=lbl))
            assert not plan.script, "the scripted straggler never fired"
            return t, rep

        t_none, rep_none = run_once(False, "bench-nospec")
        t_spec, rep_spec = run_once(True, "bench-spec")
        assert rep_none.results == want and rep_spec.results == want
        assert rep_none.speculated == 0
        assert rep_spec.speculated >= 1 and rep_spec.rescued >= 1
        # the acceptance: rescuing the straggler must beat waiting it out
        assert t_spec < t_none, \
            f"speculation ({t_spec:.2f}s) did not beat " \
            f"no-speculation ({t_none:.2f}s)"
        return {
            "items": len(items),
            "straggle_s": straggle_s,
            "speculation_off_s": round(t_none, 3),
            "speculation_on_s": round(t_spec, 3),
            "speedup": round(t_none / max(t_spec, 1e-9), 2),
            "speculated": rep_spec.speculated,
            "rescued": rep_spec.rescued,
            "identity_ok": True,
        }

    _leg("speculation", 60, _speculation)

    def _posed(log, proc, **kw):
        cmd = OptimizeCommand(log, min_file_size=1 << 30, workers=4,
                              distribute=True, **kw)
        orig = dist_mod.process_info
        dist_mod.process_info = lambda: (proc, 2)
        try:
            cmd.run()
        finally:
            dist_mod.process_info = orig
        return cmd

    def _recovery(budget_s):
        solo = _mk(os.path.join(workdir, "c16_solo"),
                   np.random.RandomState(11))
        crash_path = os.path.join(workdir, "c16_crash")
        crashed = _mk(crash_path, np.random.RandomState(11))
        c_solo = OptimizeCommand(solo, min_file_size=1 << 30, workers=4)
        t_solo, _ = _timed(c_solo.run)
        ref_rows, ref_files = _rows_files(solo)

        base_recovered = telemetry.counters("dist").get(
            "dist.slice.recovered", 0)
        # host 1 dies on its first group rewrite, lease already published
        plan = FaultPlan(script=[("dist.itemExec", "crash_before_publish")])
        with _c.set_temporarily(**fast_retry,
                                **{"delta.tpu.faults.plan": plan}):
            try:
                _posed(crashed, proc=1)
            except SimulatedCrash:
                pass
            else:
                raise AssertionError("host 1 survived its scripted crash")
        assert len(leases.read_leases(crashed.log_path)) == 1
        past = time.time() - 120  # age the orphan's heartbeat past the ttl
        for p, _b, _m in leases.read_leases(crashed.log_path):
            os.utime(p, (past, past))

        DeltaLog.clear_cache()
        crashed = DeltaLog.for_table(crash_path)
        with _c.set_temporarily(
                **{"delta.tpu.distributed.lease.settleMs": 20}):
            t_recover, _ = _timed(lambda: _posed(crashed, proc=0))

        got_rows, got_files = _rows_files(crashed)
        recovered = telemetry.counters("dist").get(
            "dist.slice.recovered", 0) - base_recovered
        assert got_rows == ref_rows and got_files == ref_files, \
            "recovered table diverged from the solo run"
        assert recovered == 1, f"expected 1 recovered slice, got {recovered}"
        assert leases.read_leases(crashed.log_path) == []
        return {
            "rows": 32 * rows_per,
            "solo_s": round(t_solo, 3),
            "crash_recover_s": round(t_recover, 3),
            "recovery_overhead_pct": round(
                (t_recover / max(t_solo, 1e-9) - 1.0) * 100.0, 2),
            "slices_recovered": recovered,
            "identity_ok": True,
        }

    _leg("recovery", 150, _recovery)

    violations = sum(1 for leg in legs.values()
                     if not leg.get("identity_ok"))
    spec = legs.get("speculation", {})
    speedup = spec.get("speedup")
    ok = isinstance(speedup, (int, float)) and speedup > 0
    rec_pct = legs.get("recovery", {}).get("recovery_overhead_pct")
    return {
        "metric": "dist_speculation_speedup_vs_none",
        "value": round(speedup, 2) if ok else -1,
        "unit": "x" if ok else "error",
        "vs_baseline": round(speedup, 2) if ok else 0,
        "legs": legs,
        "gate": {
            "dist_fault_identity_violations": {
                "value": violations, "unit": "findings"},
            "recovery_overhead_pct": {
                "value": (max(round(rec_pct, 2), 0.0)
                          if isinstance(rec_pct, (int, float)) else -1),
                "unit": "pct",
            },
        },
    }


def _emit(results):
    headline = results.get("2") or next(iter(results.values()))
    print(json.dumps({
        "metric": headline["metric"],
        "value": headline["value"],
        "unit": headline["unit"],
        "vs_baseline": headline["vs_baseline"],
        "all": results,
    }), flush=True)


def _reset_engine_state():
    """Per-config isolation — and the cleanup a mid-config deadline abort
    relies on: a SIGALRM can fire anywhere, so the next config must never
    inherit half-built caches or log handles."""
    try:
        from delta_tpu import DeltaLog
        from delta_tpu.ops.key_cache import KeyCache
        from delta_tpu.ops.state_cache import DeviceStateCache

        from delta_tpu.ops.column_cache import ColumnCache

        DeltaLog.clear_cache()
        KeyCache.reset()
        DeviceStateCache.reset()
        ColumnCache.reset()
        from delta_tpu.obs import journal

        journal.reset()
        from delta_tpu.log import checkpointer

        checkpointer.reset()
        from delta_tpu import autopilot

        autopilot.reset()
        from delta_tpu.obs import fleet, slo, timeseries, trace_store

        timeseries.reset()
        slo.reset()
        fleet.reset()
        trace_store.reset()
    except Exception:
        pass


class ConfigDeadline(BaseException):
    """Raised by the SIGALRM handler: one config exceeded its deadline.
    BaseException, not Exception — the engine's defensive `except
    Exception` handlers (device-finalize host fallback, telemetry guards)
    must not swallow the deadline and leave the config running unbounded
    (the same reasoning that made PR 5's SimulatedCrash a BaseException)."""


def _parse_argv(argv):
    """(only, compare_path, threshold): positional config selector plus the
    regression-gate flags (``--compare BENCH_rN.json`` diffs this run
    against a prior round via tools/bench_diff and exits non-zero on
    regression past ``--compare-threshold`` percent)."""
    only = compare = None
    threshold = 20.0
    args = list(argv)
    while args:
        a = args.pop(0)
        if a == "--compare":
            if not args:
                sys.exit("bench.py: --compare requires a BENCH_*.json path")
            compare = args.pop(0)
        elif a == "--compare-threshold":
            if not args:
                sys.exit("bench.py: --compare-threshold requires a percent")
            try:
                threshold = float(args.pop(0))
            except ValueError:
                sys.exit("bench.py: --compare-threshold must be numeric")
        elif a.startswith("-"):
            # a typo'd gate flag must NOT fall through to the config
            # selector — it would match no config, run nothing, and pass
            # the regression gate vacuously
            sys.exit(f"bench.py: unknown flag {a!r}")
        else:
            only = a
    return only, compare, threshold


def main():
    import signal

    only, compare_path, compare_threshold = _parse_argv(sys.argv[1:])
    workdir = tempfile.mkdtemp(prefix="delta_tpu_bench_")
    # priority order: the headline and the device-win configs land first,
    # so a driver-side timeout still records the story; the long auxiliary
    # scale configs (2x, 7) run last under the soft budget below
    configs = {
        "2": lambda: bench_merge_upsert(workdir),
        "9": lambda: bench_commit_contention(workdir),
        "6": lambda: bench_hot_plan(workdir),
        "6p": lambda: bench_hot_plan(workdir, partitioned=True),
        "10": lambda: bench_pushdown(workdir),
        "11": lambda: bench_fleet(workdir),
        "13": lambda: bench_shadow(workdir),
        "14": lambda: bench_sharded_scan(workdir),
        "15": lambda: bench_trace_overhead(workdir),
        "16": lambda: bench_dist_faults(workdir),
        "12": lambda: bench_device_scan(workdir),
        "8": lambda: bench_resident_probe(workdir),
        "5": lambda: bench_checkpoint_replay(workdir),
        "3": lambda: bench_zorder_point_query(workdir),
        "4": lambda: bench_streaming_tail(workdir),
        "1": lambda: bench_overwrite_read(workdir),
        "2x": lambda: bench_merge_scale(workdir),
        "7": lambda: bench_replay_scale(workdir),
        # *w keys are subprocess-only workers (config 14's plan leg spawns
        # "14w" with a forced 8-device mesh); the full sweep skips them
        "14w": lambda: bench_sharded_scan_worker(),
    }
    results: dict = {}
    emitted = {"done": False}

    def bail(signum, frame):  # pragma: no cover - signal path
        if results and not emitted["done"]:
            emitted["done"] = True
            results["_partial"] = f"terminated by signal {signum}"
            _emit(results)
        sys.exit(1)

    signal.signal(signal.SIGTERM, bail)

    def _alarm(signum, frame):  # pragma: no cover - signal path
        raise ConfigDeadline()

    signal.signal(signal.SIGALRM, _alarm)
    # rc must be 0 with every claim driver-captured (ISSUE 6 satellite:
    # r5 hit the DRIVER's timeout — rc 124 — and lost its artifacts): the
    # soft budget leaves headroom under the driver's wall, and a PER-CONFIG
    # deadline skips-and-records any config that would blow it
    budget_s = float(os.environ.get("BENCH_BUDGET_S", "3000"))
    default_deadline = float(os.environ.get("BENCH_CONFIG_DEADLINE_S", "480"))
    per_config_deadline = {"2": 900.0, "2x": 540.0, "8": 600.0, "9": 420.0,
                           "14": 540.0, "16": 360.0}
    t_start = time.perf_counter()
    # deadline forensics: configs run with the flight recorder armed, so a
    # SIGALRM unwinding through the open span stack leaves an incident file
    # (spans + counters at the moment of the breach) — a timed-out config
    # is a diagnosable artifact, not just `"skipped": true` in the JSON
    from delta_tpu.obs import flight_recorder
    from delta_tpu.utils.config import conf as _conf

    flight_recorder.install()
    incident_dir = os.environ.get(
        "BENCH_INCIDENT_DIR",
        str(_conf.get("delta.tpu.obs.incidentDir")
            or os.path.join(os.getcwd(), "bench_incidents")),
    )
    def run_with_telemetry(fn):
        """Per-config isolation: reset the registry, run, attach a compact
        internal-metrics snapshot (top counters + phase-histogram summaries)
        so BENCH_*.json trajectories carry attributable phase deltas, not
        just wall-clock."""
        from delta_tpu.utils import telemetry

        telemetry.reset_all()
        out = fn()
        try:
            if isinstance(out, dict):
                # skip-rate counters always ride along: BENCH rounds track
                # row-group pruning effectiveness next to latency; router
                # audit + device-memory gauges carry the new cost-model
                # ledger per round
                out["telemetry"] = telemetry.bench_snapshot(
                    include=("scan.rowgroups", "scan.bytes.skipped",
                             "scan.bytes.deviceSkipped",
                             "scan.bytes.deviceSurvivor", "scan.device",
                             "columnCache", "scan.rewrites", "footerCache",
                             "table.health", "router", "device.hbm",
                             "journal", "advisor", "fleet", "slo", "dist",
                             "obs.scrape", "obs.server.clientAborts"),
                )
        except Exception:  # noqa: BLE001 — metrics must never fail the bench
            pass
        return out

    def _gate(results):
        """Mechanical regression gate (satellite): diff this run against a
        prior round's JSON and fail the process on regression, so perf
        claims in PRs are checkable instead of prose. Reports on stderr —
        stdout keeps the one-JSON-line contract."""
        if not compare_path:
            return
        from tools.bench_diff import compare

        with open(compare_path, encoding="utf-8") as f:
            prior = json.load(f)
        regressions = compare(results, prior, compare_threshold)
        for r in regressions:
            print(f"REGRESSION: {r.describe()}", file=sys.stderr)
        if regressions:
            sys.exit(3)
        print(f"bench gate OK vs {compare_path} "
              f"(threshold {compare_threshold:g}%)", file=sys.stderr)

    try:
        if only:
            results = {only: run_with_telemetry(configs[only])}
            emitted["done"] = True  # one-line contract: bail() must not re-emit
            print(json.dumps(results[only]))
            _gate(results)
            return
        for k, fn in configs.items():
            if k.endswith("w"):
                continue  # hidden subprocess-only worker configs
            elapsed = time.perf_counter() - t_start
            remaining = budget_s - elapsed
            if remaining < 60:
                results[k] = {
                    "metric": f"config_{k}", "value": -1, "unit": "skipped",
                    "vs_baseline": 0,
                    "note": f"skipped: soft budget BENCH_BUDGET_S="
                            f"{budget_s:.0f}s exhausted at {elapsed:.0f}s",
                }
                continue
            deadline = min(per_config_deadline.get(k, default_deadline),
                           remaining)
            t_cfg = time.perf_counter()
            signal.alarm(max(int(deadline), 1))
            try:
                with _conf.set_temporarily(
                    **{"delta.tpu.obs.incidentDir": incident_dir}
                ):
                    try:
                        results[k] = run_with_telemetry(fn)
                    except ConfigDeadline as dexc:
                        # the alarm unwound through the config's open spans
                        # with the recorder armed: an incident file already
                        # exists (fullest stack, deduped on the exception);
                        # a deadline outside any span records one here
                        inc = None
                        if not getattr(dexc, "_delta_incident_recorded",
                                       False):
                            from delta_tpu.utils.telemetry import UsageEvent

                            ev = UsageEvent(
                                f"bench.config.{k}.deadline",
                                int(time.time() * 1000),
                                tags={"config": k},
                                data={"deadlineS": deadline},
                            )
                            inc = flight_recorder.record_incident(ev, dexc)
                        else:
                            files = flight_recorder.incident_files(
                                incident_dir)
                            inc = files[-1] if files else None
                        results[k] = {
                            "metric": f"config_{k}", "value": -1,
                            "unit": "skipped", "vs_baseline": 0,
                            "note": f"skipped: per-config deadline "
                                    f"{deadline:.0f}s breached after "
                                    f"{time.perf_counter() - t_cfg:.0f}s",
                            "incident": inc,
                        }
            except Exception as e:  # record-and-continue: rc stays 0 and
                # every other config's artifact is still driver-captured
                results[k] = {
                    "metric": f"config_{k}", "value": -1, "unit": "error",
                    "vs_baseline": 0,
                    "note": f"{type(e).__name__}: {e}"[:300],
                }
            finally:
                signal.alarm(0)
                _reset_engine_state()
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    # the static-analysis gate rides the bench artifact (ISSUE 10): finding
    # counts land as a config entry (unit "findings" is lower-is-better in
    # tools/bench_diff, so --compare fails a round that grew findings) and
    # as the cataloged analysis.findings gauge in the telemetry snapshot
    try:
        from delta_tpu import analysis as _analysis
        from delta_tpu.utils import telemetry as _telemetry

        _report = _analysis.analyze_repo()
        _analysis.publish_metrics(_report)
        results["analysis"] = {
            "metric": "analysis_findings", "value": len(_report.findings),
            "unit": "findings", "vs_baseline": 0,
            "counts": _report.counts(),
            "waived": len(_report.suppressed),
            "baselined": len(_report.baselined),
            "telemetry": _telemetry.bench_snapshot(include=("analysis",)),
        }
    except Exception as e:  # noqa: BLE001 — the gate must not eat the bench
        results["analysis"] = {
            "metric": "analysis_findings", "value": -1, "unit": "error",
            "vs_baseline": 0, "note": f"{type(e).__name__}: {e}"[:300],
        }
    emitted["done"] = True
    _emit(results)
    _gate(results)


if __name__ == "__main__":
    main()
