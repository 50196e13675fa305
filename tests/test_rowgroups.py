"""Row-group data skipping (exec/rowgroups): footer-stats pushdown, late
materialization, the footer cache, and the consumers wired through it.

The core property: for ANY predicate, a scan with the second pruning tier on
is result-identical to a full decode — across nulls, NaN floats, timestamp
ms-truncation round-up, IN/OR shapes, schema-evolved files missing the
predicate column, and files with deletion vectors (whose positions must stay
PHYSICAL under skipping, or DV DML would corrupt files).
"""
import datetime as dt
import math
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from delta_tpu.api.tables import DeltaTable
from delta_tpu.commands.delete import DeleteCommand
from delta_tpu.commands.update import UpdateCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.exec import rowgroups
from delta_tpu.expr.parser import parse_predicate
from delta_tpu.log.deltalog import DeltaLog
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


N = 4000
RG = 500  # rows per row group → 8 groups per single-file write


def _assert_same(a: pa.Table, b: pa.Table):
    """Row-set equality, NaN-aware (pa.Table.equals has NaN != NaN) and
    order-insensitive (sorted by id)."""
    assert a.column_names == b.column_names
    a, b = a.sort_by("id"), b.sort_by("id")
    assert a.num_rows == b.num_rows
    for name in a.column_names:
        va, vb = a.column(name).to_pylist(), b.column(name).to_pylist()
        for x, y in zip(va, vb):
            if isinstance(x, float) and isinstance(y, float) \
                    and math.isnan(x) and math.isnan(y):
                continue
            assert x == y, (name, x, y)


def _table(n=N):
    """Mixed-type table with nulls, NaN, sub-ms timestamps, strings."""
    rng = np.random.RandomState(7)
    ids = np.arange(n, dtype=np.int64)
    f = rng.randn(n)
    f[rng.rand(n) < 0.05] = np.nan
    base = dt.datetime(2021, 1, 1)
    return pa.table({
        "id": ids,
        "v": pa.array([None if i % 17 == 0 else int(i % 100) for i in range(n)],
                      pa.int64()),
        "f": pa.array(f, pa.float64()),
        "name": pa.array(["k%04d" % (i % 500) for i in range(n)]),
        # microsecond tails exercise the ms-truncation round-up path
        "ts": pa.array([base + dt.timedelta(seconds=int(i), microseconds=i % 1000)
                        for i in range(n)], pa.timestamp("us")),
    })


@pytest.fixture
def rg_conf():
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": RG}):
        yield


@pytest.fixture
def rg_table(tmp_table, rg_conf):
    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", _table()).run()
    return tmp_table


PREDICATES = [
    "id < 200",                                   # leading-group range
    "id >= 3700",                                 # trailing-group range
    "id >= 900 AND id < 1100",                    # straddles a boundary
    "id = 1234",                                  # point
    "id IN (10, 2500, 3999)",                     # IN across groups
    "id < 100 OR id >= 3900",                     # OR of two windows
    "v IS NULL AND id < 600",                     # null test + range
    "v IS NOT NULL AND id < 600",
    "f > 2.5",                                    # NaN-carrying float
    "name = 'k0007'",                             # string equality
    "name >= 'k0490'",                            # string range
    "ts < '2021-01-01 00:05:00'",                 # timestamp bound
    "ts >= '2021-01-01 00:55:00.000500'",         # sub-ms boundary
    "id < 0",                                     # empty result
    "id % 7 = 3 AND id < 900",                    # non-lowerable conjunct
]


@pytest.mark.parametrize("pred", PREDICATES)
def test_skipping_result_identical(rg_table, pred):
    t = DeltaTable.for_path(rg_table)
    with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
        full = t.to_arrow(filters=[pred])
    skipped = t.to_arrow(filters=[pred])
    _assert_same(skipped, full)


def test_selective_scan_prunes_and_counts(rg_table):
    telemetry.clear_counters()
    t = DeltaTable.for_path(rg_table)
    out = t.to_arrow(filters=["id < 200"])
    assert out.num_rows == 200
    c = telemetry.counters()
    assert c.get("scan.rowgroups.total", 0) == N // RG
    assert c.get("scan.rowgroups.pruned", 0) == N // RG - 1
    assert c.get("scan.bytes.skipped", 0) > 0


def test_skipping_off_decodes_everything(rg_table):
    telemetry.clear_counters()
    t = DeltaTable.for_path(rg_table)
    with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
        t.to_arrow(filters=["id < 200"])
    c = telemetry.counters()
    assert "scan.rowgroups.total" not in c
    assert "scan.rowgroups.pruned" not in c
    assert "footerCache.misses" not in c  # footers aren't even consulted


def test_late_materialization_skips_mask_empty_groups(rg_table):
    """A predicate footer stats can't lower still skips groups once the
    predicate columns are decoded and the mask comes back empty."""
    telemetry.clear_counters()
    t = DeltaTable.for_path(rg_table)
    out = t.to_arrow(filters=["id % 7919 = 600"])  # v%prime: never true > 600
    c = telemetry.counters()
    assert c.get("scan.rowgroups.pruned", 0) == 0  # stats keep everything
    assert c.get("scan.rowgroups.lateSkipped", 0) == N // RG - 1
    with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
        full = t.to_arrow(filters=["id % 7919 = 600"])
    _assert_same(out, full)


# -- deletion vectors: positions must stay physical ------------------------


@pytest.fixture
def dv_table(tmp_table, rg_conf):
    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(
        log, "append", _table(),
        configuration={"delta.tpu.enableDeletionVectors": "true"},
    ).run()
    return tmp_table


def test_dv_delete_with_pruning_keeps_physical_positions(dv_table):
    log = DeltaLog.for_table(dv_table)
    # two DV deletes against the SAME file: the second extends the DV using
    # positions read from a row-group-pruned decode — any logical/physical
    # confusion deletes the wrong rows
    DeleteCommand(log, "id >= 3900").run()
    DeleteCommand(log, "id < 50").run()
    t = DeltaTable.for_path(dv_table)
    out = t.to_arrow(columns=["id"])
    ids = sorted(out.column("id").to_pylist())
    assert ids == list(range(50, 3900))
    # and the survivors read back identically without skipping
    with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
        full = t.to_arrow(columns=["id"])
    assert sorted(full.column("id").to_pylist()) == ids


def test_dv_update_with_pruning(dv_table):
    log = DeltaLog.for_table(dv_table)
    UpdateCommand(log, {"name": "'touched'"}, "id >= 3800 AND v = 10").run()
    t = DeltaTable.for_path(dv_table)
    out = t.to_arrow()
    touched = out.filter(pa.compute.equal(out.column("name"), "touched"))
    expected = [i for i in range(3800, N) if i % 17 != 0 and i % 100 == 10]
    assert sorted(touched.column("id").to_pylist()) == expected
    assert out.num_rows == N  # update never loses rows


def test_scan_of_dv_file_with_pruning(dv_table):
    log = DeltaLog.for_table(dv_table)
    DeleteCommand(log, "id >= 100 AND id < 150").run()
    t = DeltaTable.for_path(dv_table)
    out = t.to_arrow(filters=["id < 300"])
    assert sorted(out.column("id").to_pylist()) == (
        list(range(100)) + list(range(150, 300))
    )


def test_dv_merge_with_pruning(dv_table):
    """DV-mode MERGE prunes candidate row groups by the target-only
    conjuncts of the condition — matched rows still mark the right
    PHYSICAL positions, unmatched rows stay live in place."""
    t = DeltaTable.for_path(dv_table)
    src = pa.table({
        "sid": pa.array([3950, 3999, 123456], pa.int64()),
        "sname": pa.array(["a", "b", "c"]),
    })
    telemetry.clear_counters()
    (t.merge(src, "id = sid AND id >= 3900")
     .when_matched_update({"name": "sname"})
     .when_not_matched_insert({
         "id": "sid", "v": "0", "f": "0.0", "name": "sname"}).execute())
    assert telemetry.counters().get("scan.rowgroups.pruned", 0) > 0
    out = t.to_arrow()
    assert out.num_rows == N + 1  # one insert, nothing lost
    by_id = dict(zip(out.column("id").to_pylist(),
                     out.column("name").to_pylist()))
    assert by_id[3950] == "a" and by_id[3999] == "b"
    assert by_id[123456] == "c"
    assert by_id[100] == "k0100"  # untouched row intact


def test_insert_only_merge_with_pruning(rg_table):
    t = DeltaTable.for_path(rg_table)
    src = pa.table({
        "sid": pa.array([500, 999999], pa.int64()),
        "sname": pa.array(["dup", "new"]),
    })
    telemetry.clear_counters()
    (t.merge(src, "id = sid AND id < 1000")
     .when_not_matched_insert({
         "id": "sid", "v": "1", "f": "1.0", "name": "sname"}).execute())
    # candidate files' groups outside id < 1000 never decode
    assert telemetry.counters().get("scan.rowgroups.pruned", 0) > 0
    out = t.to_arrow()
    assert out.num_rows == N + 1  # id=500 matched (no insert), 999999 new
    assert 999999 in out.column("id").to_pylist()


# -- schema evolution: missing predicate column keeps every group ----------


def test_evolved_file_missing_predicate_column(tmp_table, rg_conf):
    log = DeltaLog.for_table(tmp_table)
    old = pa.table({"id": pa.array(range(2000), pa.int64())})
    WriteIntoDelta(log, "append", old).run()
    new = pa.table({
        "id": pa.array(range(2000, 4000), pa.int64()),
        "extra": pa.array(range(2000), pa.int64()),
    })
    WriteIntoDelta(log, "append", new, merge_schema=True).run()
    t = DeltaTable.for_path(tmp_table)
    for pred in ["extra < 100", "extra < 100 OR id < 10", "extra IS NULL"]:
        with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
            full = t.to_arrow(filters=[pred])
        out = t.to_arrow(filters=[pred])
        _assert_same(out, full)


def test_predicate_column_outside_projection(rg_table):
    """A predicate column stored in the file but excluded from the decode
    projection must not late-skip matching groups (it would mask as
    all-null): late materialization disables itself and the result stays
    identical to a full decode."""
    from delta_tpu.exec.scan import read_files_as_table

    log = DeltaLog.for_table(rg_table)
    snap = log.update()
    out = read_files_as_table(
        log.data_path, snap.all_files, snap.metadata,
        columns=["id", "name"],
        predicate=parse_predicate("id >= 0 AND v = 50"),
    )
    # rows are NOT filtered by the decode — every row of surviving groups
    # comes back; with the guard, no group late-skips on the null mask
    assert out.num_rows == N
    with pytest.raises(ValueError):
        read_files_as_table(
            log.data_path, snap.all_files, snap.metadata,
            positions_of_interest=[np.array([0])] * (len(snap.all_files) + 1),
        )


# -- planner unit behavior -------------------------------------------------


def _write_rg_file(path, table, rg_rows):
    pq.write_table(table, path, row_group_size=rg_rows)
    return pq.read_metadata(path)


def test_planner_conservative_on_nan_bounds(tmp_path):
    # craft a file whose float bounds are NaN (legacy-writer shape is
    # simulated by an all-NaN group: Arrow then omits bounds → keep)
    p = str(tmp_path / "nan.parquet")
    t = pa.table({"f": pa.array([np.nan] * 10 + [5.0] * 10, pa.float64())})
    meta = _write_rg_file(p, t, 10)
    plan = rowgroups.plan_row_groups(meta, parse_predicate("f > 100.0"))
    # group 0 (all NaN, no bounds) must survive; group 1 (max=5) prunes
    assert 0 in plan.keep and 1 not in plan.keep


def test_planner_null_count_short_circuit(tmp_path):
    p = str(tmp_path / "nulls.parquet")
    t = pa.table({"v": pa.array([None] * 10 + list(range(10)), pa.int64())})
    meta = _write_rg_file(p, t, 10)
    plan = rowgroups.plan_row_groups(meta, parse_predicate("v IS NULL"))
    assert plan.keep == [0]  # group 1 has nullCount == 0
    plan = rowgroups.plan_row_groups(meta, parse_predicate("v IS NOT NULL"))
    assert plan.keep == [1]  # group 0 is all null


def test_planner_timestamp_bounds(tmp_path):
    p = str(tmp_path / "ts.parquet")
    base = dt.datetime(2021, 6, 1)
    t = pa.table({"ts": pa.array(
        [base + dt.timedelta(minutes=i) for i in range(20)], pa.timestamp("us")
    )})
    meta = _write_rg_file(p, t, 10)
    plan = rowgroups.plan_row_groups(
        meta, parse_predicate("ts >= '2021-06-01 00:15:00'"))
    assert plan.keep == [1]


# -- the planner reads only the statistics its predicate names --------------


def _wide_table(n=N, late=True):
    """Many flat columns, sorted so that each kind of bound can prune: an
    all-null group 2 in ``v``, an all-NaN group 0 in ``f`` (no bounds),
    binary ``blob``, a mixed-case name, and ``late`` for schema evolution."""
    i = np.arange(n)
    f = i.astype(np.float64)
    f[:RG] = np.nan
    base = dt.datetime(2021, 1, 1)
    cols = {
        "id": pa.array(i, pa.int64()),
        "v": pa.array([None if k // RG == 2 else int(k) for k in i], pa.int64()),
        "f": pa.array(f, pa.float64()),
        "name": pa.array(["k%05d" % k for k in i]),
        "blob": pa.array([b"%05d" % k for k in i], pa.binary()),
        "ts": pa.array([base + dt.timedelta(minutes=int(k)) for k in i],
                       pa.timestamp("us")),
        "d": pa.array([dt.date(2021, 1, 1) + dt.timedelta(days=int(k) // 10)
                       for k in i], pa.date32()),
        "MixedCase": pa.array(i // 10, pa.int64()),
        "price": pa.array(i * 3, pa.int64()),
    }
    if late:
        cols["late"] = pa.array(i, pa.int64())
    rng = np.random.RandomState(3)
    for j in range(12):
        cols[f"pad{j}"] = pa.array(rng.randint(0, 9, n), pa.int64())
    return pa.table(cols)


def _wide_types():
    from delta_tpu.schema import types as T

    return {"id": T.LongType(), "v": T.LongType(), "f": T.DoubleType(),
            "name": T.StringType(), "blob": T.BinaryType(),
            "ts": T.TimestampType(), "d": T.DateType(),
            "mixedcase": T.LongType(), "price": T.LongType(),
            "late": T.LongType(), "p": T.StringType()}


def _plan_every_column(meta, predicate, part_row, partition_cols, types):
    """The plan as the planner made it when each row group's environment
    held every flat column's statistics."""
    from delta_tpu.expr import ir, synthesis
    from delta_tpu.ops.pruning import conjunct_rewrites

    rewrites = conjunct_rewrites([predicate], partition_cols, types)
    rewritten = ir.and_all([r.rewritten for r in rewrites])
    col_index = rowgroups._column_index(meta)
    floats = rowgroups._float_leaves(meta, col_index)
    keep, skipped, pruned = [], 0, []
    for i in range(meta.num_row_groups):
        env = rowgroups._rg_env(meta, i, col_index, floats, part_row)
        try:
            verdict = rewritten.eval(env)
        except Exception:
            verdict = None
        if verdict is False:
            skipped += meta.row_group(i).total_byte_size
            pruned.append(env)
        else:
            keep.append(i)
    fired = [{"family": r.family or "other",
              "conjunct": synthesis.shape(r.conjunct),
              "rewrite": synthesis.shape(r.rewritten)}
             for r in rewrites
             if r.synthesized and pruned
             and any(rowgroups._safe_false(r.rewritten, e) for e in pruned)]
    return keep, skipped, fired


# (predicate, partition value of ``p`` or None, file has ``late``, the
# kept groups, the leaves whose statistics the plan reads)
PLAN_SHAPES = {
    "compare": ("id >= 3200", None, True, [6, 7], 1),
    "between": ("id BETWEEN 1200 AND 1700", None, True, [2, 3], 1),
    "in": ("id IN (7, 3999)", None, True, [0, 7], 1),
    "is_null": ("v IS NULL", None, True, [2], 1),
    "is_not_null": ("v IS NOT NULL", None, True, [0, 1, 3, 4, 5, 6, 7], 1),
    "or_across_columns": ("id < 300 OR price >= 11000", None, True, [0, 7], 2),
    "arithmetic": ("price * 2 >= 22000", None, True, [7], 1),
    "string_prefix": ("substr(name, 1, 4) = 'k001'", None, True, [0], 1),
    "temporal": ("to_date(ts) = '2021-01-03'", None, True, [5, 6, 7], 1),
    "date_shift": ("date_add(d, 30) <= '2021-03-01'", None, True, [0], 1),
    "partition_or_prunes": ("p = 'b' OR id >= 3500", "a", True, [7], 1),
    "partition_or_matches": ("p = 'b' OR id >= 3500", "b", True,
                             list(range(8)), 1),
    "nan_float_bounds": ("f > 3000.5", None, True, [0, 6, 7], 1),
    "binary_bounds": ("blob = 'x' AND id >= 3500", None, True, [7], 2),
    "missing_column_and": ("late > 5 AND id >= 3000", None, False, [6, 7], 1),
    "missing_column_or": ("late > 5 OR id >= 3000", None, False,
                          list(range(8)), 1),
    "mixed_case": ("mixedCASE >= 300 AND MIXEDcase < 350", None, True, [6], 1),
    "three_columns": ("id >= 500 AND v IS NOT NULL AND price < 3000", None,
                      True, [1], 3),
}


@pytest.mark.parametrize("shape", sorted(PLAN_SHAPES))
def test_planner_reads_only_referenced_statistics(tmp_path, shape):
    """The plan over the referenced leaves' statistics alone equals, field
    for field, the plan over every column's."""
    from delta_tpu.ops.pruning import conjunct_rewrites

    text, part, late, kept, read = PLAN_SHAPES[shape]
    meta = _write_rg_file(str(tmp_path / "w.parquet"), _wide_table(late=late), RG)
    pred = parse_predicate(text)
    part_row = {"p": part} if part is not None else None
    pcols = frozenset({"p"}) if part is not None else frozenset()
    types = _wide_types()
    keep, skipped, fired = _plan_every_column(meta, pred, part_row, pcols, types)
    plans = [rowgroups.plan_row_groups(meta, pred, part_row, pcols, types),
             rowgroups.plan_row_groups(
                 meta, pred, part_row, pcols, types,
                 rewrites=conjunct_rewrites([pred], pcols, types))]
    for plan in plans:
        assert (plan.keep, plan.skipped_bytes, plan.fired) == (keep, skipped, fired)
        assert plan.keep == kept
        assert plan.stats_columns == read
    if shape in ("arithmetic", "string_prefix", "temporal", "date_shift"):
        assert fired  # the synthesized rewrite's attribution is compared


def test_scan_plan_span_counts_the_statistics_it_read(tmp_table, rg_conf):
    """``statsColumns`` on ``delta.scan.decode.open.plan``: a two-column
    predicate over a many-column file reads two leaves' statistics; one
    with no lowerable conjunct keeps every group and reads none."""
    data = _wide_table()
    WriteIntoDelta(DeltaLog.for_table(tmp_table), "append", data).run()
    t = DeltaTable.for_path(tmp_table)
    for pred, n_rows, read in (("id >= 3200 AND price < 10500", 300, 2),
                               ("name LIKE '%7'", N // 10, 0)):
        telemetry.clear_events()
        got = t.to_arrow(filters=[pred], columns=["id"])
        assert got.num_rows == n_rows
        plans = telemetry.recent_events("delta.scan.decode.open.plan")
        assert plans
        for ev in plans:
            assert ev.data["rowGroups"] == N // RG
            assert ev.data["statsColumns"] == read < data.num_columns
            if read == 0:
                assert ev.data["kept"] == ev.data["rowGroups"]


def test_bench_spans_prints_the_statistics_each_plan_read(tmp_table, rg_conf,
                                                         capsys):
    """`tools/bench_spans.py` prints ``footerCached`` and ``statsColumns``
    of the window's row-group plans."""
    import importlib.util
    import json
    import time

    from benchmark.harness.runner import Request, Run

    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_spans.py"))
    bench_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_spans)
    WriteIntoDelta(DeltaLog.for_table(tmp_table), "append", _wide_table()).run()
    t = DeltaTable.for_path(tmp_table)
    t.to_arrow(filters=["id >= 3200"], columns=["id"])
    telemetry.clear_events()
    t0 = time.perf_counter()
    t.to_arrow(filters=["id >= 3200 AND v IS NOT NULL"], columns=["id"])
    spans = [{"name": e.op_type, "start_us": e.start_us,
              "duration_us": e.duration_us, "thread": e.thread_id,
              "data": e.data} for e in telemetry.recent_events()]
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, requests=[
        Request(0, t0, time.perf_counter(), True, spans=spans)],
        trace=None, counters={})
    bench_spans.report(run)
    line = next(l for l in capsys.readouterr().err.splitlines()
                if l.startswith("row-group plans"))
    assert json.loads(line[line.index("{"):]) == {"True, 2": 1}


def test_stats_leaves_of_a_rewrite():
    from delta_tpu.expr import ir
    from delta_tpu.ops.pruning import skipping_predicate

    rw = skipping_predicate(parse_predicate(
        "Id >= 3 AND v IS NULL AND p = 'a' AND v IS NOT NULL"), frozenset({"p"}))
    assert rowgroups.stats_leaves(rw) == {"id", "v"}
    assert rowgroups.stats_leaves(ir.Literal(None)) == frozenset()


def test_row_groups_for_positions(tmp_path):
    p = str(tmp_path / "pos.parquet")
    t = pa.table({"v": pa.array(range(40), pa.int64())})
    meta = _write_rg_file(p, t, 10)
    assert rowgroups.row_groups_for_positions(meta, [0, 35]) == {0, 3}
    assert rowgroups.row_groups_for_positions(meta, [11, 12]) == {1}
    assert rowgroups.row_groups_for_positions(meta, []) == frozenset()
    off = rowgroups.row_group_offsets(meta)
    assert list(off) == [0, 10, 20, 30, 40]


# -- footer cache ----------------------------------------------------------


def test_footer_cache_invalidation_on_rewrite(tmp_path):
    cache = rowgroups.FooterCache()
    p = str(tmp_path / "c.parquet")
    pq.write_table(pa.table({"v": pa.array(range(100), pa.int64())}), p)
    m1 = cache.get(p)
    assert cache.get(p) is m1  # hit: same parsed object
    # rewrite in place with different content (and force a distinct mtime)
    pq.write_table(pa.table({"v": pa.array(range(7), pa.int64())}), p)
    os.utime(p, ns=(1, 1))
    m2 = cache.get(p)
    assert m2 is not m1
    assert m2.num_rows == 7


def test_footer_cache_bounded_and_disabled(tmp_path):
    cache = rowgroups.FooterCache()
    paths = []
    for i in range(5):
        p = str(tmp_path / f"f{i}.parquet")
        pq.write_table(pa.table({"v": pa.array([i], pa.int64())}), p)
        paths.append(p)
    with conf.set_temporarily(**{"delta.tpu.read.footerCacheEntries": 3}):
        for p in paths:
            cache.get(p)
        assert len(cache) == 3  # LRU bounded
    with conf.set_temporarily(**{"delta.tpu.read.footerCacheEntries": 0}):
        before = len(cache)
        m = cache.get(paths[0])
        assert m.num_rows == 1 and len(cache) == before  # nothing cached


# -- CONVERT footer-derived stats ------------------------------------------


def test_stats_from_footer_matches_decode(tmp_path):
    from delta_tpu.exec.parquet import collect_stats

    p = str(tmp_path / "s.parquet")
    t = _table(1000)
    meta = _write_rg_file(p, t, 300)
    footer = rowgroups.stats_from_footer(meta)
    assert footer is not None
    decoded = collect_stats(pq.read_table(p))
    assert footer["numRecords"] == decoded["numRecords"]
    assert footer["nullCount"] == decoded["nullCount"]
    # every decode-derived bound matches the footer-derived one, including
    # the timestamp max rounded UP to the next millisecond
    assert footer["minValues"] == decoded["minValues"]
    assert footer["maxValues"] == decoded["maxValues"]


def test_stats_from_footer_declines_statless_files(tmp_path):
    p = str(tmp_path / "ns.parquet")
    pq.write_table(pa.table({"v": pa.array(range(10), pa.int64())}), p,
                   write_statistics=False)
    assert rowgroups.stats_from_footer(pq.read_metadata(p)) is None


def test_convert_uses_footer_stats(tmp_path):
    from delta_tpu.commands.convert import ConvertToDeltaCommand

    d = str(tmp_path / "conv")
    os.makedirs(d)
    pq.write_table(_table(1000), os.path.join(d, "part-0.parquet"),
                   row_group_size=300)
    telemetry.clear_counters()
    log = DeltaLog.for_table(d)
    ConvertToDeltaCommand(log, collect_stats=True).run()
    c = telemetry.counters()
    assert c.get("convert.stats.fromFooter", 0) == 1
    assert c.get("convert.stats.fromDecode", 0) == 0
    snap = log.update()
    [add] = snap.all_files
    st = add.stats_dict()
    assert st["numRecords"] == 1000
    assert st["minValues"]["id"] == 0 and st["maxValues"]["id"] == 999


# -- CDF + streaming consumers ---------------------------------------------


def test_cdf_dv_diff_reads_targeted_row_groups(tmp_table, rg_conf):
    from delta_tpu.exec.cdf import read_changes

    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(
        log, "append", _table(),
        configuration={"delta.tpu.enableDeletionVectors": "true"},
    ).run()
    v = DeleteCommand(log, "id >= 3990").run()
    telemetry.clear_counters()
    changes = read_changes(log, v, v)
    deletes = changes.filter(
        pa.compute.equal(changes.column("_change_type"), "delete"))
    assert sorted(deletes.column("id").to_pylist()) == list(range(3990, N))
    c = telemetry.counters()
    # only the final row group (holding positions 3990+) decodes
    assert c.get("scan.rowgroups.pruned", 0) == N // RG - 1


def test_streaming_source_filters(tmp_table, rg_conf):
    from delta_tpu.streaming.source import DeltaSource

    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", _table()).run()
    src = DeltaSource(log, filters=["id < 120"])
    end = src.latest_offset(src.initial_offset())
    batch = src.get_batch(None, end)
    assert sorted(batch.column("id").to_pylist()) == list(range(120))
    # unfiltered source unchanged
    src2 = DeltaSource(log)
    batch2 = src2.get_batch(None, src2.latest_offset(src2.initial_offset()))
    assert batch2.num_rows == N


# -- char(n) long-literal padding (satellite) ------------------------------


def test_char_long_literal_matches_stored_padded(tmp_table):
    from delta_tpu.schema.types import CharType, LongType, StructType

    schema = StructType().add("id", LongType()).add("c", CharType(3))
    t = DeltaTable.create(tmp_table, schema)
    data = pa.table({"id": pa.array([1, 2], pa.int64()),
                     "c": pa.array(["ab", "xyz"])})
    WriteIntoDelta(t.delta_log, "append", data).run()
    # stored form is 'ab ' (padded to 3); a 4-char literal with trailing
    # spaces must still match it (reference pads the column side up)
    out = t.to_arrow(filters=["c = 'ab  '"])
    assert out.column("id").to_pylist() == [1]
    out = t.to_arrow(filters=["c IN ('ab   ', 'zz')"])
    assert out.column("id").to_pylist() == [1]
    # over-length literal with non-space tail can never match
    out = t.to_arrow(filters=["c = 'abcd'"])
    assert out.num_rows == 0
    # short literals keep padding up (regression for the original path)
    out = t.to_arrow(filters=["c = 'ab'"])
    assert out.column("id").to_pylist() == [1]


# -- partitioned tables: mixed OR branches bind partition values -----------


def test_partitioned_mixed_or_predicate(tmp_table, rg_conf):
    log = DeltaLog.for_table(tmp_table)
    data = pa.table({
        "id": pa.array(range(2000), pa.int64()),
        "p": pa.array(["a" if i < 1000 else "b" for i in range(2000)]),
    })
    WriteIntoDelta(log, "append", data, partition_columns=["p"]).run()
    t = DeltaTable.for_path(tmp_table)
    pred = "p = 'a' OR id >= 1900"
    with conf.set_temporarily(**{"delta.tpu.read.rowGroupSkipping": False}):
        full = t.to_arrow(filters=[pred])
    out = t.to_arrow(filters=[pred])
    _assert_same(out, full)
    assert sorted(out.column("id").to_pylist()) == (
        list(range(1000)) + list(range(1900, 2000))
    )
