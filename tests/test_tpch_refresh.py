"""TPC-H's refresh functions on `lineitem` through the public MERGE API: RF1
as `MERGE ... WHEN NOT MATCHED THEN INSERT *`, RF2 as `MERGE ... WHEN MATCHED
THEN DELETE` over an order key that is not unique in the target. The engine
agrees with the benchmark's plain reference
(`benchmark/tables/lineitem_refresh.py`) on seeded tables of a few thousand
rows in several files, with deletion vectors and without, on the device route
and on the host's; once the slab is warm both statements take the resident
pairs-only route, the slab is re-sorted once a pair and its sorted view
searched once a pair for the rows the delete before flipped, and the root span
says which statement it was and what it did."""
import numpy as np
import pyarrow as pa
import pytest

from benchmark.tables import lineitem_refresh as refresh
from delta_tpu import DeltaLog, DeltaTable
from delta_tpu.ops.key_cache import KeyCache
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

TABLE = {"rows": 3_000, "chunks": 2, "lines_per_order": [1, 7],
         "order_dates": [8035, 2406], "parts": 2_000_000, "suppliers": 100_000}
RF1_ON = "t.l_orderkey = s.l_orderkey"
RF2_ON = "t.l_orderkey = s.o_orderkey"
DV = "delta.tpu.enableDeletionVectors"
ROOT = "delta.dml.merge"


@pytest.fixture(autouse=True)
def _fresh_slabs_and_mode():
    """No slab from the test before, and the device-path mode `_load` pins
    put back."""
    KeyCache.reset()
    before = conf.get("delta.tpu.merge.devicePath.mode", "auto")
    yield
    conf.set("delta.tpu.merge.devicePath.mode", before)
    KeyCache.reset()


def _load(tmp_path, seed, dv=True, file_rows=700, mode="force"):
    """A seeded table in `rows / file_rows` files, and what made it."""
    gen = refresh.Generator(TABLE, seed)
    base = gen.base()
    path = str(tmp_path / "lineitem")
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": file_rows}):
        table = DeltaTable.create(path, data=refresh.to_arrow(base),
                                  configuration={DV: "true" if dv else "false"})
    conf.set("delta.tpu.merge.devicePath.mode", mode)
    return gen, base, table


def _send(table, function):
    """One refresh function as its MERGE; (inserted, deleted) as reported."""
    if isinstance(function, refresh.Rows):
        merge = table.alias("t").merge(refresh.to_arrow(function), RF1_ON,
                                       source_alias="s")
        m = merge.when_not_matched_insert_all().execute()
    else:
        source = pa.table({refresh.RF2_KEY: pa.array(function, pa.int64())})
        merge = table.alias("t").merge(source, RF2_ON, source_alias="s")
        m = merge.when_matched_delete().execute()
    return int(m["numTargetRowsInserted"]), int(m["numTargetRowsDeleted"])


def _agrees(table, base, functions, reports):
    """The table read back through a fresh handle is the reference's, row
    for row and cell for cell, and every statement reported its counts."""
    want, counts = refresh.ref_refresh(base, functions)
    DeltaLog.clear_cache()
    got = DeltaTable.for_path(table.delta_log.data_path).to_arrow()
    assert got.num_rows == len(want)
    assert refresh.diff_rows(got, want) == {
        "rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}
    assert reports == counts
    return want


def _orders_of(base, lines):
    """Loaded order keys that have exactly ``lines`` lines."""
    keys, counts = np.unique(base.lanes["l_orderkey"], return_counts=True)
    return keys[counts == lines].astype(np.int64)


@pytest.mark.parametrize("mode", ["force", "off"])
@pytest.mark.parametrize("dv", [True, False], ids=["dv", "rewrite"])
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 7])
def test_refresh_pairs_equal_the_reference(tmp_path, seed, dv, mode):
    gen, base, table = _load(tmp_path, seed, dv=dv, mode=mode)
    functions, reports = [], []
    for k in range(3):
        s = gen.refresh_set(base, k, 25)
        for f in (s.rf1, s.rf2):
            functions.append(f)
            reports.append(_send(table, f))
    want = _agrees(table, base, functions, reports)
    assert len(want) == len(base) + sum(i - d for i, d in reports)
    assert table.delta_log.update().version == 6  # one commit a function


def _case_one_line_and_seven(gen, base, bounds):
    s = gen.refresh_set(base, 0, 4, sizes=[1, 7, 7, 1])
    return [s.rf1, np.concatenate([_orders_of(base, 1)[:3],
                                   _orders_of(base, 7)[:3]])]


def _case_key_matches_nothing(gen, base, bounds):
    s = gen.refresh_set(base, 0, 5)
    # a key on an eighth nothing uses, one below every key, one far above
    return [np.concatenate([s.rf2[:2], s.rf2[:1] + 16, [0, 10**12]])]


def _case_order_deleted_before(gen, base, bounds):
    s = gen.refresh_set(base, 1, 6)
    return [s.rf2[:4], s.rf2]


def _case_rf1_sent_twice(gen, base, bounds):
    s = gen.refresh_set(base, 0, 10)
    return [s.rf1, s.rf1, s.rf2, s.rf1]


def _case_same_order_in_both(gen, base, bounds):
    s = gen.refresh_set(base, 2, 8)
    new = np.unique(s.rf1.lanes["l_orderkey"]).astype(np.int64)
    return [s.rf1, np.concatenate([s.rf2, new[:3]])]


def _case_run_crosses_a_file(gen, base, bounds):
    keys = base.lanes["l_orderkey"]
    split = [b for b in bounds if keys[b - 1] == keys[b]]
    assert split, "no order of this table lies across two files"
    s = gen.refresh_set(base, 0, 3)
    return [s.rf1, keys[split].astype(np.int64)]


CASES = {f.__name__[len("_case_"):]: f for f in (
    _case_one_line_and_seven, _case_key_matches_nothing,
    _case_order_deleted_before, _case_rf1_sent_twice,
    _case_same_order_in_both, _case_run_crosses_a_file)}


@pytest.mark.parametrize("dv", [True, False], ids=["dv", "rewrite"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_awkward_functions_equal_the_reference(tmp_path, case, dv):
    gen, base, table = _load(tmp_path, 5, dv=dv)
    rows = [f.num_logical_records
            for f in sorted(table.delta_log.update().all_files,
                            key=lambda f: f.path)]
    assert len(rows) == 5 and sum(rows) == len(base)
    functions = CASES[case](gen, base, np.cumsum(rows)[:-1])
    reports = [_send(table, f) for f in functions]
    _agrees(table, base, functions, reports)
    if case == "rf1_sent_twice":
        assert reports[1] == (0, 0) and reports[0][0] == len(functions[0])
        # its orders were not among RF2's, so the third copy inserts nothing
        assert reports[3] == (0, 0)
    if case == "key_matches_nothing":
        assert reports[0][1] == int(np.isin(base.lanes["l_orderkey"],
                                            functions[0]).sum())
    if case == "order_deleted_before":
        assert reports[1][1] > 0 and reports[0][1] > 0
    if case == "same_order_in_both":
        assert reports[1][1] > int(np.isin(base.lanes["l_orderkey"],
                                           functions[1]).sum())


def _statement_spans():
    """(root span's data, names of the key cache's spans) of the MERGE that
    just ran, and its router event's data."""
    roots = [e for e in telemetry.recent_events(ROOT) if e.op_type == ROOT]
    router = telemetry.recent_events("delta.merge.router")[-1].data
    slab = [e.op_type for e in telemetry.recent_events("delta.keyCache")]
    return roots[-1].data, slab, router


def test_warm_pairs_take_the_pairs_only_route_and_say_what_they_did(tmp_path):
    """Twelve files, so that the touched-files pre-probe would run: the
    first statement builds the table's slab all the same, and every
    statement after it is served by the slab alone."""
    gen, base, table = _load(tmp_path, 9, file_rows=250)
    assert len(table.delta_log.update().all_files) == 12
    counters0 = telemetry.counters("merge")
    seen, found = [], []
    for k in range(3):
        s = gen.refresh_set(base, k, 20)
        for name, f in (("rf1", s.rf1), ("rf2", s.rf2)):
            telemetry.clear_events()
            report = _send(table, f)
            root, slab, router = _statement_spans()
            seen.append((name, router.get("route"),
                         slab.count("delta.keyCache.sort"),
                         slab.count("delta.keyCache.locate")))
            found += [e.data for e in telemetry.recent_events(
                "delta.keyCache.locate")]
            assert not telemetry.recent_events("delta.dist.mergeProbe")
            assert root["clauses"] == ("insert" if name == "rf1" else "delete")
            assert root["sourceRows"] == len(f)
            assert (root["inserted"], root["deleted"]) == report
            assert root["updated"] == 0
    # the first RF1 decodes the keys and builds the slab; then the slab alone
    assert seen[0] == ("rf1", "decode", 1, 0)
    assert seen[1] == ("rf2", "pairs-only", 1, 0)
    # a warm pair: the search under RF1 (it flips the rows the RF2 before
    # it deleted, on a live sorted view), the re-sort under RF2 (RF1's rows)
    assert seen[2:] == [("rf1", "pairs-only", 0, 1),
                        ("rf2", "pairs-only", 1, 0)] * 2
    moved = {k: v - counters0.get(k, 0)
             for k, v in telemetry.counters("merge").items()}
    assert moved["merge.clause.insertOnly"] == 3
    assert moved["merge.clause.delete"] == 3
    assert moved["merge.resident.pairsOnly"] == 5
    assert moved["merge.keyCache.builds"] == 1
    assert moved.get("merge.resident.pairsOnly.declined", 0) == 0
    assert moved["merge.keyCache.flipSearches"] == 2
    assert moved.get("merge.keyCache.flipResorts", 0) == 0
    sorts = [e.data for e in telemetry.recent_events("delta.keyCache.sort")]
    assert sorts[-1]["cause"] == "append"
    assert [d["steps"] for d in found] == [1, 1]
    assert all(20 <= d["flips"] <= 140 for d in found)


@pytest.mark.parametrize("first", ["rf1", "rf2"])
def test_a_small_source_builds_the_tables_slab(tmp_path, first):
    """A source that touches one file of twelve, or none: the slab the
    first statement builds is the table's (every file, registered), not one
    over the files the pre-probe would have left."""
    gen, base, table = _load(tmp_path, 4, file_rows=250)
    s = gen.refresh_set(base, 0, 5)
    telemetry.clear_events()
    report = _send(table, s.rf1 if first == "rf1" else s.rf2)
    assert not telemetry.recent_events("delta.dist.mergeProbe")
    entries = KeyCache.instance()._entries
    assert len(entries) == 1
    slab = next(iter(entries.values()))
    assert len(slab.slabs) == 12 and slab.num_rows == len(base)
    assert report == ((len(s.rf1), 0) if first == "rf1" else
                      (0, int(np.isin(base.lanes["l_orderkey"], s.rf2).sum())))


@pytest.mark.parametrize("why", ["no_key_cache", "rewrite", "device_off"])
def test_the_pre_probe_still_narrows_where_no_slab_is_built(tmp_path, why):
    """Without a slab to build (the key cache off, the device path off) or
    with rows to rewrite (no deletion vectors: not pairs-only in shape) the
    touched-files pre-probe runs as before."""
    gen, base, table = _load(tmp_path, 4, dv=why != "rewrite", file_rows=250,
                             mode="off" if why == "device_off" else "force")
    s = gen.refresh_set(base, 0, 5)
    confs = {"delta.tpu.merge.keyCache.enabled": False} \
        if why == "no_key_cache" else {}
    telemetry.clear_events()
    with conf.set_temporarily(**confs):
        report = _send(table, s.rf2)
    probes = telemetry.recent_events("delta.dist.mergeProbe")
    assert len(probes) == 1 and probes[0].data["touched"] == 1
    _agrees(table, base, [s.rf2], [report])


# -- the generator of refresh sets and the reference, by themselves ---------------


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_a_refresh_set_is_laid_out_as_the_configuration_says(seed):
    gen = refresh.Generator(TABLE, seed)
    base = gen.base()
    loaded = np.unique(base.lanes["l_orderkey"])
    for k in range(3):
        s = gen.refresh_set(base, k, 30)
        # RF2: the k-th run of loaded orders in key order
        assert np.array_equal(s.rf2, loaded[30 * k:30 * (k + 1)])
        # RF1: the same orders on the next eighth of the sparse key
        new = np.unique(s.rf1.lanes["l_orderkey"])
        assert np.array_equal(new, s.rf2 + 8)
        assert not np.isin(new, loaded).any() and new.max() < loaded.max()
        lines = np.bincount(np.searchsorted(new, s.rf1.lanes["l_orderkey"]))
        assert lines.min() >= 1 and lines.max() <= 7
        assert np.array_equal(
            s.rf1.lanes["l_linenumber"],
            np.concatenate([np.arange(1, n + 1) for n in lines]))
        again = gen.refresh_set(base, k, 30)
        assert all(np.array_equal(a, again.rf1.lanes[n])
                   for n, a in s.rf1.lanes.items())
        arrow = refresh.to_arrow(s.rf1)
        assert arrow.schema.equals(refresh.to_arrow(base).schema)
        assert arrow.equals(refresh.to_arrow(again.rf1))
    other = refresh.Generator(TABLE, seed + 1)
    assert not np.array_equal(other.refresh_set(other.base(), 0, 30).rf1.lanes[
        "l_partkey"][:20], gen.refresh_set(base, 0, 30).rf1.lanes["l_partkey"][:20])
    with pytest.raises(ValueError, match="past the load's last"):
        gen.refresh_set(base, 10**6, 30)


def test_the_reference_inserts_once_and_deletes_whole_orders():
    gen = refresh.Generator(TABLE, 3)
    base = gen.base()
    s = gen.refresh_set(base, 0, 12)
    held = int(np.isin(base.lanes["l_orderkey"], s.rf2).sum())
    state, counts = refresh.ref_refresh(base, [s.rf1, s.rf1, s.rf2, s.rf2])
    assert counts == [(len(s.rf1), 0), (0, 0), (0, held), (0, 0)]
    assert len(state) == len(base) + len(s.rf1) - held
    assert not np.isin(state.lanes["l_orderkey"], s.rf2).any()
    key = refresh.packed_key(state.lanes)
    assert len(np.unique(key)) == len(key)
    # the control's RF2: one line an order goes, the first
    ref = refresh.Refresher(refresh.part_of(base),
                            rf2_deletes=refresh.first_line_only)
    assert ref.rf2(s.rf2) == 12
    left = ref.state()
    gone = np.setdiff1d(refresh.packed_key(base.lanes),
                        refresh.packed_key(left.lanes))
    assert np.array_equal(gone, (s.rf2 << 3) | 1)


def test_the_state_reads_back_as_the_tables_arrow():
    """An untouched state is the load: its Arrow form is `to_arrow`'s,
    comments included, through lanes and through Arrow."""
    gen = refresh.Generator(TABLE, 6)
    base = gen.base()
    arrow = refresh.to_arrow(base)
    state, _ = refresh.ref_refresh(base, [])
    assert state.to_arrow(refresh.NAMES).equals(arrow)
    again = refresh.Refresher(refresh.part_from_arrow(arrow)).state()
    assert again.to_arrow(refresh.NAMES).equals(arrow)
    assert refresh.diff_rows(arrow, state) == {
        "rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}


@pytest.mark.parametrize("fault", ["row_missing", "row_twice", "cell", "comment",
                                   "flag", "null"])
def test_the_comparison_counts_each_kind_of_difference(fault):
    gen = refresh.Generator(TABLE, 8)
    base = gen.base()
    s = gen.refresh_set(base, 0, 9)
    state, _ = refresh.ref_refresh(base, [s.rf1, s.rf2])
    good = state.to_arrow(refresh.NAMES)
    shuffled = good.take(pa.array(np.random.default_rng(0).permutation(
        good.num_rows)))
    assert refresh.diff_rows(shuffled, state) == {
        "rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}

    def with_column(name, values):
        i = good.schema.get_field_index(name)
        return good.set_column(i, pa.field(name, values.type), values)

    if fault == "row_missing":
        got, want = good.slice(1), dict(rows_missing=1, rows_extra=0, cells_wrong=0)
    elif fault == "row_twice":
        got = pa.concat_tables([good, good.slice(5, 2)])
        want = dict(rows_missing=0, rows_extra=2, cells_wrong=0)
    elif fault == "cell":
        tax = good.column("l_tax").to_pylist()
        tax[7] += 1
        got = with_column("l_tax", pa.array(tax, refresh.DECIMAL))
        want = dict(rows_missing=0, rows_extra=0, cells_wrong=1)
    elif fault == "comment":
        text = good.column("l_comment").to_pylist()
        text[3], text[4] = text[3] + "x", text[4][:-1]
        got = with_column("l_comment", pa.array(text, pa.string()))
        want = dict(rows_missing=0, rows_extra=0, cells_wrong=2)
    elif fault == "flag":
        flags = good.column("l_returnflag").to_pylist()
        flags[0] = "X"
        got = with_column("l_returnflag", pa.array(flags, pa.string()))
        want = dict(rows_missing=0, rows_extra=0, cells_wrong=1)
    else:
        dates = good.column("l_shipdate").to_pylist()
        dates[2] = None
        got = with_column("l_shipdate", pa.array(dates, pa.date32()))
        want = dict(rows_missing=0, rows_extra=0, cells_wrong=1)
    assert refresh.diff_rows(got, state) == want
    with pytest.raises(TypeError, match="l_quantity"):
        refresh.diff_rows(with_column("l_quantity", good.column(
            "l_quantity").cast(pa.float64())), state)


def test_bench_spans_tells_a_pairs_two_statements_apart(tmp_path):
    """`tools/bench_spans.py` splits a request's spans by the `clauses` of
    the root span they started under: the search of the sorted view under
    RF1, the re-sort and the vector under RF2."""
    import importlib.util
    import os
    from types import SimpleNamespace

    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_spans.py"))
    bench_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_spans)

    gen, base, table = _load(tmp_path, 2)
    requests = []
    for k in range(3):
        s = gen.refresh_set(base, k, 20)
        telemetry.clear_events()
        _send(table, s.rf1)
        _send(table, s.rf2)
        requests.append(SimpleNamespace(spans=[
            {"name": e.op_type, "start_us": e.start_us,
             "duration_us": e.duration_us, "data": e.data}
            for e in telemetry.recent_events()]))
    split = bench_spans.by_clauses(requests[1:])  # the warm pairs
    assert sorted(split) == ["delete", "insert"]
    (n_rf1, rf1), (n_rf2, rf2) = split["insert"], split["delete"]
    assert n_rf1 == n_rf2 == 2
    assert "delta.keyCache.locate" in rf1 and "delta.keyCache.sort" not in rf1
    assert "delta.keyCache.sort" in rf2 and "delta.keyCache.locate" not in rf2
    assert "delta.dml.merge.deletionVectors" in rf2
    assert "delta.dml.merge.deletionVectors" not in rf1
    assert rf1["delta.dml.merge"] >= rf1["delta.dml.merge.write"] > 0
