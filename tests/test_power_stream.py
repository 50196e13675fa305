"""TPC-H's power-test stream on `lineitem` through the public entry points:
RF1 (`MERGE ... WHEN NOT MATCHED THEN INSERT *`), the stream's queries as SQL
text (`execute_sql`: Q6 and Q1, the two templates that read LINEITEM alone),
RF2 (`MERGE ... WHEN MATCHED THEN DELETE`), on one table. The engine agrees
with the benchmark's plain reference
(`benchmark/tables/lineitem_power.py::ref_power`, which follows the table's
state through the stream) on seeded tables of a few thousand rows: every
query's answer, to the last digit, is that of the version the statement
before it committed, and the table read back at the end is the reference's.
The device aggregate route answers over files with a deletion vector and
without one, over lanes of two padded lengths, and equals the host route; a
file's vector is a resident keep mask of the column cache, reused across
queries, rebuilt when the file's vector changes, and gone with the file's
lanes."""
import json
import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from benchmark.tables import lineitem_power as power
from delta_tpu import DeltaLog, DeltaTable
from delta_tpu.ops import column_cache
from delta_tpu.ops.column_cache import KEEP, ColumnCache
from delta_tpu.ops.key_cache import KeyCache
from delta_tpu.sql.parser import execute_sql
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

TABLE = {"rows": 2_800, "chunks": 2, "lines_per_order": [1, 7],
         "order_dates": [8035, 2406], "parts": 2_000_000, "suppliers": 100_000}
RF1_ON = "t.l_orderkey = s.l_orderkey"
RF2_ON = "t.l_orderkey = s.o_orderkey"
FORCE = {"delta.tpu.read.deviceResidual.mode": "force"}
OFF = {"delta.tpu.read.deviceResidual.mode": "off"}
with open(os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
        __file__))), "benchmark", "traffic", "power_stream.json")) as _f:
    MIX = json.load(_f)
QUERY = "delta.scan.deviceAggregate"
MASK = "delta.columnCache.keepMask"


@pytest.fixture(autouse=True)
def _fresh_caches_and_mode():
    """No slab and no lane from the test before, and the device-path mode
    `_load` pins put back."""
    KeyCache.reset()
    ColumnCache.reset()
    before = conf.get("delta.tpu.merge.devicePath.mode", "auto")
    yield
    conf.set("delta.tpu.merge.devicePath.mode", before)
    KeyCache.reset()
    ColumnCache.reset()


def _load(tmp_path, seed, file_rows=700):
    """A seeded table in four files of 700 rows with deletion vectors on, and
    what made it: a loaded file's lanes pad to 1,024 rows, a refresh
    function's of 25 orders to 128 or 256."""
    gen = power.Generator(TABLE, seed)
    base = gen.base()
    path = str(tmp_path / "lineitem")
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": file_rows}):
        table = DeltaTable.create(
            path, data=power.to_arrow(base),
            configuration={"delta.tpu.enableDeletionVectors": "true"})
    conf.set("delta.tpu.merge.devicePath.mode", "force")
    return gen, base, table, path


def _send(table, step):
    """One refresh function as its MERGE; (inserted, deleted) as reported."""
    kind, what = step
    if kind == "rf1":
        merge = table.alias("t").merge(power.to_arrow(what), RF1_ON,
                                       source_alias="s")
        m = merge.when_not_matched_insert_all().execute()
    else:
        source = pa.table({power.RF2_KEY: pa.array(what, pa.int64())})
        merge = table.alias("t").merge(source, RF2_ON, source_alias="s")
        m = merge.when_matched_delete().execute()
    return int(m["numTargetRowsInserted"]), int(m["numTargetRowsDeleted"])


def _ask(path, query):
    """One query of the mix as SQL text; Q6's revenue or Q1's table."""
    kind, what = query
    table = f"delta.`{path}`"
    if kind == "q6":
        year, discount, quantity = what
        text = MIX["q6"]["query"].format(
            table=table, date=f"{year}-01-01", discount=discount,
            quantity=quantity)
        return execute_sql(text).column("revenue")[0].as_py()
    return execute_sql(MIX["q1"]["query"].format(table=table, delta=what))


def _queries(rng, n):
    """``n`` queries alternating Q6 and Q1, each with parameters of its own
    from the specification's domains."""
    out = []
    for k in range(n):
        if k % 2 == 0:
            out.append(("q6", (int(rng.choice(MIX["q6"]["years"])),
                               str(rng.choice(MIX["q6"]["discounts"])),
                               int(rng.choice(MIX["q6"]["quantities"])))))
        else:
            out.append(("q1", int(rng.integers(60, 121))))
    return out


def _aggregate_spans():
    return [e for e in telemetry.recent_events(QUERY) if e.op_type == QUERY]


@pytest.mark.parametrize("seed", [1, 2, 2**31 + 38])
def test_three_streams_equal_the_reference(tmp_path, seed):
    """Every statement through the public API, in the power test's order;
    every answer and the final table against `ref_power`."""
    gen, base, table, path = _load(tmp_path, seed)
    rng = np.random.default_rng([seed, 5])
    steps, got, reports = [], [], []
    telemetry.clear_events()
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**FORCE):
        for k in range(3):
            s = gen.refresh_set(base, k, 25)
            steps.append(("rf1", s.rf1))
            reports.append(_send(table, steps[-1]))
            for query in _queries(rng, 6):
                steps.append(query)
                got.append(_ask(path, query))
            steps.append(("rf2", s.rf2))
            reports.append(_send(table, steps[-1]))
    c1 = telemetry.counters()
    want, state, counts = power.ref_power(base, steps)
    asked = [s for s in steps if s[0] in ("q6", "q1")]
    assert len(got) == len(want) == 18
    for (kind, what), g, w in zip(asked, got, want):
        assert power.same_answer(kind, g, w), (kind, what, g, w)
    assert reports == counts
    # every query on the device route, whatever the table had become
    assert c1["scan.aggregate.device"] - c0.get("scan.aggregate.device", 0) == 18
    assert c1.get("scan.aggregate.declined", 0) \
        == c0.get("scan.aggregate.declined", 0)
    DeltaLog.clear_cache()
    back = DeltaTable.for_path(path).to_arrow()
    assert back.num_rows == len(state)
    assert power.diff_rows(back, state) == {
        "rows_missing": 0, "rows_extra": 0, "cells_wrong": 0}
    assert table.delta_log.update().version == 6  # one commit a function
    # the third stream's queries met two vectors' worth of deletes on the
    # first file and lanes of two lengths
    last = _aggregate_spans()[-1].data
    assert last["vectors"] == 1
    assert last["laneShapes"][-1] == 1024 \
        and set(last["laneShapes"][:-1]) <= {128, 256}
    assert last["files"] == 4 + 3


def test_the_reference_is_additive_and_exact(tmp_path):
    """`ref_power` answers from the loaded rows less the deleted plus the
    inserted; the same query over the state's rows whole says the same."""
    gen, base, _table, _path = _load(tmp_path, 4)
    s0, s1 = gen.refresh_set(base, 0, 25), gen.refresh_set(base, 1, 25)
    queries = [("q6", (1994, "0.06", 24)), ("q1", 90), ("q6", (1997, "0.02", 25)),
               ("q1", 60), ("q1", 120)]
    steps = [("rf1", s0.rf1), ("rf2", s0.rf2), ("rf1", s1.rf1), *queries,
             ("rf2", s1.rf2), *queries]
    answers, state, counts = power.ref_power(base, steps)
    assert [c for c in counts] == [(len(s0.rf1), 0), (0, counts[1][1]),
                                   (len(s1.rf1), 0), (0, counts[3][1])]
    assert counts[1][1] > 25 and counts[3][1] > 25
    # the state after the last step, its rows as one table
    whole = power.Rows({n: state.lanes[n] for n in power.QUERY_LANES})
    for (kind, what), got in zip(queries, answers[len(queries):]):
        if kind == "q6":
            year, discount, quantity = what
            assert got == power.ref_q6(whole, year, Decimal(discount), quantity)
        else:
            from benchmark.tables.lineitem_pricing import ref_q1

            assert got.equals(ref_q1(whole, what))
    # before the second RF2 the answers count its orders' lines (Q6 keeps 2%
    # of the rows and may hold none of them; Q1 keeps nearly all)
    assert not answers[1].equals(answers[len(queries) + 1])


CASES = {
    # name: the functions sent before the queries (set 0's, in order)
    "one_shape_no_vector": (),
    "two_shapes_no_vector": ("rf1",),
    "one_shape_a_vector": ("rf2",),
    "two_shapes_a_vector": ("rf1", "rf2"),
}


@pytest.mark.parametrize("query", [("q6", (1994, "0.06", 24)), ("q1", 90),
                                   ("q1", 61)], ids=["q6", "q1", "q1_late"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_aggregate_route_over_vectors_and_lane_shapes(tmp_path, case, query):
    """The device route over files with and without a deletion vector and
    over lanes of one and of two padded lengths: the host route's answer,
    and the reference's."""
    gen, base, table, path = _load(tmp_path, 7)
    s = gen.refresh_set(base, 0, 25)
    steps = [(f, s.rf1 if f == "rf1" else s.rf2) for f in CASES[case]]
    for step in steps:
        _send(table, step)
    telemetry.clear_events()
    with conf.set_temporarily(**FORCE):
        device = _ask(path, query)
    span = _aggregate_spans()[-1].data
    assert span["route"] == "device"
    assert span["vectors"] == ("rf2" in CASES[case])
    small = column_cache._next_pow2(len(s.rf1), floor=64)
    assert small < 1024
    assert span["laneShapes"] == ([small, 1024] if "rf1" in CASES[case]
                                  else [1024])
    with conf.set_temporarily(**OFF):
        host = _ask(path, query)
    want = power.ref_power(base, steps + [query])[0][0]
    assert power.same_answer(query[0], device, want)
    if query[0] == "q6":
        assert host == device
    else:
        assert host.schema.equals(device.schema) and host.equals(device)


def _keep_entries():
    return {k: e for k, e in ColumnCache.instance()._entries.items()
            if k[2] == KEEP}


def _moved(c0, name):
    return telemetry.counters().get(name, 0) - c0.get(name, 0)


def test_a_keep_mask_is_built_once_a_vector_and_reused(tmp_path):
    """The first query after a commit that gave a file a vector builds the
    file's keep mask and uploads it; every launch of every later query finds
    it resident and sends a few bytes; a commit that deletes more rows of the
    file writes another vector, and the next query builds another mask in
    the first one's place."""
    gen, base, table, path = _load(tmp_path, 3)
    sets = [gen.refresh_set(base, k, 25) for k in range(2)]
    _send(table, ("rf2", sets[0].rf2))
    steps = [("rf2", sets[0].rf2)]
    with conf.set_temporarily(**FORCE):
        for query in (("q6", (1995, "0.05", 24)), ("q1", 99)):
            _ask(path, query)  # the lanes, the programs, the first mask
        assert len(_keep_entries()) == 1
        (key, first), = _keep_entries().items()
        assert first.nbytes == 1024 and first.deleted == sum(
            np.isin(base.lanes["l_orderkey"], sets[0].rf2))
        resident = ColumnCache.instance().resident_bytes()
        c0 = dict(telemetry.counters())
        telemetry.clear_events()
        for query in (("q1", 75), ("q6", (1993, "0.09", 25)), ("q1", 118)):
            got = _ask(path, query)
            assert power.same_answer(
                query[0], got, power.ref_power(base, steps + [query])[0][0])
        assert _moved(c0, "columnCache.keep.hits") == 3
        assert _moved(c0, "columnCache.keep.misses") == 0
        assert [e.data["cached"] for e in telemetry.recent_events(MASK)] \
            == [True] * 3
        # no mask went up: the bounds of three queries and an ungrouped carry
        assert _moved(c0, "link.h2d.bytes") < 1024
        assert _keep_entries()[key] is first
        # another vector on the same file
        _send(table, ("rf2", sets[1].rf2))
        steps.append(("rf2", sets[1].rf2))
        c0 = dict(telemetry.counters())
        telemetry.clear_events()
        for query in (("q6", (1996, "0.03", 24)), ("q1", 60)):
            got = _ask(path, query)
            assert power.same_answer(
                query[0], got, power.ref_power(base, steps + [query])[0][0])
        assert _moved(c0, "columnCache.keep.misses") == 1
        assert _moved(c0, "columnCache.keep.hits") == 1
        masks = [e.data for e in telemetry.recent_events(MASK)]
        assert [m["cached"] for m in masks] == [False, True]
        assert masks[0]["deleted"] == masks[1]["deleted"] > first.deleted
        assert masks[0]["rows"] == 700
    (key2, second), = _keep_entries().items()
    assert key2 == key and second is not first
    assert not first.is_resident and second.vector != first.vector
    # the mask is counted in the cache's bytes, once
    assert ColumnCache.instance().resident_bytes() == resident
    # a mask's span lies inside the launch stage
    launch = [e for e in telemetry.recent_events(
        "delta.columnCache.aggregate.launch")][-1]
    mask = telemetry.recent_events(MASK)[-1]
    assert launch.start_us <= mask.start_us \
        <= launch.start_us + launch.duration_us


@pytest.mark.parametrize("how", ["evicted", "epoch"])
def test_a_keep_mask_goes_with_the_files_lanes(tmp_path, how):
    """Evicting the file's lanes frees its keep mask with them; a bump of
    the table's epoch drops it with every lane of the table. The next query
    builds it again and answers the same."""
    gen, base, table, path = _load(tmp_path, 6)
    s = gen.refresh_set(base, 0, 25)
    _send(table, ("rf2", s.rf2))
    query = ("q1", 90)
    cache = ColumnCache.instance()
    with conf.set_temporarily(**FORCE):
        first = _ask(path, query)
        assert len(_keep_entries()) == 1
        before = cache.resident_bytes()
        if how == "evicted":
            # room for everything but the first file's lanes: they are the
            # least recently used once the others are touched again
            (log_path, file_path, _), = _keep_entries()
            for (lp, fp, c), e in list(cache._entries.items()):
                if fp != file_path:
                    cache.get(lp, fp, c)
            mine = sum(e.nbytes for k, e in cache._entries.items()
                       if k[1] == file_path and k[2] != KEEP)
            with conf.set_temporarily(**{
                    "delta.tpu.columnCache.maxBytes": before - mine + 1}):
                cache._evict()
            assert not any(k[1] == file_path for k in cache._entries)
            assert cache.resident_bytes() == before - mine - 1024
        else:
            cache.bump_epoch(table.delta_log.log_path)
            assert cache.resident_bytes() == 0
        assert not _keep_entries()
        c0 = dict(telemetry.counters())
        again = _ask(path, query)
        assert _moved(c0, "columnCache.keep.misses") == 1
    assert again.equals(first)
    assert len(_keep_entries()) == 1
    assert power.same_answer("q1", again, power.ref_power(
        base, [("rf2", s.rf2), query])[0][0])


def test_the_budget_counts_a_files_keep_mask():
    """`_lane_bytes` reckons a byte a padded row more for a file that comes
    with a vector."""
    from delta_tpu.ops import column_aggregate
    from delta_tpu.protocol.actions import AddFile
    from delta_tpu.schema.types import DateType, DecimalType

    fields = {"d": DateType(), "p": DecimalType(15, 2)}

    def add(vector):
        return AddFile(path="f", partition_values={}, size=1, modification_time=0,
                       data_change=True, stats=json.dumps({"numRecords": 900}),
                       deletion_vector=vector)

    plain = column_aggregate._lane_bytes([add(None)], ["d", "p"], fields)
    assert plain == 1024 * (5 + 9)
    masked = column_aggregate._lane_bytes(
        [add({"storageType": "i", "pathOrInlineDv": "x", "sizeInBytes": 1,
              "cardinality": 100})], ["d", "p"], fields)
    assert masked == 1024 * (5 + 9 + 1)
    assert column_cache.KEEP not in fields


def test_bench_spans_tells_a_streams_first_query_from_the_rest(tmp_path, capsys):
    """`tools/bench_spans.py` on two streams' real spans: the queries apart
    from the MERGEs, the first query after each MERGE apart from the rest
    and the grouped apart from the ungrouped, each with the bytes it sent up
    the link and its keep masks."""
    import importlib.util
    import time

    from benchmark.harness.runner import Request, Run

    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_spans.py"))
    bench_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_spans)
    gen, base, table, path = _load(tmp_path, 8)
    queries = [("q6", (1994, "0.06", 24)), ("q1", 90), ("q6", (1995, "0.03", 25)),
               ("q1", 70)]
    requests = []
    with conf.set_temporarily(**FORCE):
        for k in range(3):  # the first is the warm-up: no vector yet
            s = gen.refresh_set(base, k, 25)
            telemetry.clear_events()
            t0 = time.perf_counter()
            _send(table, ("rf1", s.rf1))
            for query in queries:
                _ask(path, query)
            _send(table, ("rf2", s.rf2))
            requests.append(Request(k, t0, time.perf_counter(), True, spans=[
                {"name": e.op_type, "start_us": e.start_us,
                 "duration_us": e.duration_us, "thread": e.thread_id,
                 "data": e.data} for e in telemetry.recent_events()]))
    run = Run(cell=None, seed=0, seconds=1.0, traced=True,
              requests=requests[1:], trace=None, counters={})
    apart = bench_spans.queries_apart(run.done)
    assert set(apart) == {"first ungrouped", "rest grouped", "rest ungrouped"}
    first, grouped, rest = (apart[k] for k in sorted(apart))
    assert [first[0], grouped[0], rest[0]] == [2, 4, 2]
    # the first query of a stream loads RF1's file and builds the mask of
    # the vector the stream before left; the others find both
    assert "delta.columnCache.load" in first[1]
    assert "delta.columnCache.load" not in rest[1]
    assert first[3] == {"False": 2} and rest[3] == {"True": 2}
    assert grouped[3] == {"True": 4}
    assert first[2] > 1024 > rest[2]  # the mask and the lanes went up once
    assert "delta.scan.deviceAggregate.groups" in grouped[1]
    # cells whose requests hold one kind of root print nothing of this
    only_queries = [Request(0, 0.0, 1.0, True, spans=[
        s for s in requests[1].spans if s["name"] != "delta.dml.merge"])]
    assert bench_spans.queries_apart(only_queries) == {}
    bench_spans.report(run)
    assert "the first after a MERGE apart" in capsys.readouterr().err
