"""TPC-H Q1 through the SQL surface, answered by the grouped filter-and-sum
kernel over resident lanes (`ops/column_aggregate.py`): the device route and
the host route return the same Arrow table, schema included, and both agree
with the benchmark's plain reference (`benchmark/tables/lineitem_pricing.py`);
files whose dictionaries number the same values differently merge by value;
NULL keys, NULLs in aggregated lanes, deletion vectors, absent groups and
empty results are the host's; each decline says why and the host answers the
same; a fresh ``DELTA`` compiles nothing."""
import datetime as dt
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from benchmark.tables import lineitem_pricing as pricing
from delta_tpu import DeltaLog, DeltaTable
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.ops import column_aggregate
from delta_tpu.ops.column_cache import ColumnCache
from delta_tpu.sql.parser import execute_sql
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

OFF = {"delta.tpu.read.deviceResidual.mode": "off"}
FORCE = {"delta.tpu.read.deviceResidual.mode": "force"}
TABLE = {"rows": 6_000, "chunks": 2, "lines_per_order": [1, 7],
         "order_dates": [8035, 2406], "parts": 2_000_000, "suppliers": 100_000}
Q1 = ("select l_returnflag, l_linestatus, sum(l_quantity) as sum_qty, "
      "sum(l_extendedprice) as sum_base_price, "
      "sum(l_extendedprice * (1 - l_discount)) as sum_disc_price, "
      "sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) as sum_charge, "
      "avg(l_quantity) as avg_qty, avg(l_extendedprice) as avg_price, "
      "avg(l_discount) as avg_disc, count(*) as count_order "
      "from delta.`{path}` "
      "where l_shipdate <= date '1998-12-01' - interval '{delta}' day "
      "group by l_returnflag, l_linestatus order by l_returnflag, l_linestatus")
SPAN = "delta.scan.deviceAggregate"


@pytest.fixture(autouse=True, scope="module")
def _fresh_lanes():
    ColumnCache.reset()
    yield
    ColumnCache.reset()


def _spans():
    return [e.data for e in telemetry.recent_events(SPAN) if e.op_type == SPAN]


def _both(sql, keys=()):
    """The query on the device route and on the host route: equal as Arrow
    tables, schema included (sorted by ``keys`` first where the text asks
    for no order: a grouped answer has none of its own)."""
    telemetry.clear_events()
    with conf.set_temporarily(**FORCE):
        device = execute_sql(sql)
    routes = [d.get("route") for d in _spans()]
    assert routes == ["device"], routes
    with conf.set_temporarily(**OFF):
        host = execute_sql(sql)
    if keys:
        order = [(k, "ascending") for k in keys]
        device, host = device.sort_by(order), host.sort_by(order)
    assert device.schema.equals(host.schema), (device.schema, host.schema)
    assert device.equals(host), (device.to_pylist(), host.to_pylist())
    return device


def _declined(sql, reason, keys=()):
    """The device route declines, saying why, and the host's answer comes."""
    telemetry.clear_events()
    got = execute_sql(sql)
    routes = [d.get("route") for d in _spans()]
    assert routes == [f"host:{reason}"], routes
    with conf.set_temporarily(**OFF):
        host = execute_sql(sql)
    if keys:
        order = [(k, "ascending") for k in keys]
        got, host = got.sort_by(order), host.sort_by(order)
    assert got.equals(host)
    return got


# -- Q1 itself -----------------------------------------------------------------------


@pytest.fixture(scope="module")
def lineitem_table(tmp_path_factory):
    """6,000 seeded rows of the 16 columns in four files."""
    path = str(tmp_path_factory.mktemp("lineitem_q1") / "t")
    base = pricing.Generator(TABLE, 2**31 + 32).base()
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 1_500}):
        DeltaTable.create(path, data=pricing.to_arrow(base))
    return path, base


@pytest.mark.parametrize("delta", [60, 61, 75, 90, 104, 119, 120, 1200, 2000])
def test_q1_is_the_reference_on_both_routes(lineitem_table, delta):
    path, base = lineitem_table
    got = _both(Q1.format(path=path, delta=delta))
    want = pricing.ref_q1(base, delta)
    assert got.schema.equals(want.schema), (got.schema, want.schema)
    assert got.equals(want), (got.to_pylist(), want.to_pylist())
    assert [t for _n, t in pricing.COLUMNS] == got.schema.types


def test_q1_span_says_groups_and_columns(lineitem_table):
    path, _base = lineitem_table
    telemetry.clear_events()
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**FORCE):
        got = execute_sql(Q1.format(path=path, delta=90))
    (data,) = _spans()
    assert data["route"] == "device" and data["groups"] == got.num_rows == 4
    assert data["groupColumns"] == ["l_returnflag", "l_linestatus"]
    assert data["files"] == 4 and data["rows"] == 6_000
    (merge,) = telemetry.recent_events(SPAN + ".groups")
    assert merge.data["groups"] == 4
    stage = telemetry.recent_events("delta.columnCache.aggregate")
    assert len(stage) == 1
    c1 = telemetry.counters()
    for name in ("scan.aggregate.device", "scan.aggregate.grouped"):
        assert c1[name] - c0.get(name, 0) == 1
    assert c1.get("scan.aggregate.declined", 0) == c0.get(
        "scan.aggregate.declined", 0)


def test_the_reference_prepared_and_direct_agree(lineitem_table):
    """`ref_q1` adds the rows up once for the parameter's 61 values; the pass
    a value that it makes outside them gives the same."""
    _path, base = lineitem_table
    for delta in (60, 88, 120):
        direct = pricing._table(pricing._sums_upto(
            base, pricing.LAST_SHIPDATE - delta, pricing.exact))
        assert pricing.ref_q1(base, delta).equals(direct)
    assert pricing.ref_q1_float_sums(base, 90).equals(pricing.ref_q1(base, 90))


def test_a_fresh_delta_compiles_nothing(lineitem_table):
    """The cutoff is an operand: after one query of the shape, another value
    of ``DELTA`` compiles nothing, loads nothing and moves the partials and
    16 bytes of bounds over the link."""
    path, _base = lineitem_table
    _both(Q1.format(path=path, delta=90))
    kernel = column_aggregate._group_kernel.cache_info()
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**FORCE):
        for delta in (63, 77, 101, 118):
            execute_sql(Q1.format(path=path, delta=delta))
    c1 = telemetry.counters()
    assert column_aggregate._group_kernel.cache_info().misses == kernel.misses
    assert c1.get("device.compiles", 0) == c0.get("device.compiles", 0)
    assert c1["scan.aggregate.grouped"] - c0["scan.aggregate.grouped"] == 4
    assert c1.get("columnCache.misses", 0) == c0.get("columnCache.misses", 0)
    moved = sum(c1[k] - c0.get(k, 0) for k in ("link.h2d.bytes", "link.d2h.bytes"))
    # 4 files x 8 slots (3 flags x 2 statuses, no NULL among them) x
    # (1 + 6 counts + 5 sums) x 8 B down, the bounds up
    assert moved == 4 * (4 * 8 * 12 * 8 + 16)


def test_the_ungrouped_program_keeps_its_name():
    """`agg_roofline` reads `jit_filter_aggregate`, `group_agg_roofline`
    `jit_filter_group_aggregate`: the trace tells the two apart."""
    spec = column_aggregate.AggregateSpec("sum", (column_aggregate.Factor("a"),))
    assert column_aggregate._aggregate_kernel((), (spec,)).__name__ \
        == "filter_aggregate"
    term = column_aggregate._Term(spec.factors, ("sum",), 1, 2)
    assert column_aggregate._group_kernel((), (term,), ("k",), 8).__name__ \
        == "filter_group_aggregate"


# -- a table made to be awkward ---------------------------------------------------------


FLAGS = [["A", "R", "N", "A"], ["R", "N", "A", "R"], ["N", "A", "N", "N"],
         ["R", "R", "R", "R"]]


def _awkward_table(path, seed=5, n=700):
    """Four files. Each starts with another flag, so the files' dictionaries
    number the flags differently; the last holds one flag alone (two groups
    are absent from it); flags and quantities are NULL in places."""
    rng = np.random.default_rng(seed)
    log = DeltaLog.for_table(path)

    def dec(values, nulls=None):
        nulls = np.zeros(len(values), bool) if nulls is None else nulls
        return pa.array([None if z else Decimal(int(v)).scaleb(-2)
                         for v, z in zip(values, nulls)], pa.decimal128(15, 2))

    for firsts in FLAGS:
        flag = np.array(firsts + list(rng.choice(sorted(set(firsts)), n - 4)))
        flag_null = rng.random(n) < 0.05
        flag_null[:4] = False
        ship = rng.integers(10_400, 10_561, n)
        WriteIntoDelta(log, "append", pa.table({
            "id": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
            "flag": pa.array([None if z else v for v, z in zip(flag, flag_null)],
                             pa.string()),
            "status": pa.array(rng.choice(["O", "F"], n), pa.string()),
            "line": pa.array(rng.integers(1, 8, n), pa.int32()),
            "ship": pa.array([dt.date(1970, 1, 1) + dt.timedelta(days=int(v))
                              for v in ship], pa.date32()),
            "due": pa.array([dt.date(1998, 9, 1) + dt.timedelta(days=int(v) % 3)
                             for v in ship], pa.date32()),
            "qty": dec(rng.integers(1, 51, n) * 100, rng.random(n) < 0.1),
            "price": dec(rng.integers(90_000, 10_494_950, n)),
            "disc": dec(rng.integers(0, 11, n)),
            "tax": dec(rng.integers(0, 9, n)),
            "big": pa.array(rng.integers(1 << 40, 1 << 41, n), pa.int64()),
            "wide": pa.array(rng.integers(0, 1000, n), pa.int32()),
        })).run()
    return log


@pytest.fixture(scope="module")
def awkward(tmp_path_factory):
    path = str(tmp_path_factory.mktemp("awkward") / "t")
    return path, _awkward_table(path)


def test_dictionaries_that_disagree_merge_by_value(awkward):
    path, log = awkward
    got = _both(f"select flag, status, sum(price * (1 - disc)) as s, count(*) as n "
                f"from delta.`{path}` where ship <= date '1998-12-01' "
                f"group by flag, status order by flag, status")
    assert got.num_rows == 8  # three flags and NULL, two statuses
    cache = ColumnCache.instance()
    codes = [cache.get(log.log_path, f.path, "flag").dict_codes
             for f in log.update().all_files]
    assert len({c["R"] for c in codes if "R" in c}) > 1, codes
    assert len({tuple(c) for c in codes}) == len(codes)


def test_a_merge_by_code_would_be_wrong(awkward, monkeypatch):
    """What the test above guards: with each file's codes read through the
    first file's dictionary, the same query is not the host's."""
    path, _log = awkward
    real = column_aggregate._merge_groups

    def by_code(partials, per_file, *rest):
        same = [f._replace(codes=per_file[0].codes) for f in per_file]
        return real(partials, same, *rest)

    monkeypatch.setattr(column_aggregate, "_merge_groups", by_code)
    sql = (f"select flag, count(*) as n from delta.`{path}` "
           f"where ship <= date '1998-12-01' group by flag order by flag")
    with conf.set_temporarily(**FORCE):
        try:
            wrong = execute_sql(sql).to_pylist()
        except KeyError:  # a code the first file's dictionary does not hold
            wrong = None
    with conf.set_temporarily(**OFF):
        assert wrong != execute_sql(sql).to_pylist()


@pytest.mark.parametrize("select_list,keys", [
    ("flag, count(*) as n, count(qty) as c, sum(qty) as s, avg(qty) as a", ["flag"]),
    ("flag, status, min(price) as lo, max(price * (1 + tax)) as hi", ["flag", "status"]),
    ("status, min(ship) as first, max(ship) as last, count(ship) as c", ["status"]),
    ("line, sum(price * (1 - disc) * (1 + tax)) as charge, count(*) as n", ["line"]),
    ("due, line, sum(wide) as s, avg(wide) as a, max(wide * wide) as m", ["due", "line"]),
    ("status, sum(price * (disc + 1)) as up, avg(price * (1 - disc)) as a", ["status"]),
    ("flag, sum(wide * (1 - wide)) as s, min(wide - 7) as lo", ["flag"]),
    ("sum(qty) as s, count(*) as n", ["status"]),
    ("sum(price * (1 - disc) * (1 + tax)) as charge", ["flag"]),
])
def test_grouped_aggregates_are_the_hosts(awkward, select_list, keys):
    """NULL keys a group of their own, NULL quantities not counted, integer
    and date keys, extremes, literal plus or minus a lane, a key left out of
    the select list; with and without rows."""
    path, _log = awkward
    by = ", ".join(keys)
    shown = [k for k in keys if k in select_list.split(", ")]
    # a key the select list leaves out orders the rows and drops out
    order = "" if shown else f" order by {by}"
    got = _both(f"select {select_list} from delta.`{path}` "
                f"where ship <= date '1998-11-20' group by {by}{order}", shown)
    assert got.num_rows > 1
    if "count(qty)" in select_list:
        assert got.column("flag").null_count == 1
        assert sum(got.column("c").to_pylist()) < sum(got.column("n").to_pylist())
    empty = _both(f"select {select_list} from delta.`{path}` "
                  f"where ship <= date '1990-01-01' group by {by}")
    assert empty.num_rows == 0


@pytest.mark.parametrize("order", ["flag desc, status", "status desc, flag desc"])
def test_order_by_sorts_the_groups_and_a_hidden_key_drops_out(awkward, order):
    path, _log = awkward
    got = _both(f"select status, sum(qty) as s from delta.`{path}` "
                f"where wide < 900 group by flag, status order by {order}")
    assert got.column_names == ["status", "s"] and got.num_rows == 8


def test_a_group_absent_from_a_file(awkward):
    path, log = awkward
    got = _both(f"select flag, count(*) as n from delta.`{path}` "
                f"where line <= 7 group by flag order by flag")
    cache = ColumnCache.instance()
    codes = [sorted(cache.get(log.log_path, f.path, "flag").dict_codes)
             for f in log.update().all_files]
    assert ["R"] in codes and ["A", "N", "R"] in codes  # a file knows one flag
    assert got.column("flag").to_pylist() == ["A", "N", "R", None]


def test_deletion_vector_on_a_file(tmp_table):
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.delete import DeleteCommand

    log = _awkward_table(tmp_table, seed=8)
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    sql = (f"select flag, status, sum(price * (1 - disc)) as s, count(*) as n "
           f"from delta.`{tmp_table}` where line < 7 "
           f"group by flag, status order by flag, status")
    before = _both(sql)
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        DeleteCommand(log, "id % 3 = 0 and wide < 500").run()
    assert any(f.deletion_vector is not None for f in log.update().all_files)
    after = _both(sql)
    assert sum(after.column("n").to_pylist()) < sum(before.column("n").to_pylist())


# -- declines ------------------------------------------------------------------------


@pytest.mark.parametrize("select_list,by,reason", [
    ("wide, count(*) as n", "wide", "groups"),            # 1000 values a file
    ("line, due, status, count(*) as n", "line, due, status", "groups"),
    ("flag, sum(big * big) as s", "flag", "overflow"),    # 2^82 a row
    ("flag, sum(big * (1 + big)) as s", "flag", "overflow"),
    ("flag, sum(price / 2) as s", "flag", "shape"),       # a division
    ("flag, sum(price * disc * tax * qty) as s", "flag", "shape"),
    ("flag, sum(price * (disc + tax)) as s", "flag", "shape"),
    ("flag, avg(price * (1 - disc) * (1 + tax)) as a", "flag", "type"),
    ("flag, max(price * (1.0 - disc)) as s", "flag", "type"),  # a float
    ("id, count(*) as n", "id", "groups"),
])
def test_each_decline_says_why_and_the_host_answers(awkward, select_list, by,
                                                    reason):
    path, _log = awkward
    keys = [k.strip() for k in by.split(",")]
    _declined(f"select {select_list} from delta.`{path}` "
              f"where ship <= date '1998-12-01' and line < 4 group by {by}",
              reason, keys)


def test_a_string_predicate_declines(awkward):
    path, _log = awkward
    _declined(f"select flag, count(*) as n from delta.`{path}` "
              f"where status = 'O' group by flag", "predicate", ["flag"])


def test_budget_declines_a_grouped_query(awkward):
    path, _log = awkward
    ColumnCache.reset()
    with conf.set_temporarily(**{"delta.tpu.columnCache.maxBytes": 10_000}):
        _declined(f"select flag, sum(qty) as s from delta.`{path}` "
                  f"where line < 4 group by flag", "budget", ["flag"])


def test_group_slots_are_one_int8_tile():
    assert column_aggregate.GROUP_SLOTS == 32


def test_terms_take_their_widths_from_the_lanes_extremes():
    """At SF10's extremes `l_extendedprice * (100 - l_discount)` stays in
    int32 and the tax's factor takes it to int64 and five bytes."""
    F, S = column_aggregate.Factor, column_aggregate.AggregateSpec
    price, less, plus = F("p"), F("d", -1, 100), F("t", 1, 100)
    lanes = column_aggregate._FileLanes(
        None, {}, 4_000_000, {"p": (90_100, 10_494_950), "d": (0, 10),
                              "t": (0, 8)}, {})
    specs = (S("sum", (price, less)), S("avg", (price, less)),
             S("sum", (price, less, plus)), S("count", ()))
    terms, spec_term = column_aggregate._terms(specs, [lanes])
    assert spec_term == [0, 0, 1, 2]
    assert [(t.want, t.wide_at, t.nbytes) for t in terms] == [
        (("sum",), 2, 4), (("sum",), 2, 5), ((), 0, 1)]
    assert column_aggregate._magnitude(lanes.ranges, (price, less, plus)) == [
        10_494_950, 1_049_495_000, 113_345_460_000]


# -- the benchmark's readers find what they read ------------------------------------------


def test_the_cells_metrics_read_a_run_of_the_engine(lineitem_table):
    """Every per-layer metric `lineitem_sf10_pricing.q1` lists finds its span,
    its counter or its kernel on a run of the engine's own spans, laid against
    a device trace made from them (the CPU has no device plane); the two
    aggregate programs' rooflines read their own module and not the other's."""
    import time

    from benchmark.harness import engine, trace
    from benchmark.harness.cell import load_cell
    from benchmark.harness.runner import Request, Run
    from benchmark.metrics.bytes_group_aggregate import group_aggregate_least_bytes

    path, _base = lineitem_table
    cell = load_cell("lineitem_sf10_pricing.q1")
    sut = engine.EngineTable(path, {"engine_confs": {}})
    p = cell.traffic
    least = group_aggregate_least_bytes([1_500] * 4, p["column_bytes"],
                                        p["aggregates"], p["groups"])
    assert least == 6_000 * 38 + 8 * 8 * 4 * 4
    with conf.set_temporarily(**FORCE):
        execute_sql(p["query"].format(table=f"delta.`{path}`", delta=90))
        sut.drain_spans()
        c0 = sut.counters()
        t_window = time.perf_counter_ns()
        requests = []
        for i, delta in enumerate((60, 75, 90, 120)):
            t0 = time.perf_counter()
            execute_sql(p["query"].format(table=f"delta.`{path}`", delta=delta))
            requests.append(Request(i, t0, time.perf_counter(), True, rows=4,
                                    info={"least_bytes": least},
                                    spans=sut.drain_spans()))
    c1 = sut.counters()
    ops, modules = [], []
    for r in requests:
        stage = next(s for s in r.spans if s["name"] == "delta.columnCache.aggregate")
        a = int(stage["start_us"] * 1000) - t_window + 1_000
        b = a + max(int(stage["duration_us"] * 1000) // 2, 1)
        modules.append(trace.Event("jit_filter_group_aggregate(123)", a, b))
        ops.append(trace.Event("%fusion.15 = s8[6,4194304]{1,0} fusion(...)", a, b))
    end = int(requests[-1].end * 1e9) - t_window + 2_000
    run = Run(cell, 0, 1.0, True, requests=requests,
              counters={k: v - c0.get(k, 0) for k, v in c1.items()
                        if v != c0.get(k, 0)},
              trace=trace.Trace((0, end), {0: trace.DeviceTrace(ops, modules)}),
              window_perf_ns=t_window, device_kind="TPU v5 lite")
    run.window_start, run.window_end = requests[0].start, requests[-1].end
    values = {m.name: m.read(run) for m in cell.per_layer}
    assert set(values) == {
        "scan_plan_ms", "scan_lane_hit_pct", "device_idle_pct.scan",
        "agg_device_ms", "agg_link_B", "select_span_cover_pct",
        "select_idle_unattributed_pct", "group_agg_roofline", "group_merge_ms"}
    assert all(v is not None for v in values.values()), values
    assert values["scan_lane_hit_pct"] == 100.0
    assert values["agg_link_B"] == 4 * 8 * 12 * 8 + 16
    assert 0 < values["group_agg_roofline"] and values["group_merge_ms"] > 0
    assert values["agg_device_ms"] > values["group_merge_ms"]
    assert [m.name for m in cell.end_to_end] == ["scan_per_s", "scan_p95_ms",
                                                 "setup_s"]
    # the ungrouped program's roofline finds nothing in a grouped run
    q6 = load_cell("lineitem_sf10.q6")
    roofline = next(m for m in q6.per_layer if m.name == "agg_roofline")
    assert roofline.read(run) is None
