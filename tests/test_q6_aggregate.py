"""TPC-H Q6 through the SQL surface, answered by the fused filter-and-sum
kernel over resident lanes (`ops/column_aggregate.py`): the specification's
text parses (typed dates, intervals, exact decimal literal arithmetic), the
device route and the host route return the same Arrow table and both agree
with the benchmark's plain reference (`benchmark/tables/lineitem.py`) over
all 80 parameter triples, NULLs and deletion vectors are honoured, decimal
lanes are exact, and the route declines, saying why, when int64 could
overflow."""
import datetime as dt
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from benchmark.tables import lineitem
from delta_tpu import DeltaLog, DeltaTable
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.exec.scan import scan_to_table
from delta_tpu.expr import ir, jaxeval
from delta_tpu.expr.parser import parse_expression, parse_predicate
from delta_tpu.expr.vectorized import boolean_mask
from delta_tpu.ops import column_aggregate
from delta_tpu.ops.column_cache import ColumnCache, _lane_from_arrow
from delta_tpu.sql.parser import execute_sql
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

OFF = {"delta.tpu.read.deviceResidual.mode": "off"}
FORCE = {"delta.tpu.read.deviceResidual.mode": "force"}
TABLE = {"rows": 20_000, "chunks": 3, "lines_per_order": [1, 7],
         "order_dates": [8035, 2406], "parts": 2_000_000, "suppliers": 100_000}
Q6 = ("select sum(l_extendedprice * l_discount) as revenue from delta.`{path}` "
      "where l_shipdate >= date '{year}-01-01' "
      "and l_shipdate < date '{year}-01-01' + interval '1' year "
      "and l_discount between {discount} - 0.01 and {discount} + 0.01 "
      "and l_quantity < {quantity}")
TRIPLES = [(y, f"0.0{d}", q) for y in range(1993, 1998) for d in range(2, 10)
           for q in (24, 25)]


@pytest.fixture(autouse=True, scope="module")
def _fresh_lanes():
    ColumnCache.reset()
    yield
    ColumnCache.reset()


def _routes():
    """(route, ...) of the aggregate spans recorded since the last clear."""
    return [e.data.get("route")
            for e in telemetry.recent_events("delta.scan.deviceAggregate")
            if e.op_type == "delta.scan.deviceAggregate"]  # not its stages


def _both(sql):
    """The query on the device route and on the host route."""
    telemetry.clear_events()
    with conf.set_temporarily(**FORCE):
        device = execute_sql(sql)
    assert _routes() == ["device"], _routes()
    with conf.set_temporarily(**OFF):
        host = execute_sql(sql)
    assert device.schema.equals(host.schema), (device.schema, host.schema)
    assert device.equals(host), (device.to_pylist(), host.to_pylist())
    return device


@pytest.fixture(scope="module")
def lineitem_table(tmp_path_factory):
    """20,000 seeded rows of the 16 columns in five files."""
    path = str(tmp_path_factory.mktemp("lineitem") / "t")
    base = lineitem.Generator(TABLE, 2**31 + 27).base()
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 4_000}):
        DeltaTable.create(path, data=lineitem.to_arrow(base))
    return path, base


@pytest.mark.parametrize("triple", TRIPLES, ids=lambda t: "-".join(map(str, t)))
def test_q6_is_the_reference_on_both_routes(lineitem_table, triple):
    path, base = lineitem_table
    year, discount, quantity = triple
    got = _both(Q6.format(path=path, year=year, discount=discount,
                          quantity=quantity))
    assert got.schema.field("revenue").type == pa.decimal128(38, 4)
    assert got.column("revenue")[0].as_py() == lineitem.ref_q6(
        base, year, Decimal(discount), quantity)


def test_float_folded_bounds_would_lose_a_discount(lineitem_table):
    """What the comparison of the benchmark's cell has to catch."""
    _path, base = lineitem_table
    exact = lineitem.ref_q6(base, 1994, Decimal("0.06"), 24)
    assert lineitem.ref_q6_float_bounds(base, 1994, Decimal("0.06"), 24) < exact
    assert lineitem.ref_q6_float_bounds(base, 1994, Decimal("0.05"), 24) == \
        lineitem.ref_q6(base, 1994, Decimal("0.05"), 24)


def test_generator_keeps_the_published_shapes():
    base = lineitem.Generator(TABLE, 5).base()
    table = lineitem.to_arrow(base)
    assert table.num_rows == 20_000 and table.schema.equals(lineitem.arrow_schema())
    assert table.schema.names == lineitem.NAMES and len(lineitem.NAMES) == 16
    lanes = base.lanes
    assert lanes["l_quantity"].min() >= 100 and lanes["l_quantity"].max() <= 5000
    assert lanes["l_discount"].max() <= 10 and lanes["l_tax"].max() <= 8
    assert ((lanes["l_shipdate"] - 8035) >= 1).all()
    lengths = pa.compute.utf8_length(table.column("l_comment"))
    assert pa.compute.min(lengths).as_py() >= 10
    assert pa.compute.max(lengths).as_py() <= 43
    again = lineitem.to_arrow(lineitem.Generator(TABLE, 5).base())
    assert again.equals(table)


# -- NULLs and deletion vectors ---------------------------------------------------


def _nullable_table(path, null_column, seed=11, files=3, n=3_000):
    rng = np.random.default_rng(seed)
    log = DeltaLog.for_table(path)

    def dec(values, nulls):
        return pa.array([None if z else Decimal(int(v)).scaleb(-2)
                         for v, z in zip(values, nulls)], pa.decimal128(15, 2))

    for _ in range(files):
        nulls = {c: (rng.random(n) < 0.1) if c == null_column
                 else np.zeros(n, bool)
                 for c in ("l_shipdate", "l_discount", "l_quantity",
                           "l_extendedprice")}
        ship = rng.integers(8036, 10561, n)
        WriteIntoDelta(log, "append", pa.table({
            "id": pa.array(rng.integers(0, 1 << 40, n), pa.int64()),
            "l_shipdate": pa.array(
                [None if z else dt.date(1970, 1, 1) + dt.timedelta(days=int(v))
                 for v, z in zip(ship, nulls["l_shipdate"])], pa.date32()),
            "l_discount": dec(rng.integers(0, 11, n), nulls["l_discount"]),
            "l_quantity": dec(rng.integers(1, 51, n) * 100, nulls["l_quantity"]),
            "l_extendedprice": dec(rng.integers(90_000, 10_494_950, n),
                                   nulls["l_extendedprice"]),
        })).run()
    return log


@pytest.mark.parametrize("null_column", ["l_shipdate", "l_discount",
                                         "l_quantity", "l_extendedprice"])
def test_nulls_in_each_column(tmp_table, null_column):
    _nullable_table(tmp_table, null_column)
    for year, discount, quantity in TRIPLES[::9]:
        _both(Q6.format(path=tmp_table, year=year, discount=discount,
                        quantity=quantity))
    got = _both(f"select count(*) as n, count({null_column}) as c, "
                f"min({null_column}) as lo, max({null_column}) as hi "
                f"from delta.`{tmp_table}` where id >= 0")
    assert got.column("c")[0].as_py() < got.column("n")[0].as_py()


def test_deletion_vector_on_a_file(tmp_table):
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.delete import DeleteCommand

    log = _nullable_table(tmp_table, "l_discount")
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    before = _both(Q6.format(path=tmp_table, year=1994, discount="0.06",
                             quantity=25))
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        DeleteCommand(log, "id % 3 = 0 and l_quantity < 30").run()
    assert any(f.deletion_vector is not None for f in log.update().all_files)
    after = _both(Q6.format(path=tmp_table, year=1994, discount="0.06",
                            quantity=25))
    assert after.column("revenue")[0].as_py() < before.column("revenue")[0].as_py()
    counts = _both(f"select count(*) as n from delta.`{tmp_table}` "
                   f"where l_quantity < 30")
    with conf.set_temporarily(**OFF):
        rows = scan_to_table(log.update(), ["l_quantity < 30"]).num_rows
    assert counts.column("n")[0].as_py() == rows


@pytest.mark.parametrize("select_list", [
    "sum(l_quantity) as s, avg(l_discount) as a",
    "avg(l_extendedprice * l_quantity) as a, max(l_extendedprice * l_discount) as m",
    "min(l_shipdate) as lo, max(l_shipdate) as hi, count(l_shipdate) as c",
    "sum(l_linenumber) as s, avg(l_linenumber) as a, min(l_orderkey) as k",
    "sum(l_linenumber * l_linenumber) as s, count(*) as n",
])
def test_other_aggregates_are_typed_as_the_host_types_them(lineitem_table,
                                                           select_list):
    path, _base = lineitem_table
    _both(f"select {select_list} from delta.`{path}` where l_discount > 0.055 "
          f"and l_shipdate < date '1996-03-01' - interval '2' month")
    empty = _both(f"select {select_list} from delta.`{path}` "
                  f"where l_quantity < 0")
    assert empty.num_rows == 1


# -- the text of the specification parses -----------------------------------------


@pytest.mark.parametrize("text,want", [
    ("date '1994-01-01'", "'1994-01-01'"),
    ("date '1994-01-01' + interval '1' year", "'1995-01-01'"),
    ("DATE '1996-02-29' + INTERVAL '1' YEAR", "'1997-02-28'"),
    ("date '1996-01-31' + interval '1' month", "'1996-02-29'"),
    ("date '1996-03-31' - interval '1' month - interval '2' day", "'1996-02-27'"),
    ("date '1998-12-01' - interval '90' day", "'1998-09-02'"),
    ("0.06 - 0.01", "0.05"),
    ("0.06 + 0.01", "0.07"),
    ("1.10 + 0.05", "1.15"),
    ("-0.5 * 3", "-1.5"),
    ("2 * 0.35 - 1", "-0.30"),
])
def test_literals_fold_exactly_at_parse_time(text, want):
    e = parse_expression(text)
    assert isinstance(e, ir.Literal) and e.sql() == want


def test_a_date_literal_is_typed_and_an_interval_needs_a_date():
    from delta_tpu.schema.types import DateType
    from delta_tpu.utils.errors import DeltaAnalysisError

    assert isinstance(parse_expression("date '1994-01-01'").data_type, DateType)
    assert parse_expression("0.07").exact == Decimal("0.07")
    assert parse_expression("1.5e3").exact is None  # a float, as written
    # `date` and `interval` stay usable as column names
    assert isinstance(parse_expression("date"), ir.Column)
    assert parse_expression("interval > 3").left.name == "interval"
    for bad in ["x + interval '1' day", "date '1994-13-01'",
                "date '1994-01-01' + interval '1.5' year",
                "date '1994-01-01' + interval '1' fortnight"]:
        with pytest.raises(DeltaAnalysisError):
            parse_expression(bad)


def _decimal_table():
    cents = [4, 5, 6, 7, 8, None]
    return pa.table({
        "d": pa.array([None if c is None else Decimal(c).scaleb(-2)
                       for c in cents], pa.decimal128(15, 2)),
        "f": pa.array([None if c is None else c / 100 for c in cents])})


@pytest.mark.parametrize("column", ["d", "f"])
def test_between_with_literal_arithmetic_keeps_both_ends(column):
    """The regression: `0.06 + 0.01` folded in float64 is below 0.07, and
    the rows at 0.07 fell out of a Q6 written as the specification has it."""
    e = parse_predicate(f"{column} between 0.06 - 0.01 and 0.06 + 0.01")
    table = _decimal_table()
    want = [False, True, True, True, False, False]
    assert boolean_mask(e, table).to_pylist() == want
    assert [bool(e.eval(r)) for r in table.to_pylist()] == want
    pre_folded = parse_predicate(f"{column} >= 0.05 and {column} <= 0.07")
    assert boolean_mask(pre_folded, table).to_pylist() == want


def test_dml_conditions_fold_decimal_literals_exactly(tmp_table):
    """UPDATE and DELETE conditions go through the same parser: a price of
    exactly 1.15 is not below `1.10 + 0.05`."""
    cents = [114, 115, 116]
    DeltaTable.create(tmp_table, data=pa.table({
        "id": pa.array([1, 2, 3], pa.int64()),
        "price": pa.array([Decimal(c).scaleb(-2) for c in cents],
                          pa.decimal128(15, 2))}))
    execute_sql(f"update delta.`{tmp_table}` set id = id + 10 "
                f"where price < 1.10 + 0.05")
    execute_sql(f"delete from delta.`{tmp_table}` where price > 1.20 - 0.05")
    rows = DeltaTable.for_path(tmp_table).to_arrow().sort_by("id").to_pylist()
    assert [(r["id"], r["price"]) for r in rows] == [
        (2, Decimal("1.15")), (11, Decimal("1.14"))]


# -- decimal lanes ------------------------------------------------------------------


@pytest.mark.parametrize("precision,scale,values", [
    (15, 2, ["-123.45", "0.00", "104949.50", None, "-0.01"]),
    (18, 0, ["999999999999999999", "-999999999999999999", "0", None]),
    (18, 18, ["0.999999999999999999", "-0.000000000000000001", None]),
    (5, 0, ["-7", "7", None]),
])
def test_decimal_lanes_round_trip(precision, scale, values):
    arr = pa.array([None if v is None else Decimal(v) for v in values],
                   pa.decimal128(precision, scale))
    for piece in (arr, arr.slice(1), pa.chunked_array([arr.slice(0, 2),
                                                       arr.slice(2)])):
        lane, valid, codes = _lane_from_arrow(piece)
        assert codes is None and lane.dtype == np.int64
        back = [Decimal(int(v)).scaleb(-scale) if ok else None
                for v, ok in zip(lane, valid)]
        assert back == piece.to_pylist()
    assert _lane_from_arrow(pa.array([Decimal(7)], pa.decimal128(19, 0))) is None


@pytest.mark.parametrize("literal,units", [
    ("0.07", (7, True)), ("0.070", (7, True)), ("0.075", (7, False)),
    ("-0.075", (-8, False)), ("3", (300, True)), ("-0.001", (-1, False)),
])
def test_literals_scale_exactly_to_the_lane(literal, units):
    assert jaxeval.decimal_literal_units(parse_expression(literal), 2) == units
    assert jaxeval.decimal_literal_units(ir.Literal(0.07), 2) is None  # a float


@pytest.mark.parametrize("predicate", [
    "m >= 0.05 and m <= 0.07", "m > 0.055", "m >= 0.055", "m < 0.055",
    "m <= 0.055", "m = 0.06", "m = 0.055", "m != 0.055", "m != 0.06",
    "not (m = 0.055)", "m in (0.05, 0.065, 0.07)", "m in (0.065)",
    "m is null or m > 0.07", "0.06 < m", "m < -0.011", "m <=> 0.06",
    "m between 0.06 - 0.01 and 0.06 + 0.01 and id > 4096", "m > n",
])
def test_decimal_predicates_mask_on_the_device_as_on_the_host(tmp_table,
                                                              predicate):
    """A `to_arrow(filters=...)` scan with a decimal predicate gets a device
    mask, exact also where the literal has more digits than the scale."""
    rng = np.random.default_rng(3)
    log = DeltaLog.for_table(tmp_table)
    n = 500

    def dec(values):
        return pa.array([None if v % 13 == 0 else Decimal(int(v) - 4).scaleb(-2)
                         for v in values], pa.decimal128(9, 2))

    for _ in range(2):
        WriteIntoDelta(log, "append", pa.table({
            "id": pa.array(rng.integers(0, 1 << 30, n), pa.int64()),
            "m": dec(rng.integers(0, 16, n)),
            "n": dec(rng.integers(0, 16, n))})).run()
    snap = log.update()
    ColumnCache.reset()
    c0 = telemetry.counters().get("scan.device.engaged", 0)
    with conf.set_temporarily(**FORCE):
        device = scan_to_table(snap, [predicate]).sort_by("id")
    assert telemetry.counters().get("scan.device.engaged", 0) == c0 + 1
    with conf.set_temporarily(**OFF):
        host = scan_to_table(snap, [predicate]).sort_by("id")
    assert device.equals(host)
    empty = predicate in ("m = 0.055", "m in (0.065)")
    assert (host.num_rows == 0) if empty else (0 < host.num_rows < 2 * n)


# -- the route is taken from what can be observed --------------------------------------


def _ints_table(path, values, dtype=pa.int64()):
    DeltaTable.create(path, data=pa.table({
        "a": pa.array(values, dtype), "b": pa.array(values, dtype)}))


def _declined(sql, reason):
    telemetry.clear_events()
    before = telemetry.counters().get("scan.aggregate.declined", 0)
    with conf.set_temporarily(**FORCE):
        got = execute_sql(sql)
    assert _routes() == [f"host:{reason}"]
    assert telemetry.counters()["scan.aggregate.declined"] == before + 1
    with conf.set_temporarily(**OFF):
        assert got.equals(execute_sql(sql))
    return got


def test_overflow_bound_declines_to_the_host(tmp_table):
    _ints_table(tmp_table, [2**62, 2**62, -5])
    _declined(f"select sum(a) as s from delta.`{tmp_table}`", "overflow")
    _declined(f"select sum(a * b) as s from delta.`{tmp_table}` where a < 0",
              "overflow")
    # the same table's count and extremes cannot overflow
    got = _both(f"select count(*) as n, max(a) as hi from delta.`{tmp_table}` "
                f"where a > 0")
    assert got.to_pylist() == [{"n": 2, "hi": 2**62}]


def test_a_product_the_host_would_wrap_declines(tmp_path):
    """int32 * int32 is int32 on the host: where it could wrap there, the
    device's int64 answer would differ, so the route declines."""
    path = str(tmp_path / "t")
    _ints_table(path, [70_000, 3], pa.int32())
    _declined(f"select max(a * b) as m from delta.`{path}`", "overflow")
    small = str(tmp_path / "s")
    _ints_table(small, [40_000, 3], pa.int32())
    assert _both(f"select sum(a * b) as s from delta.`{small}` where a > 0"
                 ).to_pylist() == [{"s": 1_600_000_009}]


@pytest.mark.parametrize("sql,reason", [
    ("select sum(a + b) as s from {t}", "shape"),
    ("select count(*) as n from {t}", "shape"),
    ("select sum(a) as s from {t} where a + b > 3", "predicate"),
    ("select sum(a) as s from {t} where a > 1 or b < 0", "predicate"),
    ("select sum(x) as s from {t}", "type"),
    ("select sum(a) as s from {t} where s = 'q'", "predicate"),
])
def test_shapes_outside_the_scope_decline(tmp_table, sql, reason):
    DeltaTable.create(tmp_table, data=pa.table({
        "a": pa.array([1, 2, 3], pa.int64()), "b": pa.array([4, 5, 6], pa.int64()),
        "x": pa.array([0.5, 1.5, 2.5]), "s": pa.array(["q", "r", "q"])}))
    _declined(sql.format(t=f"delta.`{tmp_table}`"), reason)


def test_budget_declines_and_off_switches_the_route_off(lineitem_table):
    path, _base = lineitem_table
    sql = Q6.format(path=path, year=1995, discount="0.04", quantity=24)
    ColumnCache.reset()
    with conf.set_temporarily(**{"delta.tpu.columnCache.maxBytes": 100_000}):
        _declined(sql, "budget")
    telemetry.clear_events()
    with conf.set_temporarily(**OFF):
        execute_sql(sql)
    assert _routes() == ["host:off"]
    _both(sql)  # and with room, the lanes load and the device answers


def test_group_by_reaches_the_device_route(lineitem_table):
    """Until PR 32 a grouped query never reached `device_aggregate`; its own
    tests are `test_q1_grouped_aggregate.py`."""
    path, _base = lineitem_table
    telemetry.clear_events()
    got = execute_sql(f"select l_returnflag, sum(l_quantity) as q "
                      f"from delta.`{path}` group by l_returnflag")
    routes = [e.data.get("route") for e in telemetry.recent_events(
        "delta.scan.deviceAggregate") if e.op_type == "delta.scan.deviceAggregate"]
    assert routes == ["device"] and got.num_rows == 3


def test_one_program_serves_every_literal(lineitem_table):
    """The bounds are operands: after one query of a shape, a new triple
    compiles nothing and moves a hundred bytes over the link."""
    path, _base = lineitem_table
    _both(Q6.format(path=path, year=1993, discount="0.02", quantity=24))
    kernel = column_aggregate._aggregate_kernel.cache_info()
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**FORCE):
        for year, discount, quantity in TRIPLES[5::11]:
            execute_sql(Q6.format(path=path, year=year, discount=discount,
                                  quantity=quantity))
    c1 = telemetry.counters()
    n = len(TRIPLES[5::11])
    assert column_aggregate._aggregate_kernel.cache_info().misses == kernel.misses
    assert c1.get("device.compiles", 0) == c0.get("device.compiles", 0)
    assert c1["scan.aggregate.device"] - c0["scan.aggregate.device"] == n
    assert c1.get("columnCache.misses", 0) == c0.get("columnCache.misses", 0)
    moved = sum(c1[k] - c0.get(k, 0) for k in ("link.h2d.bytes", "link.d2h.bytes"))
    assert moved == n * (48 + 32 + 32)


# -- the benchmark's readers find what they read ------------------------------------------


def test_the_cells_metrics_read_a_run_of_the_engine(lineitem_table):
    """Every per-layer metric `lineitem_sf10.q6` lists finds its span, its
    counter or its kernel on a run of the engine's own spans, laid against a
    device trace made from them (the CPU has no device plane)."""
    from benchmark.harness import engine, trace
    from benchmark.harness.cell import load_cell
    from benchmark.harness.runner import Request, Run
    from benchmark.metrics.bytes_aggregate import aggregate_least_bytes
    import time

    path, _base = lineitem_table
    cell = load_cell("lineitem_sf10.q6")
    sut = engine.EngineTable(path, {"engine_confs": {}})
    least = aggregate_least_bytes([4_000] * 5, cell.traffic["predicate_column_bytes"])
    assert least == 20_000 * 28 + 5 * 8
    with conf.set_temporarily(**FORCE):
        execute_sql(Q6.format(path=path, year=1994, discount="0.06", quantity=24))
        sut.drain_spans()
        c0 = sut.counters()
        t_window = time.perf_counter_ns()
        requests = []
        for i, (year, discount, quantity) in enumerate(TRIPLES[:6]):
            t0 = time.perf_counter()
            execute_sql(Q6.format(path=path, year=year, discount=discount,
                                  quantity=quantity))
            requests.append(Request(i, t0, time.perf_counter(), True, rows=1,
                                    info={"least_bytes": least},
                                    spans=sut.drain_spans()))
    c1 = sut.counters()
    # a device that ran the kernel's module for half of each aggregate stage
    ops, modules = [], []
    for r in requests:
        stage = next(s for s in r.spans if s["name"] == "delta.columnCache.aggregate")
        a = int(stage["start_us"] * 1000) - t_window + 1_000
        b = a + max(int(stage["duration_us"] * 1000) // 2, 1)
        modules.append(trace.Event("jit_filter_aggregate(123)", a, b))
        ops.append(trace.Event("%fusion.12 = pred[4194304]{0} fusion(...)", a, b))
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
        "agg_device_ms", "agg_roofline", "agg_link_B", "select_span_cover_pct",
        "select_idle_unattributed_pct",
        # PR 36: the stages inside the launches' span and the query's
        "agg_launch_cover_pct", "agg_launch_ms", "agg_fetch_wait_ms",
        "agg_lanes_ms"}
    assert all(v is not None for v in values.values()), values
    assert values["agg_launch_ms"] > 0 and values["agg_fetch_wait_ms"] > 0
    assert values["agg_launch_cover_pct"] > 50 and values["agg_lanes_ms"] > 0
    assert values["agg_launch_ms"] + values["agg_fetch_wait_ms"] \
        + values["agg_lanes_ms"] + values["scan_plan_ms"] < values["agg_device_ms"]
    assert values["scan_lane_hit_pct"] == 100.0
    assert values["agg_link_B"] == 48 + 32 + 32
    assert 0 < values["agg_roofline"] and values["agg_device_ms"] > 0
    assert values["select_span_cover_pct"] > 50
    assert [m.name for m in cell.end_to_end] == ["scan_per_s", "scan_p95_ms",
                                                 "setup_s"]
