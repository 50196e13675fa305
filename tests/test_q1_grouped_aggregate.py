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
    # one span for the launches, its two stages inside it (PR 36)
    assert [e.op_type[len("delta.columnCache.aggregate"):]
            for e in telemetry.recent_events("delta.columnCache.aggregate")
            ] == [".launch", ".fetch", ""]
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
    # a row a file of the carry, the files counted up to a power of two (8
    # for these 4) x 8 slots (3 flags x 2 statuses, no NULL among them) x
    # (1 + 6 counts + 5 sums) x 8 B down, the bounds up
    assert moved == 4 * (8 * 8 * 12 * 8 + 16)


@pytest.mark.parametrize("program", ["wide", "tiled"])
def test_the_ungrouped_program_keeps_its_name(program):
    """`agg_roofline` reads `jit_filter_aggregate`, `group_agg_roofline`
    `jit_filter_group_aggregate`: the trace tells the two apart, and both
    formulations of the grouped program are the second."""
    spec = column_aggregate.AggregateSpec("sum", (column_aggregate.Factor("a"),))
    assert column_aggregate._aggregate_kernel((), (spec,)).__name__ \
        == "filter_aggregate"
    term = column_aggregate._Term(spec.factors, ("sum",), 1, 2)
    assert column_aggregate._group_kernel((), (term,), ("k",), 8).__name__ \
        == "filter_group_aggregate"
    assert column_aggregate._group_kernel((), (term,), ("k",), 8, program) \
        .__name__ == "filter_group_aggregate"


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


# -- the two formulations of the grouped program ---------------------------------------


I32 = 2**31 - 1
F, S = column_aggregate.Factor, column_aggregate.AggregateSpec


def _lane(rng, cap, lo, hi, nulls=0.0, dtype=np.int64, ends=()):
    """A lane of ``cap`` values uniform in [lo, hi], the first of them
    ``ends`` (the extremes a rule is tested at), NULL (its slot 0, a key's
    code -1 as the column cache writes them) with probability ``nulls``."""
    values = rng.integers(lo, hi + 1, cap).astype(dtype)
    values[:len(ends)] = ends
    ok = rng.random(cap) >= nulls
    ok[:len(ends)] = True
    return np.where(ok, values, 0).astype(dtype), ok


def _case(name):
    """``(lanes, n, keep, preds, bounds, keys, layout, specs, tile,
    tiled)``: hand-made lanes of one file for a case of the parametrised
    test below, the registers a step of the tile kernel is to take, and
    whether `_fits_tiles` has to say ``tiled``."""
    rng = np.random.default_rng(sum(map(ord, name)))
    cap, n, tile, keep, tiled = 4096, 3_000, 2, None, True
    keys, sizes = ("k1", "k2"), (3, 2)
    null_keys = (0.05, 0.0)
    preds, bounds = ("d",), [(10_000, 10_400)]
    specs = [S("sum", (F("a"),)), S("avg", (F("a"), F("b", -1, 100))),
             S("sum", (F("a"), F("b", -1, 100), F("c", 1, 100))),
             S("count", (F("b"),)), S("count", ())]
    ranges = {"a": (90_000, 10_494_950), "b": (0, 10), "c": (0, 8)}
    nulls = {"a": 0.0, "b": 0.0, "c": 0.0}
    ends = {}
    if name == "slots_16":
        sizes, null_keys = (3, 3), (0.05, 0.05)
    elif name == "slots_32":
        sizes, null_keys = (3, 7), (0.05, 0.05)
    elif name == "the_real_tile":
        cap, n, tile = 65_536, 40_001, 32  # two steps, the second part empty
    elif name == "a_small_file":
        cap, n = 64, 50                     # below one register: padded
    elif name == "a_deletion_vector":
        keep = rng.random(cap) > 0.2
    elif name == "nulls_in_a_key_and_a_factor":
        null_keys, nulls = (0.3, 0.0), {"a": 0.1, "b": 0.2, "c": 0.3}
    elif name == "negative_values":
        ranges = {"a": (-10_494_950, 10_494_950), "b": (-50, 50), "c": (-300, 50)}
    elif name == "a_product_of_int32_max":
        specs = [S("sum", (F("a"), F("c"))), S("sum", (F("a"),))]
        ranges = {"a": (-I32, I32), "c": (-32_767, 32_767)}
        ends = {"a": (I32, I32, -I32, -I32), "c": (32_767, -32_767, 32_767, -32_767)}
    elif name == "a_last_factor_of_32768":
        specs = [S("sum", (F("a"), F("c"))), S("sum", (F("a"),))]
        ranges = {"a": (-I32, I32), "c": (-32_767, 32_768)}
        ends = {"a": (I32, -I32), "c": (32_768, 32_768)}
        tiled = False
    elif name == "a_third_factor_at_the_edge":
        specs = [S("sum", (F("a"), F("b"), F("c", -1, 32_767)))]
        ranges = {"a": (0, 65_535), "b": (0, 32_767), "c": (0, 65_534)}
        ends = {"a": (65_535, 65_535), "b": (32_767, 32_767), "c": (0, 65_534)}
    elif name == "a_second_step_past_int32":
        specs = [S("sum", (F("a"), F("b"), F("c")))]
        ranges = {"a": (0, 10_494_950), "b": (0, 1_000), "c": (0, 8)}
        tiled = False
    elif name == "a_lane_past_int32":
        specs = [S("sum", (F("a"),)), S("count", ())]
        ranges = {"a": (0, 2**40), "b": (0, 1), "c": (0, 1)}
        tiled = False
    elif name == "every_limb_0":
        ranges, sizes, null_keys = {"a": (0, 0), "b": (100, 100), "c": (0, 0)}, (1, 1), (0, 0)
        n, preds, bounds = cap, (), []
    elif name == "every_limb_255":
        specs = [S("sum", (F("a"),)), S("sum", (F("b"),)), S("sum", (F("a"), F("c")))]
        ranges, sizes, null_keys = {"a": (-1, -1), "b": (I32, I32), "c": (1, 1)}, (1, 1), (0, 0)
        n, preds, bounds = cap, (), []
    elif name == "min_and_max":
        specs = [S("min", (F("a"),)), S("max", (F("a"), F("b", -1, 100))),
                 S("sum", (F("a"),)), S("min", (F("a"), F("b", -1, 100), F("c", 1, 100)))]
        nulls = {"a": 0.1, "b": 0.0, "c": 0.1}
    elif name == "bounds_past_int32":
        bounds = [(-2**63, 2**40)]
    elif name == "a_bound_no_row_meets":
        bounds = [(2**31, 2**63 - 1)]
    elif name == "no_predicate":
        preds, bounds = (), []
    else:
        assert name == "slots_8", name
    lanes = {c: _lane(rng, cap, *ranges.get(c, (0, 1)), nulls[c],
                      ends=ends.get(c, ())) for c in ("a", "b", "c")}
    lanes["d"] = _lane(rng, cap, 9_900, 10_500, 0.02, np.int32)
    layout, stride = [], 1
    for c, size, null in zip(keys, sizes, null_keys):
        # a string lane's codes: int32, a NULL's -1; the other key an integer
        values, ok = _lane(rng, cap, 0, size - 1, null,
                           np.int32 if c == "k1" else np.int64)
        lanes[c] = (np.where(ok, values, -1 if c == "k1" else 0), ok)
        layout.append((0, size, stride))
        stride *= size + bool(null)
    return (lanes, n, keep, preds, bounds, keys, tuple(layout), tuple(specs),
            tile, tiled)


def _python_group_by(lanes, n, keep, preds, bounds, keys, layout, terms, slots):
    """The partials of one file in Python integers, a row at a time."""
    where = column_aggregate._term_columns(terms)
    width = 1 + sum(len(c) for c in where)
    out = [[0] * width for _ in range(slots)]
    for row in out:
        for t, at in zip(terms, where):
            if "min" in at:
                row[at["min"]] = column_aggregate._I64_MAX
            if "max" in at:
                row[at["max"]] = column_aggregate._I64_MIN
    cell = {c: (v.tolist(), ok.tolist()) for c, (v, ok) in lanes.items()}
    for r in range(n):
        if keep is not None and not keep[r]:
            continue
        if not all(cell[c][1][r] and lo <= cell[c][0][r] <= hi
                   for c, (lo, hi) in zip(preds, bounds)):
            continue
        g = sum((cell[c][0][r] - lo if cell[c][1][r] else size) * stride
                for c, (lo, size, stride) in zip(keys, layout))
        out[g][0] += 1
        for t, at in zip(terms, where):
            if not all(cell[f.column][1][r] for f in t.factors):
                continue
            out[g][at["count"]] += 1
            x = 1
            for f in t.factors:
                x *= f.offset + f.sign * cell[f.column][0][r]
            if "sum" in at:
                out[g][at["sum"]] += x
            if "min" in at:
                out[g][at["min"]] = min(out[g][at["min"]], x)
            if "max" in at:
                out[g][at["max"]] = max(out[g][at["max"]], x)
    return out


CASES = ["slots_8", "slots_16", "slots_32", "the_real_tile", "a_small_file",
         "a_deletion_vector", "nulls_in_a_key_and_a_factor", "negative_values",
         "a_product_of_int32_max", "a_last_factor_of_32768",
         "a_third_factor_at_the_edge", "a_second_step_past_int32",
         "a_lane_past_int32", "every_limb_0", "every_limb_255", "min_and_max",
         "bounds_past_int32", "a_bound_no_row_meets", "no_predicate"]


@pytest.mark.parametrize("program", ["tiled", "wide"])
@pytest.mark.parametrize("name", CASES)
def test_each_program_is_a_group_by_in_python_integers(name, program, monkeypatch):
    """Both formulations of `filter_group_aggregate` against a plain group-by
    in Python integers on the same lanes: 8, 16 and 32 slots, a row count
    that is no multiple of the tile and below the padded capacity, a deletion
    vector, NULLs in a key and in a factor, negative values, factors at the
    edges of the rule that chooses between the two (`_fits_tiles`), every
    limb at its least and at its largest for a whole tile, bounds beyond
    int32. Where the rule says ``wide`` the tile kernel is not run: it would
    be wrong there, which is what the rule is for."""
    import jax.numpy as jnp

    from delta_tpu.utils.jaxcompat import enable_x64

    (lanes, n, keep, preds, bounds, keys, layout, specs, tile,
     tiled) = _case(name)
    f = column_aggregate._FileLanes(
        None, lanes, n,
        {c: (int(v.min()), int(v.max())) for c, (v, _ok) in lanes.items()}, {})
    terms, _spec_term = column_aggregate._terms(specs, [f])
    assert column_aggregate._fits_tiles(terms, [f], sorted(lanes)) is tiled
    if program == "tiled" and not tiled:
        return
    slots = -(-(layout[-1][2] * (layout[-1][1] + 1)) // 8) * 8
    want = _python_group_by(lanes, n, keep, preds, bounds, keys, layout, terms,
                            min(slots, 32))
    slots = len(want)
    monkeypatch.setattr(column_aggregate, "_TILE_VREGS", tile)
    column_aggregate._group_kernel.cache_clear()
    with enable_x64():
        kernel = column_aggregate._group_kernel(preds, terms, keys, slots, program)
        got = kernel({c: (jnp.asarray(v), jnp.asarray(ok))
                      for c, (v, ok) in lanes.items()},
                     jnp.asarray(np.array(bounds, np.int64).reshape(-1, 2)),
                     jnp.asarray(np.int32(n)),
                     None if keep is None else jnp.asarray(keep),
                     jnp.asarray(np.array(layout, np.int64)),
                     jnp.asarray(np.int32(1)),
                     jnp.zeros((3, slots, len(want[0])), jnp.int64))
        got = np.asarray(got)
    column_aggregate._group_kernel.cache_clear()
    assert got.dtype == np.int64 and not got[0].any() and not got[2].any()
    assert got[1].tolist() == want
    assert sum(row[0] for row in want) > 0 or name == "a_bound_no_row_meets"


def test_the_rule_takes_q1_at_sf10s_extremes_and_the_span_says_so(lineitem_table):
    """Every term of Q1 meets `_fits_tiles` at scale factor 10's extremes
    (the last step of ``sum_charge`` is the one split), and a Q1 through the
    SQL surface says which program answered: `program` on the stage's span
    and on the query's, `scan.aggregate.grouped.tiled` beside `.grouped`."""
    price, less, plus = F("p"), F("d", -1, 100), F("t", 1, 100)
    sf10 = column_aggregate._FileLanes(
        None, {"p": (np.zeros(4_194_304, np.int8),)}, 4_000_000,
        {"p": (90_100, 10_494_950), "d": (0, 10), "t": (0, 8), "q": (100, 5_000),
         "s": (8_036, 10_561), "f": (0, 2), "l": (0, 1)}, {})
    specs = (S("sum", (F("q"),)), S("sum", (price,)), S("sum", (price, less)),
             S("sum", (price, less, plus)), S("avg", (F("d"),)), S("count", ()))
    terms, _ = column_aggregate._terms(specs, [sf10])
    assert column_aggregate._fits_tiles(terms, [sf10], list("pdtqsfl"))
    assert [t.wide_at < len(t.factors) for t in terms] == [
        False, False, False, True, False, False]
    wider = sf10._replace(ranges=dict(sf10.ranges, t=(0, 32_668)))
    terms, _ = column_aggregate._terms(specs, [wider])
    assert not column_aggregate._fits_tiles(terms, [wider], list("pdtqsfl"))

    path, _base = lineitem_table
    telemetry.clear_events()
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**FORCE):
        execute_sql(Q1.format(path=path, delta=90))
    (query,) = _spans()
    (stage,) = [e for e in telemetry.recent_events("delta.columnCache.aggregate")
                if e.op_type == "delta.columnCache.aggregate"]
    assert query["program"] == stage.data["program"] == "tiled"
    c1 = telemetry.counters()
    for name in ("scan.aggregate.grouped", "scan.aggregate.grouped.tiled"):
        assert c1[name] - c0.get(name, 0) == 1


@pytest.mark.parametrize("select_list,program", [
    ("line, sum(price * (1 - disc) * (1 + tax)) as charge, count(*) as n", "tiled"),
    ("line, sum(big) as s, count(*) as n", "wide"),           # a lane past int32
    ("line, sum(price * qty * wide) as s", "wide"),           # int64 at the second step
    ("line, sum(wide * (33000 - wide)) as s, min(big) as lo", "wide"),
    ("line, min(price * (1 + tax)) as lo, sum(qty) as s", "tiled"),
])
def test_a_query_outside_the_rule_is_the_hosts_from_the_wide_program(
        awkward, select_list, program):
    """The same table from whichever formulation the widths select: the rule
    reads the lanes' extremes, and a query it sends to ``wide`` equals the
    host's as one it sends to ``tiled`` does."""
    path, _log = awkward
    c0 = dict(telemetry.counters())
    _both(f"select {select_list} from delta.`{path}` "
          f"where ship <= date '1998-11-20' group by line order by line")
    data = _spans()[0]  # the device route's; the host's follows it
    assert data["program"] == program
    moved = telemetry.counters().get("scan.aggregate.grouped.tiled", 0) \
        - c0.get("scan.aggregate.grouped.tiled", 0)
    assert moved == (program == "tiled")


@pytest.fixture(scope="module")
def one_v5e():
    """A described TPU v5e, not an attached one: its compiler is installed
    wherever the tests run."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("slots,keep,cap,files", [
    (8, False, 4_194_304, 16), (32, True, 4_194_304, 16),
    # PR 38, the power stream: a refresh function's file (60,000 rows pad to
    # 65,536: two grid steps) and a loaded file under a keep mask, a table of
    # 17 to 32 files
    (8, False, 65_536, 32), (8, True, 4_194_304, 32)])
def test_the_tile_kernel_compiles_for_the_chip_at_a_files_shape(
        one_v5e, monkeypatch, slots, keep, cap, files):
    """What the interpreter cannot show: the chip's compiler takes the tile
    kernel as written (an int8 contraction along lanes, 32-bit words recast
    as int8 rows, only 32-bit integers under x64) at 4,194,304 rows and at
    65,536, and the launch is one module with one kernel in it."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from delta_tpu.utils.jaxcompat import enable_x64

    specs = (S("sum", (F("q"),)), S("sum", (F("p"),)),
             S("sum", (F("p"), F("d", -1, 100))),
             S("sum", (F("p"), F("d", -1, 100), F("t", 1, 100))),
             S("avg", (F("d"),)), S("count", ()))
    sf10 = column_aggregate._FileLanes(
        None, {}, 4_000_000, {"p": (90_100, 10_494_950), "d": (0, 10), "t": (0, 8),
                              "q": (100, 5_000)}, {})
    terms, _ = column_aggregate._terms(specs, [sf10])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)

    monkeypatch.setattr(column_aggregate, "_off_chip", lambda: False)
    column_aggregate._group_kernel.cache_clear()
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # a described chip's executable is no use
    try:
        with enable_x64():
            kernel = column_aggregate._group_kernel(("s",), terms, ("f", "l"),
                                                    slots, "tiled")
            lanes = {c: (shape((cap,), jnp.int32 if c in "sfl" else jnp.int64),
                         shape((cap,), jnp.bool_)) for c in "sflqpdt"}
            text = kernel.lower(
                lanes, shape((1, 2), jnp.int64), shape((), jnp.int32),
                shape((cap,), jnp.bool_) if keep else None,
                shape((2, 3), jnp.int64), shape((), jnp.int32),
                shape((files, slots, 12), jnp.int64)).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
        column_aggregate._group_kernel.cache_clear()
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "jit_filter_group_aggregate" in text


def test_a_flips_search_compiles_for_the_chip_at_the_refresh_slabs_shape(one_v5e):
    """`ops/key_cache.py`'s search for a flip's rows (here because one file
    of the suite describes a chip: a second could meet a worker that may not
    load the compiler). At TPC-H SF10's capacity and a refresh function's
    bucket the chip's compiler takes it, its module is the one
    `merge_inverse_ms` reads, and its scratch is the two int64 operands'
    32-bit planes and no re-tiled copy of a capacity-sized plane beside
    them (which a node wider than 128 entries brings: 698 MiB)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from delta_tpu.ops import key_cache
    from delta_tpu.utils.jaxcompat import enable_x64

    cap, flips = 60_817_408, 65_536
    assert key_cache._slab_capacity(59_986_052) == cap
    assert key_cache._search_steps(cap) == 3

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e)

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()  # a described chip's executable is no use
    try:
        with enable_x64():
            compiled = key_cache._inverse_permutation_at().lower(
                shape((cap,), jnp.int64), shape((cap,), jnp.int32),
                shape((cap,), jnp.int64), shape((flips,), jnp.int32)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()
    assert "HloModule jit_inverse_permutation_at" in compiled.as_text()
    memory = compiled.memory_analysis()
    assert memory.output_size_in_bytes == 4 * flips
    assert memory.temp_size_in_bytes < 8 * cap + (32 << 20)


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
        "select_idle_unattributed_pct", "group_agg_roofline", "group_merge_ms",
        # PR 36: the stages inside the launches' span and the query's
        "agg_launch_cover_pct", "agg_launch_ms", "agg_fetch_wait_ms",
        "agg_lanes_ms"}
    assert all(v is not None for v in values.values()), values
    assert values["agg_launch_ms"] > 0 and values["agg_fetch_wait_ms"] > 0
    assert values["agg_launch_cover_pct"] > 50 and values["agg_lanes_ms"] > 0
    assert values["agg_launch_ms"] + values["agg_fetch_wait_ms"] \
        + values["agg_lanes_ms"] + values["scan_plan_ms"] \
        + values["group_merge_ms"] < values["agg_device_ms"]
    assert values["scan_lane_hit_pct"] == 100.0
    assert values["agg_link_B"] == 8 * 8 * 12 * 8 + 16  # 4 files: 8 rows
    assert 0 < values["group_agg_roofline"] and values["group_merge_ms"] > 0
    assert values["agg_device_ms"] > values["group_merge_ms"]
    assert [m.name for m in cell.end_to_end] == ["scan_per_s", "scan_p95_ms",
                                                 "setup_s"]
    # the ungrouped program's roofline finds nothing in a grouped run
    q6 = load_cell("lineitem_sf10.q6")
    roofline = next(m for m in q6.per_layer if m.name == "agg_roofline")
    assert roofline.read(run) is None
