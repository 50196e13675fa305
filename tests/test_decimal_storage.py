"""Decimals are stored as the integers Spark's Parquet writer stores them:
INT32 up to precision 9, INT64 up to 18, ``FIXED_LEN_BYTE_ARRAY`` above
(`exec/parquet.write_parquet_file`). A table may hold files of both kinds —
those written before the change keep their byte arrays — and every read
path gives the same ``decimal128(p, s)`` column and the same answer from
either: the scan (row-group skipping, the device residual mask), MERGE,
OPTIMIZE, the Change Data Feed and the column cache's lanes.
"""
import decimal
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest

from delta_tpu import DeltaTable
from delta_tpu.exec import parquet as pq_exec
from delta_tpu.exec.cdf import CHANGE_TYPE_COL
from delta_tpu.exec.write import write_files
from delta_tpu.ops import column_cache
from delta_tpu.ops.column_cache import ColumnCache
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

D = decimal.Decimal
FORCE = {"delta.tpu.read.deviceResidual.mode": "force"}
OFF = {"delta.tpu.read.deviceResidual.mode": "off"}
SMALL_GROUPS = {"delta.tpu.write.rowGroupRows": 40}

# precision -> the physical type Spark's writer gives it
PHYSICAL = {1: "INT32", 7: "INT32", 9: "INT32", 10: "INT64", 15: "INT64",
            18: "INT64", 19: "FIXED_LEN_BYTE_ARRAY", 38: "FIXED_LEN_BYTE_ARRAY"}


@pytest.fixture(autouse=True)
def _fresh_cache():
    ColumnCache.reset()
    yield
    ColumnCache.reset()


def _extremes(p: int, s: int) -> pa.Array:
    """NULLs, negatives, zero and the largest magnitude ``decimal(p, s)``
    holds, both signs."""
    with decimal.localcontext() as ctx:
        ctx.prec = 40  # 38 digits; Python's default context holds 28
        q = D(1).scaleb(-s)
        top = (D(10) ** p - 1).scaleb(-s)
        vals = [top, -top, D(0), None, q, -q,
                (D(10) ** (p - 1)).scaleb(-s) if p > 1 else q, None, -D(7).scaleb(-s)]
        return pa.array([v.quantize(q) if v is not None else None for v in vals],
                        pa.decimal128(p, s))


def _physical(path: str, column: str) -> str:
    schema = pq.read_metadata(path).schema
    return next(schema.column(j).physical_type for j in range(len(schema))
                if schema.column(j).path == column)


def _data_files(t: DeltaTable):
    snap = t.delta_log.update()
    return [os.path.join(t.delta_log.data_path, f.path) for f in snap.all_files]


def _old_then_new(t: DeltaTable):
    """The table's data files, those with byte-array decimals first."""
    return sorted(_data_files(t),
                  key=lambda p: _physical(p, "amt") != "FIXED_LEN_BYTE_ARRAY")


def _old_writer(monkeypatch):
    """Write through the engine as before the change: the same writer with
    its decimals left as fixed-length byte arrays."""
    real = pq.write_table

    def write_table(*args, **kwargs):
        kwargs["store_decimal_as_integer"] = False
        return real(*args, **kwargs)

    monkeypatch.setattr(pq_exec.pq, "write_table", write_table)
    return lambda: monkeypatch.setattr(pq_exec.pq, "write_table", real)


@pytest.mark.parametrize("precision", list(PHYSICAL))
def test_a_decimal_is_stored_as_the_integer_its_precision_fits(tmp_path, precision):
    scale = precision // 2
    table = pa.table({"d": _extremes(precision, scale)})
    path = str(tmp_path / "f.parquet")
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 4}):
        _, _, footer = pq_exec.write_parquet_file(table, path)
    assert _physical(path, "d") == PHYSICAL[precision]
    # every row group's chunk: 9 rows in groups of 4
    assert footer.num_row_groups == 3
    assert {footer.row_group(i).column(0).physical_type
            for i in range(3)} == {PHYSICAL[precision]}
    back = pq.read_table(path)
    assert back.schema.field("d").type == pa.decimal128(precision, scale)
    assert back.equals(table)


@pytest.mark.parametrize("precision,scale", [(1, 0), (7, 2), (9, 9), (10, 0),
                                             (15, 2), (18, 4), (19, 2), (38, 10)])
def test_a_decimal_round_trips_exactly_through_a_table(tmp_table, precision, scale):
    col = _extremes(precision, scale)
    data = pa.table({"id": pa.array(range(len(col)), pa.int64()), "d": col})
    t = DeltaTable.create(tmp_table, data=data)
    [path] = _data_files(t)
    assert _physical(path, "d") == PHYSICAL[precision]
    got = t.to_arrow().sort_by("id")
    assert got.schema.field("d").type == pa.decimal128(precision, scale)
    assert got.column("d").to_pylist() == col.to_pylist()


@pytest.mark.parametrize("precision", [7, 9, 15, 18])
def test_footer_statistics_of_integer_decimals_are_the_decoded_ones(tmp_table, precision):
    """The footer path of the shared writer (`write.stats.footer`) still
    serves a file whose decimals are integers, and its string is
    `stats_json`'s byte for byte."""
    scale = 2 if precision > 2 else 0
    data = pa.table({"k": pa.array(range(9), pa.int32()),
                     "d": _extremes(precision, scale),
                     "e": _extremes(precision, scale)[::-1]})
    t = DeltaTable.create(tmp_table, data=data)
    meta = t.delta_log.update().metadata
    before = telemetry.counters("write.stats")
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 4}):
        adds = write_files(t.delta_log.data_path, data, meta)
    after = telemetry.counters("write.stats")
    assert after.get("write.stats.footer", 0) - before.get("write.stats.footer", 0) == len(adds)
    assert after.get("write.stats.decoded", 0) == before.get("write.stats.decoded", 0)
    for add in adds:
        path = os.path.join(t.delta_log.data_path, add.path)
        assert _physical(path, "d") == PHYSICAL[precision]
        assert add.stats == pq_exec.stats_json(pq.read_table(path))
        assert json.loads(add.stats)["nullCount"]["d"] == 2


# -- a table holding files of both kinds ------------------------------------


def _rows(lo: int, n: int) -> pa.Table:
    """``n`` rows, ``amt`` rising with ``id`` so row groups cover narrow
    ranges of it, with NULLs and a ``decimal(15,2)`` and a ``decimal(20,3)``
    beside it."""
    ids = np.arange(lo, lo + n, dtype=np.int64)
    amt = [None if i % 17 == 0 else D(int(i) * 37 - 2000).scaleb(-2) for i in ids]
    return pa.table({
        "id": pa.array(ids),
        "amt": pa.array(amt, pa.decimal128(7, 2)),
        "price": pa.array([D(int(i) * 1_000_003 - 10 ** 9).scaleb(-2) for i in ids],
                          pa.decimal128(15, 2)),
        "big": pa.array([D(int(i) * 10 ** 15 + 1).scaleb(-3) for i in ids],
                        pa.decimal128(20, 3)),
    })


@pytest.fixture
def mixed(tmp_table, monkeypatch):
    """A table whose first file stores its decimals as byte arrays (as
    every file written before the change) and whose second as integers."""
    restore = _old_writer(monkeypatch)
    with conf.set_temporarily(**SMALL_GROUPS):
        t = DeltaTable.create(tmp_table, data=_rows(0, 200), configuration={
            "delta.enableChangeDataFeed": "true"})
        restore()
        t.write(_rows(200, 200))
    old, new = _old_then_new(t)
    assert [_physical(old, c) for c in ("amt", "price", "big")] == \
        ["FIXED_LEN_BYTE_ARRAY"] * 3
    assert [_physical(new, c) for c in ("amt", "price", "big")] == \
        ["INT32", "INT64", "FIXED_LEN_BYTE_ARRAY"]
    return t


def _expected(pred) -> pa.Table:
    rows = pa.concat_tables([_rows(0, 200), _rows(200, 200)])
    return rows.filter(pred(rows)) if pred else rows


def _d72(text: str) -> pa.Scalar:
    return pa.scalar(D(text), pa.decimal128(7, 2))


def _in_window(rows):
    return pc.and_(pc.greater_equal(rows["amt"], _d72("10.00")),
                   pc.less(rows["amt"], _d72("95.00")))


@pytest.mark.parametrize("mode", ["off", "force"])
def test_a_mixed_table_scans_the_same_from_either_kind_of_file(mixed, mode):
    telemetry.clear_events()
    with conf.set_temporarily(**(FORCE if mode == "force" else OFF)):
        got = mixed.to_arrow(filters=["amt >= 10.00 and amt < 95.00"]).sort_by("id")
    want = _expected(_in_window)
    assert got.schema == want.schema
    assert got.equals(want)
    # both files hold matching rows; row-group skipping read few of their groups
    events = telemetry.recent_events("delta.scan.decode")
    decodes = {e.span_id: os.path.basename(e.data["file"])
               for e in events if e.op_type == "delta.scan.decode"}
    reads = {decodes[e.parent_id]: e.data for e in events
             if e.op_type == "delta.scan.decode.rowGroups"}
    old, new = (os.path.basename(p) for p in _old_then_new(mixed))
    # the old file decodes its three decimals from byte arrays, the new its
    # one decimal above precision 18 only
    assert reads[old]["flbaDecimals"] == 3 and reads[new]["flbaDecimals"] == 1
    assert all(r["groups"] < 5 for r in reads.values())
    if mode == "force":
        assert telemetry.recent_events("delta.scan.deviceMask")


def test_a_scan_reports_the_byte_array_decimals_it_decoded(mixed):
    telemetry.clear_events()
    got = mixed.to_arrow(filters=["amt >= 10.00"], columns=["id", "amt", "price"])
    assert got.num_rows == _expected(
        lambda r: pc.greater_equal(r["amt"], _d72("10.00"))).num_rows
    flba = sorted(e.data["flbaDecimals"] for e in
                  telemetry.recent_events("delta.scan.decode.rowGroups"))
    assert flba == [0, 2]  # the new file none, the old file amt and price


def test_an_untraced_scan_does_not_count_byte_array_decimals(mixed, monkeypatch):
    """The footer's schema is walked for ``flbaDecimals`` only where the
    span is recorded."""
    from delta_tpu.exec import scan

    def walked(*a, **k):
        raise AssertionError("schema walked for an unrecorded span")

    monkeypatch.setattr(scan, "_byte_array_decimals", walked)
    with conf.set_temporarily(**{"delta.tpu.telemetry.enabled": False}):
        got = mixed.to_arrow(filters=["amt >= 10.00"]).sort_by("id")
    assert got.equals(_expected(
        lambda r: pc.greater_equal(r["amt"], _d72("10.00"))))


def test_a_merge_into_a_mixed_table(mixed):
    src = pa.table({
        "id": pa.array([5, 250, 1000], pa.int64()),
        "amt": pa.array([D("-99999.99"), D("99999.99"), None], pa.decimal128(7, 2)),
        "price": pa.array([D("0.01"), D("-9999999999999.99"), D("1.00")],
                          pa.decimal128(15, 2)),
        "big": pa.array([D("1.000"), None, D("-99999999999999999.999")],
                        pa.decimal128(20, 3)),
    })
    (mixed.alias("t").merge(src, "t.id = s.id", source_alias="s")
     .when_matched_update_all().when_not_matched_insert_all().execute())
    got = mixed.to_arrow().sort_by("id")
    want = {r["id"]: r for r in _expected(None).to_pylist()}
    for r in src.to_pylist():
        want[r["id"]] = r
    assert got.to_pylist() == [want[k] for k in sorted(want)]
    assert got.schema == src.schema


def test_optimize_rewrites_a_mixed_table_as_integer_decimals(mixed):
    want = mixed.to_arrow().sort_by("id")
    mixed.optimize().execute_compaction()
    [path] = _data_files(mixed)
    assert [_physical(path, c) for c in ("amt", "price", "big")] == \
        ["INT32", "INT64", "FIXED_LEN_BYTE_ARRAY"]
    assert mixed.to_arrow().sort_by("id").equals(want)


def test_the_change_feed_of_a_mixed_table(mixed):
    mixed.update({"amt": "amt + 1.00"}, "id % 50 = 3")
    mixed.delete("id = 7 or id = 207")
    changes = mixed.table_changes(1)
    cdc_dir = os.path.join(mixed.delta_log.data_path, "_change_data")
    written = [os.path.join(cdc_dir, f) for f in os.listdir(cdc_dir)]
    assert written and all(_physical(p, "amt") == "INT32" for p in written)
    rows = {(r[CHANGE_TYPE_COL], r["id"]): r for r in changes.to_pylist()}
    base = {r["id"]: r for r in _expected(None).to_pylist()}
    for i in (3, 53, 203, 253):
        pre, post = rows[("update_preimage", i)], rows[("update_postimage", i)]
        assert pre["amt"] == base[i]["amt"] and pre["big"] == base[i]["big"]
        want = None if base[i]["amt"] is None else base[i]["amt"] + D("1.00")
        assert post["amt"] == want
    for i in (7, 207):
        assert rows[("delete", i)]["price"] == base[i]["price"]
    assert changes.schema.field("amt").type == pa.decimal128(7, 2)


def test_column_cache_lanes_are_equal_from_either_kind_of_file(tmp_table, monkeypatch):
    data = _rows(0, 300)
    restore = _old_writer(monkeypatch)
    t = DeltaTable.create(tmp_table, data=data)
    restore()
    t.write(data)
    old, new = _old_then_new(t)
    assert _physical(old, "price") == "FIXED_LEN_BYTE_ARRAY"
    assert _physical(new, "price") == "INT64"
    cols = ["amt", "price"]
    lanes = []
    for path in (old, new):
        n, read, _ = column_cache._read_file(
            t.delta_log.data_path, os.path.basename(path), cols)
        assert n == 300
        lanes.append({c: column_cache._lane_from_arrow(read[c].combine_chunks())
                      for c in cols})
    for c in cols:
        (v0, m0, _), (v1, m1, _) = lanes[0][c], lanes[1][c]
        np.testing.assert_array_equal(v0, v1)
        np.testing.assert_array_equal(m0, m1)
    # the unscaled integers themselves
    np.testing.assert_array_equal(
        lanes[1]["price"][0],
        [int(x.scaleb(2)) for x in data.column("price").to_pylist()])


# -- partitioned tables and CDC files --------------------------------------


def test_a_partitioned_table_stores_its_decimals_as_integers(tmp_table):
    data = _rows(0, 120).append_column(
        "part", pa.array([i % 3 for i in range(120)], pa.int32()))
    t = DeltaTable.create(tmp_table, data=data, partition_columns=["part"])
    paths = _data_files(t)
    assert len(paths) == 3
    for p in paths:
        assert [_physical(p, c) for c in ("amt", "price", "big")] == \
            ["INT32", "INT64", "FIXED_LEN_BYTE_ARRAY"]
    got = t.to_arrow().sort_by("id")
    assert got.select(data.column_names).equals(data)
    got = t.to_arrow(filters=["part = 1 and price < 0"]).sort_by("id")
    assert got.column("id").to_pylist() == [
        r["id"] for r in data.to_pylist() if r["part"] == 1 and r["price"] < 0]


def test_a_cdc_file_stores_its_decimals_as_integers(tmp_table):
    t = DeltaTable.create(tmp_table, data=_rows(0, 50), configuration={
        "delta.enableChangeDataFeed": "true"})
    t.update({"price": "price * 2"}, "id < 5")
    cdc_dir = os.path.join(tmp_table, "_change_data")
    [cdc] = [os.path.join(cdc_dir, f) for f in os.listdir(cdc_dir)]
    assert [_physical(cdc, c) for c in ("amt", "price", "big")] == \
        ["INT32", "INT64", "FIXED_LEN_BYTE_ARRAY"]
    stored = pq.read_table(cdc)
    base = {r["id"]: r for r in _rows(0, 50).to_pylist()}
    for r in stored.to_pylist():
        assert r["amt"] == base[r["id"]]["amt"] and r["big"] == base[r["id"]]["big"]
        factor = 2 if r[CHANGE_TYPE_COL] == "update_postimage" else 1
        assert r["price"] == base[r["id"]]["price"] * factor
    assert sorted(r["id"] for r in stored.to_pylist()) == sorted(list(range(5)) * 2)


def test_bench_spans_prints_the_byte_array_decimals_read(mixed, capsys):
    """`tools/bench_spans.py` says how many byte-array decimals each of the
    window's reads decoded."""
    import importlib.util
    import time

    from benchmark.harness.runner import Request, Run

    spec = importlib.util.spec_from_file_location(
        "bench_spans", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "bench_spans.py"))
    bench_spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench_spans)
    telemetry.clear_events()
    t0 = time.perf_counter()
    with conf.set_temporarily(**SMALL_GROUPS):
        mixed.write(_rows(400, 100))
    mixed.to_arrow(filters=["amt >= 10.00"])
    spans = [{"name": e.op_type, "start_us": e.start_us,
              "duration_us": e.duration_us, "thread": e.thread_id,
              "data": e.data} for e in telemetry.recent_events()]
    run = Run(cell=None, seed=0, seconds=1.0, traced=True, requests=[
        Request(0, t0, time.perf_counter(), True, spans=spans)],
        trace=None, counters={})
    bench_spans.report(run)
    printed = capsys.readouterr().err
    line = next(l for l in printed.splitlines()
                if l.startswith("reads by their byte-array decimals"))
    # three files read: the old one's three byte-array decimals, the new
    # ones' one decimal above precision 18 each
    assert json.loads(line[line.index("{"):]) == {"3": 1, "1": 2}
