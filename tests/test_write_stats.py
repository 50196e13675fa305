"""A written file's statistics come from the footer its encoder just made
(ISSUE 39): `exec/write.write_files` hands `rowgroups.stats_from_footer` the
``FileMetaData`` that `write_parquet_file` returns, and walks the rows again
(`parquet.stats_json`) only where the footer declines. Either way the string
on the `AddFile` is the one `stats_json` gives, byte for byte: the log's bytes
are counted, and a reader of the log cannot tell which path wrote a file.
"""
import datetime as dt
import decimal
import json
import os
import urllib.parse

import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from delta_tpu import DeltaTable
from delta_tpu.exec import parquet as pq_exec
from delta_tpu.exec.write import write_files
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf

NAN, INF = float("nan"), float("inf")
BIG = "日" * 2000  # 6,000 B: over the 4,096 B past which the encoder withholds bounds
D = decimal.Decimal


def _ints(t):
    lo, hi = -(2 ** (t.bit_width - 1)), 2 ** (t.bit_width - 1) - 1
    return pa.array([3, lo, None, 7, hi, 0, -1, 12, 5], t)


# name -> (columns, where the statistics come from, table properties);
# files hold four rows a row group (`_write`), so each has several groups
CASES = {
    "int8": ({"c": _ints(pa.int8())}, "footer", {}),
    "int16": ({"c": _ints(pa.int16())}, "footer", {}),
    "int32": ({"c": _ints(pa.int32())}, "footer", {}),
    "int64": ({"c": _ints(pa.int64())}, "footer", {}),
    "float32": ({"c": pa.array([1.1, 7.3, None, 3.0, 0.1, 2.5, -4.75, 3.0, 9.9],
                               pa.float32())}, "footer", {}),
    "float64": ({"c": pa.array([1.5, 7.0, None, 3.0, 0.25, 2.5, -3.0, 1e300, 5e-324])},
                "footer", {}),
    # a NaN is no bound: the encoder and the decode both step over it
    "float64-nan": ({"c": pa.array([1.5, NAN, None, 7.0, 3.0, 2.5, NAN, -3.0, 9.0])},
                    "footer", {}),
    "float32-nan": ({"c": pa.array([1.5, NAN, None, 7.0, 3.0, 2.5, NAN, -3.0, 9.0],
                                   pa.float32())}, "footer", {}),
    # an infinite bound is left out of the JSON by both
    "float64-inf": ({"c": pa.array([1.5, INF, None, -INF, 3.0, 2.5, 4.0, -3.0, 9.0])},
                    "footer", {}),
    # a row group of NaN alone has no bounds in the footer: the rows answer
    "float64-nan-group": ({"c": pa.array([NAN] * 4 + [1.0, 2.0, 3.0, 4.0, 5.0])},
                          "decode", {}),
    # the encoder writes a zero minimum as -0.0 and a zero maximum as +0.0
    # whatever the rows held: the sign is not the data's, the rows answer
    "float64-zero-min": ({"c": pa.array([0.0, 1.0, 2.0, 3.0, 4.0])}, "decode", {}),
    "float64-negative-zero": ({"c": pa.array([1.5, -0.0, None, 7.0, 3.0, 0.0, 2.5])},
                              "decode", {}),
    "float32-negative-zero-max": ({"c": pa.array([-1.5, -0.0, None, -7.0],
                                                 pa.float32())}, "decode", {}),
    "string": ({"c": pa.array(["", "b", None, "ü", "日本", "a", "zz", "Z", "é"])},
               "footer", {}),
    # the long value is no row group's bound: the footer holds them all
    "string-long-inside": ({"c": pa.array(["", "b", None, "ü", "日本", "a",
                                           "y" * 5000, "Z", "é"])}, "footer", {}),
    "string-long-bound": ({"c": pa.array(["", "b", None, "ü", "a", BIG, "Z"])},
                          "decode", {}),
    "binary": ({"c": pa.array([b"", b"b", None, b"\xff", b"a", b"zz"], pa.binary())},
               "footer", {}),
    "date32": ({"c": pa.array([dt.date(2020, 1, 1), None, dt.date(1969, 12, 31),
                               dt.date(2030, 5, 5), dt.date(1, 1, 1)], pa.date32())},
               "footer", {}),
    # the maximum has microseconds below the millisecond: rounded up
    "timestamp-us": ({"c": pa.array(
        [dt.datetime(2020, 1, 1, 0, 0, 0, 1), None, dt.datetime(1969, 12, 31),
         dt.datetime(2030, 5, 5, 1, 2, 3, 123456), dt.datetime(2000, 1, 1)],
        pa.timestamp("us"))}, "footer", {}),
    "boolean": ({"c": pa.array([True, None, False, True, True, True])}, "footer", {}),
    "boolean-true": ({"c": pa.array([True, None, True, True, True])}, "footer", {}),
    "decimal-7-2": ({"c": pa.array([D("1.23"), None, D("-99999.99"), D("0.00"),
                                    D("99999.99")], pa.decimal128(7, 2))},
                    "footer", {}),
    "decimal-15-2": ({"c": pa.array([D("1.23"), None, D("-9999999999999.99"),
                                     D("7.00"), D("9999999999999.99")],
                                    pa.decimal128(15, 2))}, "footer", {}),
    "all-null": ({"c": pa.array([None] * 9, pa.int32()),
                  "s": pa.array([None] * 9, pa.string())}, "footer", {}),
    "struct": ({"k": pa.array([1, 2, 3], pa.int64()),
                "c": pa.array([{"a": 1}, None, {"a": 3}],
                              pa.struct([("a", pa.int32())]))}, "decode", {}),
    # a struct past the indexed columns is not the footer's to cover
    "struct-not-indexed": ({"k": pa.array([1, 2, 3], pa.int64()),
                            "c": pa.array([{"a": 1}, None, {"a": 3}],
                                          pa.struct([("a", pa.int32())]))},
                           "footer", {"delta.dataSkippingNumIndexedCols": "1"}),
    "mixed-case-names": ({"Id": _ints(pa.int64()),
                          "userName": pa.array(list("abcdefgh") + [None]),
                          "LAST_seen": pa.array([dt.date(2024, 1, i + 1)
                                                 for i in range(9)], pa.date32())},
                         "footer", {}),
}
WIDE = {"a": _ints(pa.int32()), "b": pa.array(list("abcdefgh") + [None]),
        "c": pa.array([1.5, NAN, None, 7.0, 3.0, 2.5, NAN, -3.0, 9.0]),
        "d": pa.array([D("1.23")] * 8 + [None], pa.decimal128(7, 2)),
        "e": _ints(pa.int64())}
for n in (0, 3, -1):
    CASES[f"indexed-cols-{n}"] = (
        WIDE, "footer", {"delta.dataSkippingNumIndexedCols": str(n)})


def _moved(before):
    now = telemetry.counters("write.stats")
    return {k.rsplit(".", 1)[1]: now.get(k, 0) - before.get(k, 0)
            for k in ("write.stats.footer", "write.stats.decoded")}


def _write(path, data, props, **conf_keys):
    """An empty table of the data's schema, then the shared writer over the
    data under a caller's span: the AddFiles, and the writer's spans."""
    table = DeltaTable.create(str(path), data=data.slice(0, 0),
                              configuration=props)
    metadata = table.delta_log.update().metadata
    before = telemetry.counters("write.stats")
    telemetry.clear_events()
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 4,
                                 **conf_keys}):
        with telemetry.record_operation("delta.test.caller") as caller:
            adds = write_files(str(path), data, metadata)
    return adds, metadata, caller, telemetry.recent_events(), _moved(before)


def _file_rows(path, add):
    return pq.read_table(os.path.join(
        str(path), urllib.parse.unquote(add.path).replace("/", os.sep)))


@pytest.mark.parametrize("case", list(CASES))
def test_a_written_file_carries_exactly_the_decoded_statistics(tmp_path, case):
    columns, source, props = CASES[case]
    data = pa.table(columns)
    [add], metadata, caller, events, moved = _write(tmp_path / "t", data, props)
    n = int(props.get("delta.dataSkippingNumIndexedCols", 32))
    # the string `stats_json` gives for the rows handed in, and for the rows
    # the file holds: byte for byte, whichever path made it
    assert add.stats == pq_exec.stats_json(data, n)
    assert add.stats == pq_exec.stats_json(_file_rows(tmp_path / "t", add), n)
    assert json.loads(add.stats)["numRecords"] == data.num_rows
    assert pq.read_metadata(os.path.join(
        str(tmp_path / "t"), add.path)).num_row_groups == -(-data.num_rows // 4)
    [stats] = [e for e in events if e.op_type == "delta.write.stats"]
    assert stats.data == {"columns": data.num_columns, "source": source}
    assert stats.parent_id == caller.span_id
    assert moved == {"footer": int(source == "footer"),
                     "decoded": int(source == "decode")}
    if n == 0:
        assert json.loads(add.stats) == {
            "numRecords": 9, "minValues": {}, "maxValues": {}, "nullCount": {}}
    if n == 3:
        assert list(json.loads(add.stats)["nullCount"]) == ["a", "b", "c"]
    if case == "timestamp-us":
        assert json.loads(add.stats)["maxValues"]["c"] == "2030-05-05T01:02:03.124Z"
    if case == "string-long-bound":
        assert json.loads(add.stats)["maxValues"]["c"] == BIG
    if case.startswith("decimal") or case == "binary":
        assert json.loads(add.stats)["minValues"] == {}  # no bound in JSON


def test_a_partitioned_job_on_the_pool_takes_each_files_own_footer(tmp_path):
    """Six files of three partitions on the writer's pool: each file's
    statistics are its own footer's, one of them (a zero minimum) its own
    rows', and each equals what `stats_json` gives for that file."""
    n = 36
    data = pa.table({
        "region": pa.array([("eu", "us", "ap")[i % 3] for i in range(n)]),
        "Id": pa.array(range(n), pa.int64()),
        "score": pa.array([float(i) for i in range(n)]),  # 0.0 in one file
        "note": pa.array([None if i % 5 == 0 else f"n{i}" for i in range(n)]),
    })
    table = DeltaTable.create(str(tmp_path / "t"), data=data.slice(0, 0),
                              partition_columns=["region"])
    metadata = table.delta_log.update().metadata
    before = telemetry.counters("write.stats")
    telemetry.clear_events()
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 4}):
        with telemetry.record_operation("delta.test.caller") as caller:
            adds = write_files(str(tmp_path / "t"), data, metadata,
                               target_file_rows=6)
    assert len(adds) == 6
    assert sorted(a.partition_values["region"] for a in adds) == [
        "ap", "ap", "eu", "eu", "us", "us"]
    for add in adds:
        rows = _file_rows(tmp_path / "t", add)
        assert rows.column_names == ["Id", "score", "note"]
        assert add.stats == pq_exec.stats_json(rows)
    stats = [e for e in telemetry.recent_events()
             if e.op_type == "delta.write.stats"]
    assert sorted(e.data["source"] for e in stats) == ["decode"] + ["footer"] * 5
    for e in stats:
        assert e.parent_id == caller.span_id
        assert e.thread_name.startswith("delta-parquet-write")
    assert _moved(before) == {"footer": 5, "decoded": 1}


def test_the_encoder_hands_back_the_footer_of_the_file_it_wrote(tmp_path):
    """`write_parquet_file` returns size, mtime and the file's footer (no
    re-read): what `pq.read_metadata` reads off the disk afterwards."""
    data = pa.table({"a": pa.array(range(10), pa.int64()),
                     "s": pa.array([f"v{i}" for i in range(10)])})
    path = str(tmp_path / "d" / "f.parquet")
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 4}):
        size, mtime, footer = pq_exec.write_parquet_file(data, path)
    on_disk = pq.read_metadata(path)
    assert size == os.path.getsize(path) and mtime > 0
    assert (footer.num_rows, footer.num_row_groups) == (10, 3)
    assert footer.to_dict()["row_groups"] == on_disk.to_dict()["row_groups"]
