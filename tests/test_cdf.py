"""Change Data Feed: write-side capture + read-side reconstruction.

The reference carries the ``cdc`` action but blocks writing it
(``actions/actions.scala:151-156``); this engine implements the feature the
modern-Delta way. Covers: insert/delete/update/merge capture, preimage/
postimage pairs, reconstruction of append and full-file-delete commits
without CDC files, deletion-vector diff reconstruction, version ranges, and
the protocol gate (CDF needs writer v4).
"""
import pyarrow as pa
import pytest

from delta_tpu.api.tables import DeltaTable
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.exec.cdf import (
    CHANGE_TYPE_COL,
    COMMIT_TIMESTAMP_COL,
    COMMIT_VERSION_COL,
)
from delta_tpu.protocol.actions import AddCDCFile
from delta_tpu.utils.errors import DeltaAnalysisError, DeltaUnsupportedOperationError

CDF_PROPS = {"delta.enableChangeDataFeed": "true"}


def make_table(path, n=10, cdf=True, extra_props=None):
    props = dict(CDF_PROPS) if cdf else {}
    props.update(extra_props or {})
    data = pa.table({
        "id": pa.array(range(n), pa.int64()),
        "value": pa.array([f"v{i}" for i in range(n)]),
    })
    return DeltaTable.create(path, data=data, configuration=props or None)


def changes(t, start, end=None):
    got = t.table_changes(start, end)
    return sorted(
        got.to_pylist(),
        key=lambda r: (r[COMMIT_VERSION_COL], r[CHANGE_TYPE_COL], r.get("id") or 0),
    )


def by_type(rows):
    out = {}
    for r in rows:
        out.setdefault(r[CHANGE_TYPE_COL], []).append(r)
    return out


# -- basic capture ------------------------------------------------------------


def test_create_reconstructs_inserts(tmp_table):
    t = make_table(tmp_table, n=3)
    rows = changes(t, 0)
    assert len(rows) == 3
    assert all(r[CHANGE_TYPE_COL] == "insert" for r in rows)
    assert all(r[COMMIT_VERSION_COL] == 0 for r in rows)


def test_delete_captures_deleted_rows(tmp_table):
    t = make_table(tmp_table)
    t.delete("id < 3")
    rows = changes(t, 1)
    assert [r["id"] for r in rows] == [0, 1, 2]
    assert all(r[CHANGE_TYPE_COL] == "delete" for r in rows)
    # the commit carries an AddCDCFile action
    _, acts = next(iter(t.delta_log.get_changes(1)))
    assert any(isinstance(a, AddCDCFile) for a in acts)


def test_update_captures_pre_and_postimage(tmp_table):
    t = make_table(tmp_table)
    t.update({"value": "'X'"}, "id = 4")
    rows = by_type(changes(t, 1))
    assert [r["value"] for r in rows["update_preimage"]] == ["v4"]
    assert [r["value"] for r in rows["update_postimage"]] == ["X"]


def test_merge_captures_all_change_kinds(tmp_table):
    t = make_table(tmp_table)
    src = pa.table({"id": pa.array([2, 3, 100], pa.int64()),
                    "value": pa.array(["U2", "DEL", "N100"])})
    (t.alias("t").merge(src, "t.id = s.id", source_alias="s")
       .when_matched_update_all("s.value != 'DEL'")
       .when_matched_delete("s.value = 'DEL'")
       .when_not_matched_insert_all()
       .execute())
    rows = by_type(changes(t, 1))
    assert [r["id"] for r in rows["insert"]] == [100]
    assert [r["id"] for r in rows["delete"]] == [3]
    assert [r["value"] for r in rows["update_preimage"]] == ["v2"]
    assert [r["value"] for r in rows["update_postimage"]] == ["U2"]


def test_merge_skips_files_with_no_fired_clause(tmp_table):
    """A file whose matched rows all fall through every clause condition is
    left in place: no remove+add rewrite, and no spurious delete+insert
    change rows for rows that never logically changed."""
    t = make_table(tmp_table, n=5)
    from delta_tpu.commands.write import WriteIntoDelta

    WriteIntoDelta(t.delta_log, "append", pa.table({
        "id": pa.array(range(1000, 1005), pa.int64()),
        "value": pa.array([f"w{i}" for i in range(5)]),
    })).run()
    files_before = {f.path for f in t.delta_log.update().all_files}
    # id=2 (first file): update fires; id=1000 (second file): matched but
    # the clause condition is false — second file must stay untouched
    src = pa.table({"id": pa.array([2, 1000], pa.int64()),
                    "value": pa.array(["U2", "NOOP"])})
    (t.alias("t").merge(src, "t.id = s.id", source_alias="s")
       .when_matched_update_all("s.value != 'NOOP'")
       .execute())
    files_after = {f.path for f in t.delta_log.update().all_files}
    # the second file survives the merge verbatim
    second = [p for p in files_before if p in files_after]
    assert len(second) == 1
    rows = by_type(changes(t, 2))
    assert [r["id"] for r in rows["update_preimage"]] == [2]
    assert [r["id"] for r in rows["update_postimage"]] == [2]
    assert "insert" not in rows and "delete" not in rows
    # table contents intact
    got = t.to_arrow()
    vals = dict(zip(got.column("id").to_pylist(), got.column("value").to_pylist()))
    assert vals[2] == "U2" and vals[1000] == "w0" and got.num_rows == 10


def test_append_reconstructed_without_cdc_files(tmp_table):
    t = make_table(tmp_table, n=2)
    WriteIntoDelta(t.delta_log, "append",
                   pa.table({"id": pa.array([10], pa.int64()),
                             "value": pa.array(["new"])})).run()
    _, acts = next(iter(t.delta_log.get_changes(1)))
    assert not any(isinstance(a, AddCDCFile) for a in acts)
    rows = changes(t, 1)
    assert [(r["id"], r[CHANGE_TYPE_COL]) for r in rows] == [(10, "insert")]


def test_whole_table_delete_reconstructed_from_removes(tmp_table):
    t = make_table(tmp_table, n=4)
    t.delete()  # case 1: file-level removes, no CDC written
    rows = changes(t, 1)
    assert len(rows) == 4
    assert all(r[CHANGE_TYPE_COL] == "delete" for r in rows)


# -- deletion-vector interplay ------------------------------------------------


def test_dv_delete_without_cdf_reconstructs_from_dv_diff(tmp_table):
    t = make_table(
        tmp_table, cdf=False,
        extra_props={"delta.tpu.enableDeletionVectors": "true"},
    )
    t.delete("id < 4")
    t.delete("id = 7")  # second DV on the same file: diff must isolate id=7
    rows1 = changes(t, 1, 1)
    assert sorted(r["id"] for r in rows1) == [0, 1, 2, 3]
    rows2 = changes(t, 2, 2)
    assert [r["id"] for r in rows2] == [7]
    assert all(r[CHANGE_TYPE_COL] == "delete" for r in rows1 + rows2)


def test_dv_plus_cdf_uses_cdc_files(tmp_table):
    t = make_table(
        tmp_table, extra_props={"delta.tpu.enableDeletionVectors": "true"}
    )
    t.update({"value": "'Z'"}, "id >= 8")
    rows = by_type(changes(t, 1))
    assert sorted(r["id"] for r in rows["update_preimage"]) == [8, 9]
    assert [r["value"] for r in rows["update_postimage"]] == ["Z", "Z"]
    _, acts = next(iter(t.delta_log.get_changes(1)))
    assert any(isinstance(a, AddCDCFile) for a in acts)


# -- ranges & errors ----------------------------------------------------------


def test_version_range_selection(tmp_table):
    t = make_table(tmp_table, n=2)
    t.delete("id = 0")        # v1
    t.update({"value": "'u'"}, "id = 1")  # v2
    assert all(r[COMMIT_VERSION_COL] == 1 for r in changes(t, 1, 1))
    both = changes(t, 1, 2)
    assert {r[COMMIT_VERSION_COL] for r in both} == {1, 2}
    assert {r[COMMIT_VERSION_COL] for r in changes(t, 2)} == {2}


def test_commit_timestamps_present(tmp_table):
    t = make_table(tmp_table)
    t.delete("id = 1")
    rows = changes(t, 1)
    assert all(r[COMMIT_TIMESTAMP_COL] > 0 for r in rows)


def test_start_after_end_rejected(tmp_table):
    t = make_table(tmp_table)
    with pytest.raises(DeltaAnalysisError):
        t.table_changes(5, 2)


def test_cdc_write_blocked_without_property(tmp_table):
    """Matches the reference's gate (actions.scala:151-156): committing cdc
    actions to a non-CDF table fails."""
    t = make_table(tmp_table, cdf=False)
    cdc = AddCDCFile(path="_change_data/x.parquet", partition_values={}, size=1)
    with pytest.raises(DeltaUnsupportedOperationError):
        t.delta_log.with_new_transaction(
            lambda txn: txn.commit([cdc], __import__(
                "delta_tpu.commands.operations", fromlist=["x"]
            ).Write(mode="Append"))
        )


def test_cdf_table_requires_writer_v4(tmp_table):
    t = make_table(tmp_table)
    assert t.delta_log.update().protocol.min_writer_version >= 4


def test_cdc_files_do_not_affect_table_state(tmp_table):
    t = make_table(tmp_table)
    t.delete("id < 5")
    t.update({"value": "'q'"}, "id = 9")
    assert t.to_arrow().num_rows == 5
    # CDC files are not part of all_files
    for f in t.delta_log.update().all_files:
        assert not f.path.startswith("_change_data")


# -- streaming CDF source -----------------------------------------------------


def test_streaming_cdf_source_tails_changes(tmp_table):
    from delta_tpu.streaming.source import DeltaCDFSource

    t = make_table(tmp_table, n=4)
    src = DeltaCDFSource(t.delta_log)
    start = src.initial_offset()
    end = src.latest_offset(start)
    batch = src.get_batch(None, end)
    assert batch.num_rows == 4  # initial snapshot as inserts
    assert set(batch.column(CHANGE_TYPE_COL).to_pylist()) == {"insert"}

    t.delete("id = 2")
    t.update({"value": "'u'"}, "id = 3")
    cur = end
    rows = []
    while True:
        nxt = src.latest_offset(cur)
        if nxt is None:
            break
        rows.extend(src.get_batch(cur, nxt).to_pylist())
        cur = nxt
    kinds = sorted(r[CHANGE_TYPE_COL] for r in rows)
    assert kinds == ["delete", "update_postimage", "update_preimage"]
    versions = {r[COMMIT_VERSION_COL] for r in rows}
    assert versions == {1, 2}


def test_streaming_tails_of_an_append_only_log_agree(tmp_table):
    """Over a log of plain appends (no CDF property: every commit is
    reconstructed), batched by `max_files_per_trigger` from version 0, the
    row source and the change-feed source both deliver exactly the rows a
    snapshot scan holds, the feed's all as inserts."""
    from delta_tpu.exec.scan import scan_to_table
    from delta_tpu.log.deltalog import DeltaLog
    from delta_tpu.streaming.source import DeltaCDFSource, DeltaSource

    log = DeltaLog.for_table(tmp_table)
    n_commits, per = 12, 10
    for i in range(n_commits):
        WriteIntoDelta(log, "append", pa.table({
            "id": pa.array(range(i * per, (i + 1) * per), pa.int64()),
        })).run()

    def tail(src):
        off, ids, kinds, batches = src.initial_offset(), [], set(), 0
        while (end := src.latest_offset(off)) is not None:
            batch = src.get_batch(off, end)
            ids.extend(batch.column("id").to_pylist())
            if CHANGE_TYPE_COL in batch.column_names:
                kinds.update(batch.column(CHANGE_TYPE_COL).to_pylist())
            off, batches = end, batches + 1
        return sorted(ids), kinds, batches

    want = sorted(scan_to_table(log.update()).column("id").to_pylist())
    assert want == list(range(n_commits * per))
    rows, _kinds, batches = tail(DeltaSource(
        log, max_files_per_trigger=5, starting_version=0))
    assert rows == want and batches == 3
    feed, kinds, _ = tail(DeltaCDFSource(
        log, max_files_per_trigger=5, starting_version=0))
    assert feed == want and kinds == {"insert"}


def test_streaming_cdf_source_ignores_hygiene(tmp_table):
    """Updates/deletes never raise on the CDF source (they ARE the data),
    unlike the row source's ignoreChanges contract."""
    from delta_tpu.streaming.source import DeltaCDFSource, DeltaSource

    t = make_table(tmp_table, n=4)
    t.update({"value": "'u'"}, "id = 1")
    plain = DeltaSource(t.delta_log, starting_version=0)
    with pytest.raises(Exception):
        for _ in plain._changes_from(1, -1):
            pass
    cdf_src = DeltaCDFSource(t.delta_log, starting_version=0)
    assert [f.version for f in cdf_src._changes_from(1, -1)] == [1]


def test_cdf_start_beyond_latest_rejected(tmp_table):
    t = make_table(tmp_table)
    with pytest.raises(DeltaAnalysisError):
        t.table_changes(100)


def test_cdf_cleaned_start_version_is_data_loss(tmp_table):
    """Retention-cleaned commits must surface as an error, not a silently
    shorter feed."""
    import os
    from delta_tpu.protocol import filenames

    t = make_table(tmp_table, n=2)
    t.delete("id = 0")      # v1
    t.delete("id = 1")      # v2
    t.delta_log.checkpoint()
    os.remove(f"{t.delta_log.log_path}/{filenames.delta_file(0)}")
    os.remove(f"{t.delta_log.log_path}/{filenames.delta_file(1)}")
    from delta_tpu.log.deltalog import DeltaLog

    DeltaLog.clear_cache()
    t2 = DeltaTable.for_path(tmp_table)
    with pytest.raises(DeltaAnalysisError):
        t2.table_changes(0)
    assert t2.table_changes(2).num_rows >= 1  # retained range still works


def test_streaming_cdf_schema_change_still_fatal(tmp_table):
    """The CDF source waives change/delete hygiene but NOT schema drift."""
    from delta_tpu.commands.alter import add_columns
    from delta_tpu.schema.types import LongType, StructField
    from delta_tpu.streaming.source import DeltaCDFSource
    from delta_tpu.utils.errors import DeltaIllegalStateError

    t = make_table(tmp_table, n=2)
    src = DeltaCDFSource(t.delta_log, starting_version=0)
    add_columns(t.delta_log, [StructField("extra", LongType())])
    with pytest.raises(DeltaIllegalStateError):
        for _ in src._changes_from(1, -1):
            pass


def test_streaming_cdf_admission_caps_commits_per_trigger(tmp_table):
    from delta_tpu.streaming.source import DeltaCDFSource

    t = make_table(tmp_table, n=4)
    for i in range(4):
        t.delete(f"id = {i}")  # v1..v4
    src = DeltaCDFSource(t.delta_log, starting_version=0,
                         max_files_per_trigger=2)
    start = src.initial_offset()
    end1 = src.latest_offset(start)
    assert end1.reservoir_version <= 2, "cap must bound commits per batch"
    end2 = src.latest_offset(end1)
    assert end2.reservoir_version > end1.reservoir_version


def test_streaming_cdf_snapshot_rows_carry_real_timestamp(tmp_table):
    from delta_tpu.streaming.source import DeltaCDFSource

    t = make_table(tmp_table, n=2)
    src = DeltaCDFSource(t.delta_log)
    end = src.latest_offset(src.initial_offset())
    batch = src.get_batch(None, end)
    assert all(ts > 0 for ts in batch.column(COMMIT_TIMESTAMP_COL).to_pylist())
