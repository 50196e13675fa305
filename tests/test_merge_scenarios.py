"""Extended MERGE scenario families — the remaining behavior catalogue of
the reference's `MergeIntoSuiteBase.scala` (testExtendedMerge /
testNullCase / testAnalysisErrorsInExtendedMerge / insert-only /
testEvolution groups), re-expressed against the engine-native API. Each
test states the scenario it mirrors; any intentional divergence is noted
in PARITY.md §divergences."""
import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.utils.config import conf
from delta_tpu.utils.errors import (
    DeltaAnalysisError,
    DeltaError,
    DeltaUnsupportedOperationError,
)


@pytest.fixture(params=["device", "host"])
def executor(request):
    mode = "force" if request.param == "device" else "off"
    with conf.set_temporarily(**{"delta.tpu.merge.devicePath.mode": mode}):
        yield request.param


def _write(path, data):
    log = DeltaLog.for_table(str(path))
    WriteIntoDelta(log, "append",
                   pa.table(data) if isinstance(data, dict) else data).run()
    return log


def _rows(log, sort="k"):
    from delta_tpu.exec.scan import scan_to_table

    t = scan_to_table(log.update())
    if sort and sort in t.column_names:
        t = t.sort_by(sort)
    return t.to_pylist()


def _merge(log, source, cond, matched=(), not_matched=(), **kw):
    kw.setdefault("source_alias", "s")
    kw.setdefault("target_alias", "t")
    cmd = MergeIntoCommand(
        log, pa.table(source) if isinstance(source, dict) else source, cond,
        list(matched), list(not_matched), **kw
    )
    cmd.run()
    return cmd


def up(cond=None, **assigns):
    return MergeClause("update", assignments=assigns or None, condition=cond)


def delete(cond=None):
    return MergeClause("delete", condition=cond)


def ins(cond=None, **assigns):
    return MergeClause("insert", assignments=assigns or None, condition=cond)


K64 = pa.int64()


def _kv(ks, vs):
    return {"k": pa.array(ks, K64), "v": pa.array(vs, pa.float64())}


# ---------------------------------------------------------------------------
# testExtendedMerge: clause-combination matrix
# ---------------------------------------------------------------------------


def test_only_conditional_update(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3], [0.0, 0.0, 0.0]))
    _merge(log, _kv([1, 2, 9], [10, 20, 90]), "t.k = s.k",
           matched=[up("s.v > 15", v="s.v")])
    assert [r["v"] for r in _rows(log)] == [0.0, 20.0, 0.0]


def test_only_conditional_update_unmet_is_noop(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([1], [5.0]), "t.k = s.k", matched=[up("s.v > 99", v="s.v")])
    assert _rows(log) == [{"k": 1, "v": 1.0}]


def test_only_delete(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3], [1, 2, 3]))
    _merge(log, _kv([2, 9], [0, 0]), "t.k = s.k", matched=[delete()])
    assert [r["k"] for r in _rows(log)] == [1, 3]


def test_only_conditional_delete(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3], [1.0, 2.0, 3.0]))
    _merge(log, _kv([1, 2, 3], [1, 99, 99]), "t.k = s.k",
           matched=[delete("s.v > 50 AND t.v < 3.0")])
    assert [r["k"] for r in _rows(log)] == [1, 3]


def test_conditional_update_then_delete(tmp_path, executor):
    """First matching clause wins: rows passing the update condition
    update; remaining matched rows delete."""
    log = _write(tmp_path / "t", _kv([1, 2, 3, 4], [1, 2, 3, 4]))
    _merge(log, _kv([1, 2, 3], [10, 20, 30]), "t.k = s.k",
           matched=[up("t.v >= 2.0", v="s.v"), delete()])
    assert _rows(log) == [
        {"k": 2, "v": 20.0}, {"k": 3, "v": 30.0}, {"k": 4, "v": 4.0}]


def test_conditional_delete_then_update_order_matters(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3, 4], [1, 2, 3, 4]))
    _merge(log, _kv([1, 2, 3], [10, 20, 30]), "t.k = s.k",
           matched=[delete("t.v >= 2.0"), up(v="s.v")])
    assert _rows(log) == [{"k": 1, "v": 10.0}, {"k": 4, "v": 4.0}]


def test_conditional_update_delete_insert_full_matrix(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3], [1, 2, 3]))
    _merge(log, _kv([1, 2, 8, 9], [10, 20, 80, 90]), "t.k = s.k",
           matched=[up("s.v <= 10", v="s.v"), delete()],
           not_matched=[ins("s.v >= 90")])
    assert _rows(log) == [
        {"k": 1, "v": 10.0}, {"k": 3, "v": 3.0}, {"k": 9, "v": 90.0}]


def test_update_plus_conditional_insert_no_updates_case(tmp_path):
    """Insert-only data through an update+insert merge: update clause never
    fires, conditional insert filters."""
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([7, 8], [70, 5]), "t.k = s.k",
           matched=[up(v="s.v")], not_matched=[ins("s.v > 10")])
    assert _rows(log) == [{"k": 1, "v": 1.0}, {"k": 7, "v": 70.0}]


def test_delete_plus_insert_multiple_matches_for_both(tmp_path, executor):
    """An unconditional single DELETE tolerates duplicate source matches;
    duplicate not-matched source keys insert once each (dup rows insert)."""
    log = _write(tmp_path / "t", _kv([1, 2], [1, 2]))
    _merge(log, _kv([1, 1, 9, 9], [0, 0, 90, 91]), "t.k = s.k",
           matched=[delete()], not_matched=[ins()])
    got = _rows(log)
    assert [r["k"] for r in got] == [2, 9, 9]
    assert sorted(r["v"] for r in got if r["k"] == 9) == [90.0, 91.0]


def test_multiple_not_matched_clauses_first_wins(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([5, 6], [50, 60]), "t.k = s.k",
           not_matched=[ins("s.v >= 60", v="s.v + 1000", k="s.k"), ins()])
    assert _rows(log) == [
        {"k": 1, "v": 1.0}, {"k": 5, "v": 50.0}, {"k": 6, "v": 1060.0}]


def test_only_conditional_update_with_multiple_matches_errors(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(DeltaError, match="[Mm]ultiple"):
        _merge(log, _kv([1, 1], [10, 20]), "t.k = s.k",
               matched=[up("s.v > 0", v="s.v")])


def test_only_delete_with_multiple_matches_ok(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2], [1, 2]))
    _merge(log, _kv([1, 1], [0, 0]), "t.k = s.k", matched=[delete()])
    assert [r["k"] for r in _rows(log)] == [2]


# ---------------------------------------------------------------------------
# testNullCase family
# ---------------------------------------------------------------------------


def _null_kv(ks, vs):
    return {"k": pa.array(ks, K64), "v": pa.array(vs, pa.float64())}


def test_null_value_in_target_nonkey(tmp_path, executor):
    log = _write(tmp_path / "t", _null_kv([1, 2], [None, 2.0]))
    _merge(log, _kv([1], [10]), "t.k = s.k", matched=[up(v="s.v")],
           not_matched=[ins()])
    assert _rows(log) == [{"k": 1, "v": 10.0}, {"k": 2, "v": 2.0}]


def test_null_value_in_source_nonkey_propagates(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _null_kv([1, 5], [None, None]), "t.k = s.k",
           matched=[up(v="s.v")], not_matched=[ins()])
    assert _rows(log) == [{"k": 1, "v": None}, {"k": 5, "v": None}]


def test_null_keys_both_sides_never_match(tmp_path, executor):
    """SQL equality: NULL = NULL is not true — null-key rows on both sides
    stay unmatched (source null keys insert)."""
    log = _write(tmp_path / "t", _null_kv([None, 2], [0.5, 2.0]))
    _merge(log, _null_kv([None, 2], [99.0, 20.0]), "t.k = s.k",
           matched=[up(v="s.v")], not_matched=[ins()])
    got = _rows(log)
    ks = [r["k"] for r in got]
    assert ks.count(None) == 2 and 2 in ks
    assert {r["v"] for r in got if r["k"] is None} == {0.5, 99.0}
    assert [r["v"] for r in got if r["k"] == 2] == [20.0]


def test_null_handling_is_null_in_condition(tmp_path, executor):
    """IS NULL conjuncts in the merge condition route through the residual
    evaluator with Kleene semantics."""
    log = _write(tmp_path / "t", _null_kv([1, None], [1.0, 5.0]))
    _merge(log, _kv([1], [10]), "t.k = s.k AND t.v IS NOT NULL",
           matched=[up(v="s.v")])
    got = _rows(log)
    assert [r["v"] for r in got if r["k"] == 1] == [10.0]
    assert [r["v"] for r in got if r["k"] is None] == [5.0]


def test_null_in_condition_literal(tmp_path):
    """A `= NULL` conjunct is never true: no row matches, inserts fire."""
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([1], [10]), "t.k = s.k AND t.v = NULL",
           matched=[up(v="s.v")], not_matched=[ins()])
    got = _rows(log)
    assert len(got) == 2 and sorted(r["v"] for r in got) == [1.0, 10.0]


def test_insert_only_null_in_source_key(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _null_kv([None, 7], [50.0, 70.0]), "t.k = s.k",
           not_matched=[ins()])
    got = _rows(log)
    assert len(got) == 3
    assert {r["v"] for r in got if r["k"] is None} == {50.0}


# ---------------------------------------------------------------------------
# analysis errors in extended syntax
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("clause_kind", ["update", "delete", "insert"])
def test_condition_unknown_reference_errors(tmp_path, clause_kind):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    bad_cond = "zzz > 0"
    if clause_kind == "update":
        clauses = dict(matched=[up(bad_cond, v="s.v")])
    elif clause_kind == "delete":
        clauses = dict(matched=[delete(bad_cond)])
    else:
        clauses = dict(not_matched=[ins(bad_cond)])
    with pytest.raises(DeltaError):
        _merge(log, _kv([1], [10]), "t.k = s.k", **clauses)


def test_insert_condition_referencing_target_errors(tmp_path):
    """NOT MATCHED conditions see only the source row (there IS no target
    row); a target-qualified reference must fail analysis."""
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(DeltaError):
        _merge(log, _kv([9], [90]), "t.k = s.k",
               not_matched=[ins("t.v > 0")])


def test_update_assignment_unknown_target_column_errors(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(DeltaError):
        _merge(log, _kv([1], [10]), "t.k = s.k",
               matched=[MergeClause("update", assignments={"nope": "s.v"})])


def test_update_assignments_conflict_same_column_errors(tmp_path):
    """Duplicate assignment targets in one UPDATE clause are rejected
    (reference: 'update assignments conflict')."""
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(DeltaError):
        _merge(log, _kv([1], [10]), "t.k = s.k",
               matched=[MergeClause("update",
                                    assignments={"v": "s.v", "V": "s.v + 1"})])


def test_delete_clause_with_assignments_errors(tmp_path):
    with pytest.raises(DeltaError):
        log = _write(tmp_path / "t", _kv([1], [1.0]))
        _merge(log, _kv([1], [10]), "t.k = s.k",
               matched=[MergeClause("delete", assignments={"v": "s.v"})])


def test_non_last_unconditional_matched_clause_errors(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(DeltaError):
        _merge(log, _kv([1], [10]), "t.k = s.k",
               matched=[up(v="s.v"), delete("s.v > 0")])


def test_aggregate_in_merge_condition_errors(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with pytest.raises(Exception):
        _merge(log, _kv([1], [10]), "t.k = s.k AND sum(s.v) > 0",
               matched=[up(v="s.v")])


# ---------------------------------------------------------------------------
# source shapes: self-merge, query-shaped sources, column order
# ---------------------------------------------------------------------------


def test_self_merge_table_as_its_own_source(tmp_path, executor):
    from delta_tpu.exec.scan import scan_to_table

    log = _write(tmp_path / "t", _kv([1, 2], [1.0, 2.0]))
    selfsrc = scan_to_table(log.update())
    _merge(log, selfsrc, "t.k = s.k", matched=[up(v="s.v + 100")])
    assert [r["v"] for r in _rows(log)] == [101.0, 102.0]


def test_source_is_filtered_query(tmp_path):
    """Source = the result of a computation (the reference's 'source is a
    query'): merge consumes any Arrow table."""
    import pyarrow.compute as pc

    log = _write(tmp_path / "t", _kv([1, 2, 3], [1, 2, 3]))
    big = pa.table(_kv([1, 2, 3, 4], [10, 20, 30, 40]))
    src = big.filter(pc.greater(big.column("v"), 15.0))
    _merge(log, src, "t.k = s.k", matched=[up(v="s.v")], not_matched=[ins()])
    assert [r["v"] for r in _rows(log)] == [1.0, 20.0, 30.0, 40.0]


def test_columns_specified_in_wrong_order(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    src = pa.table({"v": pa.array([10.0]), "k": pa.array([1], K64)})
    _merge(log, src, "t.k = s.k", matched=[up(v="s.v")], not_matched=[ins()])
    assert _rows(log) == [{"k": 1, "v": 10.0}]


def test_not_all_columns_specified_in_update(tmp_path):
    log = _write(tmp_path / "t", {
        "k": pa.array([1], K64), "a": pa.array([1.0]), "b": pa.array([2.0])})
    _merge(log, {"k": pa.array([1], K64), "a": pa.array([10.0]),
                 "b": pa.array([20.0])},
           "t.k = s.k", matched=[up(a="s.a")])
    assert _rows(log) == [{"k": 1, "a": 10.0, "b": 2.0}]


def test_same_column_names_in_source_and_target_resolved_by_alias(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([1], [9.0]), "t.k = s.k", matched=[up(v="t.v + s.v")])
    assert _rows(log) == [{"k": 1, "v": 10.0}]


def test_merge_by_unaliased_column_names(tmp_path):
    """Unqualified references resolve source-first in values, target in
    assignment targets (engine rule; reference resolves via plans)."""
    log = _write(tmp_path / "t", _kv([1, 5], [1.0, 5.0]))
    _merge(log, {"k": pa.array([1], K64), "nv": pa.array([10.0])},
           "t.k = s.k", matched=[up(v="nv")])
    assert [r["v"] for r in _rows(log)] == [10.0, 5.0]


def test_merge_source_column_sharing_char_target_name_not_padded(tmp_path):
    """ADVICE (high): a clause condition on a SOURCE column that merely
    shares a name with a target char(n) column must NOT get its literal
    padded — `s.status = 'x'` compares against the source's raw 'x', not
    'x    '. The reference pads only refs resolving to char attributes."""
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.schema.types import CharType, LongType, StructType

    path = str(tmp_path / "t")
    schema = StructType().add("k", LongType()).add("status", CharType(5))
    t = DeltaTable.create(path, schema)
    WriteIntoDelta(t.delta_log, "append", pa.table({
        "k": pa.array([1, 2], pa.int64()),
        "status": pa.array(["a", "b"], pa.string()),
    })).run()
    src = pa.table({
        "k": pa.array([1, 2], pa.int64()),
        "status": pa.array(["x", "keep"], pa.string()),
    })
    cmd = _merge(t.delta_log, src, "t.k = s.k",
                 matched=[up("s.status = 'x'", status="s.status")])
    assert cmd.metrics["numTargetRowsUpdated"] == 1
    rows = _rows(t.delta_log)
    assert rows[0]["status"] == "x    "  # updated, then char-padded on write
    assert rows[1]["status"] == "b    "  # clause condition false: untouched

    # ... while a TARGET-qualified char comparison still pads its literal
    cmd2 = _merge(t.delta_log, src, "t.k = s.k",
                  matched=[delete("t.status = 'b'")])
    assert cmd2.metrics["numTargetRowsDeleted"] == 1
    assert [r["k"] for r in _rows(t.delta_log)] == [1]


# ---------------------------------------------------------------------------
# insert-only family
# ---------------------------------------------------------------------------


def test_insert_only_with_source_condition(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([5, 6, 7], [50, 60, 70]), "t.k = s.k",
           not_matched=[ins("s.v >= 60")])
    assert [r["k"] for r in _rows(log)] == [1, 6, 7]


def test_insert_only_predicate_on_key(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([5, 6], [50, 60]), "t.k = s.k AND s.k % 2 = 0",
           not_matched=[ins()])
    got = [r["k"] for r in _rows(log)]
    assert 5 in got and 6 in got  # non-equi conjunct only gates MATCHING


def test_insert_only_multiple_matches_duplicates_insert(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    _merge(log, _kv([9, 9], [90, 91]), "t.k = s.k", not_matched=[ins()])
    assert sorted(r["v"] for r in _rows(log) if r["k"] == 9) == [90.0, 91.0]


def test_insert_only_explicit_subset_of_columns(tmp_path):
    log = _write(tmp_path / "t", {
        "k": pa.array([1], K64), "a": pa.array([1.0]), "b": pa.array([2.0])})
    _merge(log, {"k": pa.array([9], K64), "a": pa.array([90.0])},
           "t.k = s.k", not_matched=[ins(k="s.k", a="s.a")])
    got = _rows(log)
    assert got[1] == {"k": 9, "a": 90.0, "b": None}


# ---------------------------------------------------------------------------
# schema evolution extras
# ---------------------------------------------------------------------------


def _evolve(**kw):
    return conf.set_temporarily(**{
        "delta.tpu.schema.autoMerge.enabled": True, **kw})


def test_evolution_new_column_with_only_insert_star(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with _evolve():
        _merge(log, {"k": pa.array([9], K64), "v": pa.array([90.0]),
                     "extra": pa.array(["x"])},
               "t.k = s.k", not_matched=[ins()])
    got = _rows(log)
    assert got[0]["extra"] is None and got[1]["extra"] == "x"


def test_evolution_new_column_with_only_update_star(tmp_path):
    log = _write(tmp_path / "t", _kv([1, 2], [1.0, 2.0]))
    with _evolve():
        _merge(log, {"k": pa.array([1], K64), "v": pa.array([10.0]),
                     "extra": pa.array([7], K64)},
               "t.k = s.k", matched=[up()])
    got = _rows(log)
    assert got[0]["extra"] == 7 and got[1]["extra"] is None


def test_evolution_update_star_with_column_not_in_source(tmp_path):
    """update * with a target column absent from the source keeps the
    target value (star expands over SOURCE columns)."""
    log = _write(tmp_path / "t", {
        "k": pa.array([1], K64), "a": pa.array([1.0]), "b": pa.array([5.0])})
    with _evolve():
        _merge(log, {"k": pa.array([1], K64), "a": pa.array([10.0])},
               "t.k = s.k", matched=[up()])
    assert _rows(log) == [{"k": 1, "a": 10.0, "b": 5.0}]


def test_evolution_mixed_star_and_explicit_clauses(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with _evolve():
        _merge(log, {"k": pa.array([1, 9], K64), "v": pa.array([10.0, 90.0]),
                     "nc": pa.array([100.0, 900.0])},
               "t.k = s.k",
               matched=[MergeClause("update", assignments={"v": "s.nc"})],
               not_matched=[ins()])
    got = _rows(log)
    assert got[0] == {"k": 1, "v": 100.0, "nc": None}
    assert got[1] == {"k": 9, "v": 90.0, "nc": 900.0}


def test_evolution_incompatible_type_change_errors(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    with _evolve():
        with pytest.raises(DeltaError):
            _merge(log, {"k": pa.array([1], K64),
                         "v": pa.array(["not-a-number"])},
                   "t.k = s.k", matched=[up()])


def test_evolution_on_partitioned_table(tmp_path):
    from delta_tpu.api.tables import DeltaTable
    from delta_tpu.schema.types import DoubleType, LongType, StringType, StructType

    path = str(tmp_path / "pt")
    schema = (StructType().add("p", StringType()).add("k", LongType())
              .add("v", DoubleType()))
    DeltaTable.create(path, schema, partition_columns=["p"])
    log = DeltaLog.for_table(path)
    WriteIntoDelta(log, "append", pa.table({
        "p": pa.array(["a"]), "k": pa.array([1], K64),
        "v": pa.array([1.0])})).run()
    with _evolve():
        _merge(log, {"p": pa.array(["a", "b"]), "k": pa.array([1, 2], K64),
                     "v": pa.array([10.0, 20.0]),
                     "extra": pa.array([5, 6], K64)},
               "t.k = s.k", matched=[up()], not_matched=[ins()])
    got = _rows(log)
    assert {r["p"] for r in got} == {"a", "b"}
    assert [r["extra"] for r in got] == [5, 6]


def test_star_expansion_with_dotted_source_names(tmp_path):
    """Reference parity ('star expansion with names including dots'): a
    flat source column whose NAME contains a dot evolves in as a flat
    column and round-trips its values."""
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    src = pa.table({"k": pa.array([1], K64), "v": pa.array([10.0]),
                    "v.x": pa.array([9.0])})
    with _evolve():
        _merge(log, src, "t.k = s.k", matched=[up()])
    got = _rows(log)
    assert got[0]["v"] == 10.0 and got[0]["v.x"] == 9.0


# ---------------------------------------------------------------------------
# metrics parity spot checks
# ---------------------------------------------------------------------------


def test_merge_metrics_update_delete_insert_counts(tmp_path, executor):
    log = _write(tmp_path / "t", _kv([1, 2, 3, 4], [1, 2, 3, 4]))
    cmd = _merge(log, _kv([1, 2, 9], [10, 0, 90]), "t.k = s.k",
                 matched=[up("s.v > 5", v="s.v"), delete()],
                 not_matched=[ins()])
    m = cmd.metrics
    assert m["numTargetRowsUpdated"] == 1
    assert m["numTargetRowsDeleted"] == 1
    assert m["numTargetRowsInserted"] == 1
    assert m["numSourceRows"] == 3


def test_merge_metrics_zero_touch_when_nothing_matches(tmp_path):
    log = _write(tmp_path / "t", _kv([1], [1.0]))
    cmd = _merge(log, _kv([9], [90]), "t.k = s.k", matched=[up(v="s.v")])
    assert cmd.metrics["numTargetRowsUpdated"] == 0
    assert cmd.metrics["numTargetRowsInserted"] == 0


# ---------------------------------------------------------------------------
# findTouchedFiles from the key statistics: a rewrite decodes only the files
# whose [min, max] of the key admits a source key, and joins as before
# ---------------------------------------------------------------------------

TOUCHED_CASES = {  # case -> (target key type, source keys, files decoded of 4)
    "long": (K64, pa.array([5, 207], K64), 2),
    "int_target_long_source": (pa.int32(), pa.array([105, 2**40], K64), 1),
    "text_source": (K64, pa.array(["5", "307"]), 2),
    "fractional_source": (K64, pa.array([5.5, 105.0]), 4),
    "date": (pa.date32(), pa.array([5, 120], pa.date32()), 2),
    "text_key": (pa.string(), pa.array(["k005"]), 4),
    "null_keys": (K64, pa.array([None, None], K64), 0),
}


@pytest.mark.parametrize("case", list(TOUCHED_CASES))
def test_a_rewrite_decodes_only_files_whose_statistics_admit_a_key(
        tmp_path, executor, case):
    """Four files of 100 keys each, rewritten in place (no deletion
    vectors). Only integer and date keys narrow (their statistics are
    exact); a source key that does not cast to the key's type narrows
    nothing; a null key admits no file. The table is the one the join's own
    key semantics make, and the files rewritten are those with a match."""
    from delta_tpu.utils import telemetry

    typ, keys, n_decoded = TOUCHED_CASES[case]

    def key_of(i):
        if pa.types.is_string(typ):
            return f"k{i:03d}"
        return i

    log = DeltaLog.for_table(str(tmp_path / "t"))
    for lo in range(0, 400, 100):
        WriteIntoDelta(log, "append", pa.table({
            "k": pa.array([key_of(i) for i in range(lo, lo + 100)], typ),
            "v": pa.array(np.arange(lo, lo + 100, dtype=np.float64))})).run()
    telemetry.clear_events()
    cmd = _merge(log, pa.table({"k": keys, "v": pa.array([-1.0] * len(keys))}),
                 "t.k = s.k", matched=[up(v="s.v")])
    [decode] = telemetry.recent_events("delta.dml.merge.rowDecode")
    assert decode.data["files"] == n_decoded
    # what the join matches: a text source compares as text, an integral
    # float as the integer, a fraction and a null as nothing
    hit = {"long": [5, 207], "int_target_long_source": [105],
           "text_source": [5, 307], "fractional_source": [105], "date": [5, 120],
           "text_key": [5], "null_keys": []}[case]
    got = _rows(log)
    assert [r["v"] for r in got] == [-1.0 if i in hit else float(i)
                                     for i in range(400)]
    assert [r["k"] for r in got] == [
        r["k"] for r in pa.table({"k": pa.array(
            [key_of(i) for i in range(400)], typ)}).to_pylist()]
    assert cmd.metrics["numTargetRowsUpdated"] == len(hit)
    assert cmd.metrics["numTargetFilesRemoved"] == len({i // 100 for i in hit})
