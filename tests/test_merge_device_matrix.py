"""Device/host MERGE result-identity matrix (ISSUE 6 satellite).

The fused device path — both residency variants: the cold slab pipeline
(`MergeIntoCommand._launch_slab_pipeline` + `ops/key_cache.SlabBuilder`)
and the HBM cache hit (`ops/key_cache.KeyCache`) — must be row-identical
to the host Arrow hash join across the semantic corners: matched /
not-matched / insert-only / multi-match error / NULL-key sentinels /
composite packed keys, deletion vectors included. Every scenario runs the
same merge on two copies of a seeded table, fused-forced vs host-pinned,
and compares the full sorted row sets.
"""
import contextlib
import shutil

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.expr import ir
from delta_tpu.ops.key_cache import KeyCache
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf
from delta_tpu.utils.errors import DeltaUnsupportedOperationError


@pytest.fixture(autouse=True)
def _fresh_cache():
    KeyCache.reset()
    yield
    KeyCache.reset()


@pytest.fixture(params=["cold", "hit"])
def fused(request):
    """Which fused-device residency variant the scenario forces: 'cold'
    (no cached entry — the slab pipeline builds + registers inline) or
    'hit' (the key lane is pre-built, the merge probes the cache)."""
    return request.param


UP = MergeClause("update", assignments=None)
INS = MergeClause("insert", assignments=None)
DEL = MergeClause("delete")
ALIAS = dict(source_alias="s", target_alias="t")


def _seed_table(path, *, composite=False, with_null_target=False, files=3):
    """Multi-file target with negative + positive int64 keys and payload
    columns; optionally a second key component / NULL target keys."""
    log = DeltaLog.for_table(str(path))
    rng = np.random.RandomState(11)
    per = 40
    for i in range(files):
        lo = -40 + i * per
        keys = np.arange(lo, lo + per, dtype=np.int64)
        k = pa.array(keys)
        if with_null_target and i == 1:
            py = keys.tolist()
            py[3] = None  # one NULL target key per middle file
            k = pa.array(py, pa.int64())
        cols = {
            "k": k,
            "v": pa.array(rng.rand(per)),
            "tag": pa.array([f"r{j}" for j in keys]),
        }
        if composite:
            cols["k2"] = pa.array((keys % 7).astype(np.int64))
        WriteIntoDelta(log, "append", pa.table(cols)).run()
    return log


def _rows(log, keys=("k",)):
    from delta_tpu.exec.scan import scan_to_table

    t = scan_to_table(log.update())
    return sorted(t.to_pylist(), key=lambda r: tuple(
        (r[c] is None, r[c]) for c in list(keys) + ["tag", "v"]))


def _run(log, source, cond, matched, not_matched, mode):
    with conf.set_temporarily(**{
        "delta.tpu.merge.devicePath.mode": mode,
        "delta.tpu.deletionVectors.enabled": True,
        "delta.tpu.merge.keyCache.enabled": mode != "off",
    }):
        cmd = MergeIntoCommand(log, source, cond, matched, not_matched,
                               **ALIAS)
        cmd.run()
    return cmd


def _prebuild(log, cond, target_cols, source_cols):
    """Build the table's resident key lane using the merge's own resolved
    key signature (what the background build would have produced)."""
    probe = MergeIntoCommand(log, pa.table({c: pa.array([], pa.int64())
                                            for c in source_cols}),
                             cond, [UP], [INS], **ALIAS)
    resolved = probe._resolve(probe.condition, target_cols, source_cols)
    equi, _ = probe._split_equi_keys(resolved)
    t_exprs = [t for t, _ in equi]
    sig = MergeIntoCommand._key_signature(t_exprs)
    key_cols = [c for c in target_cols
                if c.lower() in {r.lower() for t, _ in equi
                                 for r in ir.references(t)}]
    e = KeyCache.instance().get(log.update(), sig, key_cols, t_exprs)
    assert e is not None
    return e


def _identity_case(tmp_path, fused, source, cond, matched, not_matched,
                   *, composite=False, with_null_target=False,
                   expect_path=None, keys=("k",)):
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = _seed_table(a, composite=composite,
                        with_null_target=with_null_target)
    shutil.copytree(a, b)
    log_b = DeltaLog.for_table(b)
    tcols = [f.name for f in log_a.update().metadata.schema.fields]
    scols = source.column_names
    if fused == "hit":
        _prebuild(log_a, cond, tcols, scols)
    cmd_a = _run(log_a, source, cond, matched, not_matched, "force")
    cmd_b = _run(log_b, source, cond, matched, not_matched, "off")
    assert cmd_a._device_join is not None, "fused path did not engage"
    assert cmd_a._join_path == (
        expect_path or ("resident" if fused == "hit" else "device-cold"))
    assert cmd_b._device_join is None
    for k in sorted(set(cmd_a.metrics) & set(cmd_b.metrics)):
        if k.endswith("TimeMs"):
            continue  # wall-clock differs by construction
        assert cmd_a.metrics[k] == cmd_b.metrics[k], k
    assert _rows(log_a, keys) == _rows(log_b, keys)
    return cmd_a, cmd_b


# -- the matrix -------------------------------------------------------------


def _upsert_source():
    rng = np.random.RandomState(3)
    keys = np.concatenate([
        np.arange(-10, 20, 3, dtype=np.int64),        # hits incl. negatives
        np.arange(500, 520, dtype=np.int64),          # misses -> inserts
    ])
    return pa.table({
        "k": pa.array(keys),
        "v": pa.array(rng.rand(len(keys))),
        "tag": pa.array([f"s{i}" for i in range(len(keys))]),
    })


def test_matched_and_not_matched_upsert(tmp_path, fused):
    """The headline shape: star upsert, hits + misses, DV mode."""
    cmd_a, _ = _identity_case(
        tmp_path, fused, _upsert_source(), "t.k = s.k", [UP], [INS])
    assert cmd_a.metrics["numTargetRowsUpdated"] == 10
    assert cmd_a.metrics["numTargetRowsInserted"] == 20


def test_matched_only_with_clause_conditions(tmp_path, fused):
    """UPDATE/DELETE with conditions referencing both sides; no inserts."""
    src = _upsert_source()
    _identity_case(
        tmp_path, fused, src, "t.k = s.k",
        [MergeClause("update", condition="s.v >= 0.5", assignments=None),
         MergeClause("delete")],
        [])


def test_insert_only_duplicate_sources(tmp_path, fused):
    """Insert-only fast path: duplicate source keys are legal (left-anti),
    and the fused probe fetches only the head (no pair download)."""
    keys = np.array([5, 5, 700, 700, 701, -3], np.int64)
    src = pa.table({
        "k": pa.array(keys),
        "v": pa.array(np.linspace(0, 1, len(keys))),
        "tag": pa.array([f"d{i}" for i in range(len(keys))]),
    })
    cmd_a, _ = _identity_case(
        tmp_path, fused, src, "t.k = s.k", [], [INS])
    # 5 and -3 exist; one insert per miss ROW (700, 700, 701)
    assert cmd_a.metrics["numTargetRowsInserted"] == 3


def test_null_source_and_target_keys_sentinel(tmp_path, fused):
    """SQL NULL semantics under sentinel encoding: NULL source keys never
    match (they insert), NULL target keys never match (they stay)."""
    src = pa.table({
        "k": pa.array([7, None, None, 900], pa.int64()),
        "v": pa.array([0.1, 0.2, 0.3, 0.4]),
        "tag": pa.array(["n0", "n1", "n2", "n3"]),
    })
    cmd_a, _ = _identity_case(
        tmp_path, fused, src, "t.k = s.k", [UP], [INS],
        with_null_target=True)
    assert cmd_a.metrics["numTargetRowsUpdated"] == 1   # only k=7
    assert cmd_a.metrics["numTargetRowsInserted"] == 3  # 2 NULLs + 900


def test_composite_packed_keys(tmp_path, fused):
    """Two-component equi keys pack into one int64 lane (hi<<32|lo) with
    negative components; identity incl. per-component NULLs."""
    keys = np.array([-5, 2, 9, 9, 333], np.int64)
    src = pa.table({
        "k": pa.array(keys),
        "k2": pa.array([(-5) % 7, 2 % 7, 9 % 7, 6, 1], pa.int64()),
        "v": pa.array(np.linspace(0, 1, len(keys))),
        "tag": pa.array([f"c{i}" for i in range(len(keys))]),
    })
    cmd_a, _ = _identity_case(
        tmp_path, fused, src, "t.k = s.k AND t.k2 = s.k2", [UP], [INS],
        composite=True, keys=("k", "k2"))
    # (9, 6) and (333, 1) miss; (-5), (2), (9 % 7) hit
    assert cmd_a.metrics["numTargetRowsUpdated"] == 3
    assert cmd_a.metrics["numTargetRowsInserted"] == 2


def test_multi_match_error_parity(tmp_path, fused):
    """Duplicate source matches for one target row must raise on BOTH
    executors (reference `MergeIntoCommand.scala:351-365`)."""
    src = pa.table({
        "k": pa.array([4, 4], pa.int64()),
        "v": pa.array([1.0, 2.0]),
        "tag": pa.array(["m0", "m1"]),
    })
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = _seed_table(a)
    shutil.copytree(a, b)
    log_b = DeltaLog.for_table(b)
    if fused == "hit":
        _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], src.column_names)
    with pytest.raises(DeltaUnsupportedOperationError, match="multiple source"):
        _run(log_a, src, "t.k = s.k", [UP], [INS], "force")
    with pytest.raises(DeltaUnsupportedOperationError, match="multiple source"):
        _run(log_b, src, "t.k = s.k", [UP], [INS], "off")
    # single unconditional DELETE legally multi-matches on both
    cmd_a = _run(log_a, src, "t.k = s.k", [DEL], [], "force")
    cmd_b = _run(log_b, src, "t.k = s.k", [DEL], [], "off")
    assert cmd_a.metrics["numTargetRowsDeleted"] == 1
    assert cmd_b.metrics["numTargetRowsDeleted"] == 1
    assert _rows(log_a) == _rows(log_b)


def test_second_round_over_deletion_vectors(tmp_path, fused):
    """Round 2 merges into the DV-carrying files round 1 produced: the cold
    slab build must scatter DV-filtered decodes into physical layout, the
    hit path must advance through the DV diff."""
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = _seed_table(a)
    shutil.copytree(a, b)
    log_b = DeltaLog.for_table(b)
    if fused == "hit":
        _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], ["k", "v", "tag"])
    src1 = _upsert_source()
    _run(log_a, src1, "t.k = s.k", [UP], [INS], "force")
    _run(log_b, src1, "t.k = s.k", [UP], [INS], "off")
    if fused == "cold":
        KeyCache.reset()  # round 2 cold-builds over DV'd files
    src2 = pa.table({
        "k": pa.array([-10, 2, 505, 999], pa.int64()),
        "v": pa.array([9.0, 8.0, 7.0, 6.0]),
        "tag": pa.array(["z0", "z1", "z2", "z3"]),
    })
    cmd_a = _run(log_a, src2, "t.k = s.k", [UP], [INS], "force")
    cmd_b = _run(log_b, src2, "t.k = s.k", [UP], [INS], "off")
    assert cmd_a._device_join is not None
    assert cmd_a.metrics["numTargetRowsUpdated"] == 3  # -10, 2, 505
    assert cmd_a.metrics["numTargetRowsInserted"] == 1
    assert cmd_b.metrics["numTargetRowsUpdated"] == 3
    assert _rows(log_a) == _rows(log_b)


def test_post_optimize_merge_parity(tmp_path, fused):
    """ISSUE 6 small-fix regression: OPTIMIZE between merges bumps the
    key-cache epoch; the next fused merge must rebuild (never probe the
    pre-rewrite slab) and stay row-identical to the host."""
    from delta_tpu.commands.optimize import OptimizeCommand

    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = _seed_table(a)
    shutil.copytree(a, b)
    log_b = DeltaLog.for_table(b)
    _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], ["k", "v", "tag"])
    OptimizeCommand(log_a, min_file_size=1 << 30).run()
    OptimizeCommand(log_b, min_file_size=1 << 30).run()
    assert KeyCache.instance().peek(log_a.log_path,
                                    "[\"Column('k')\"]") is None \
        or not KeyCache.instance()._entries, \
        "epoch bump must drop the pre-rewrite entry"
    if fused == "hit":
        _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], ["k", "v", "tag"])
    src = _upsert_source()
    cmd_a = _run(log_a, src, "t.k = s.k", [UP], [INS], "force")
    cmd_b = _run(log_b, src, "t.k = s.k", [UP], [INS], "off")
    assert cmd_a._device_join is not None
    assert _rows(log_a) == _rows(log_b)


# -- the resident pairs-only route (ISSUE 26) --------------------------------
#
# When the key cache serves the MERGE and nothing of a target row is needed
# but which row it is, the probe's pairs are the join: no decode of the
# target. Every case below runs the same MERGE
# on two copies of a table with deletion vectors, both with the slab in the
# cache: copy a takes the route, copy b the decode route (its observable
# patched false; never a conf), and everything they leave behind is compared.

DV_PROPS = {"delta.tpu.enableDeletionVectors": "true"}
PAIRS_ONLY = "merge.resident.pairsOnly"
DECLINED = "merge.resident.pairsOnly.declined"


def _dv_tables(tmp_path, *, rows=800, files=8, composite=False,
               key_type=pa.int64(), props=None):
    """Two copies of one table with deletion vectors: ``files`` files of
    keys 0..rows in order, so a key names its file."""
    from delta_tpu import DeltaTable

    rng = np.random.RandomState(5)
    keys = np.arange(rows, dtype=np.int64)
    cols = {"k": pa.array(keys).cast(key_type),
            "v": pa.array(rng.rand(rows)),
            "tag": pa.array([f"r{j}" for j in keys])}
    if composite:
        cols["k2"] = pa.array(keys % 7)
    a, b = str(tmp_path / "a"), str(tmp_path / "b")
    with conf.set_temporarily(**{
            "delta.tpu.write.targetFileRows": rows // files}):
        DeltaTable.create(a, data=pa.table(cols),
                          configuration={**DV_PROPS, **(props or {})})
    shutil.copytree(a, b)
    log_a, log_b = DeltaLog.for_table(a), DeltaLog.for_table(b)
    assert len(log_a.update().all_files) == files
    return log_a, log_b


def _source(keys, *, k2=None, key_type=pa.int64(), names=("k", "v", "tag")):
    keys = list(keys)
    cols = {names[0]: pa.array(keys, key_type),
            names[1]: pa.array(np.linspace(0.0, 1.0, len(keys))),
            names[2]: pa.array([f"s{i}" for i in range(len(keys))])}
    if k2 is not None:
        cols["k2"] = pa.array(list(k2), pa.int64())
    return pa.table(cols)


def _counts():
    from delta_tpu.utils import telemetry

    c = telemetry.counters("merge.resident")
    return c.get(PAIRS_ONLY, 0), c.get(DECLINED, 0)


@contextlib.contextmanager
def _decode_route(monkeypatch):
    """The decode route of today on a resident MERGE: the observable that
    admits the pairs-only route reads false."""
    with monkeypatch.context() as m:
        m.setattr(MergeIntoCommand, "_pairs_only_shape",
                  lambda self, *a: False)
        yield


def _left_behind(log):
    """What the MERGEs left: the deletion vector of every live file, as
    (cardinality, bytes) or None, a file named by the commit that first
    added it and its place there (the copies' new files differ in name
    only); and each data file the last commit added, in its order, as
    (rows, bytes)."""
    import os

    import pyarrow.parquet as pq

    from delta_tpu.protocol.actions import AddFile

    snap = log.update()
    label, new = {}, []
    for version, actions in log.get_changes(0):
        adds = [a for a in actions if isinstance(a, AddFile)]
        first = [a for a in adds if a.path not in label]
        for i, a in enumerate(first):
            label[a.path] = f"v{version}#{i}"
        if version == snap.version:
            new = [(pq.read_table(os.path.join(log.data_path, a.path)), a.size)
                   for a in first]
    dvs = {label[f.path]: f.deletion_vector and (
               f.deletion_vector["cardinality"], f.deletion_vector["sizeInBytes"])
           for f in snap.all_files}
    return dvs, new


def _same_outcome(log_a, log_b, cmd_a, cmd_b, keys=("k",), order=True):
    """``order``: the new files hold the same rows in the same order (two
    device routes; the host join pairs in its own order)."""
    for k in sorted(set(cmd_a.metrics) | set(cmd_b.metrics)):
        if not k.endswith("TimeMs"):
            assert cmd_a.metrics[k] == cmd_b.metrics[k], k
    dvs_a, new_a = _left_behind(log_a)
    dvs_b, new_b = _left_behind(log_b)
    assert dvs_a == dvs_b
    assert len(new_a) == len(new_b)
    for (ta, size_a), (tb, size_b) in zip(new_a, new_b):
        if order:
            assert ta.equals(tb) and size_a == size_b
        else:
            assert ta.sort_by("k").equals(tb.sort_by("k"))
    assert _rows(log_a, keys) == _rows(log_b, keys)


def _both_routes(log_a, log_b, monkeypatch, source, cond, matched,
                 not_matched, keys=("k",)):
    n0, d0 = _counts()
    cmd_a = _run(log_a, source, cond, matched, not_matched, "force")
    assert _counts() == (n0 + 1, d0)
    with _decode_route(monkeypatch):
        cmd_b = _run(log_b, source, cond, matched, not_matched, "force")
    assert _counts() == (n0 + 1, d0)
    assert cmd_a._pairs_only and not cmd_b._pairs_only
    assert cmd_a._join_path == cmd_b._join_path == "resident"
    _same_outcome(log_a, log_b, cmd_a, cmd_b, keys)
    return cmd_a, cmd_b


PAIRS_ONLY_CASES = {
    # hits in every file (one of them the first row of a file) and misses
    "first-resident": dict(source=_source([0, 3, 150, 399, 400, 799, 900, 901])),
    # the second round merges over the vectors and the file of the first:
    # the slab advances through them
    "second-round": dict(source=_source([3, 150, 400, 901, 902]),
                         then=_source([3, 151, 400, 902, 903])),
    "composite": dict(
        composite=True, cond="t.k = s.k AND t.k2 = s.k2", keys=("k", "k2"),
        source=_source([5, 9, 9, 333, 1000], k2=[5, 9 % 7, 6, 333 % 7, 1])),
    "int32-target-int64-source": dict(
        key_type=pa.int32(),
        source=_source([1, 250, 799, 2**31 + 5, 2**40])),
    "null-source-keys": dict(source=_source([7, None, None, 900])),
    "other-order-and-case": dict(
        source=_source([2, 450, 1234], names=("K", "V", "TAG"))
        .select(["TAG", "K", "V"])),
    "one-file-of-eight": dict(source=_source([510, 520, 530])),
    "insert-only": dict(source=_source([5, 5, 700, 900, 900, 901]),
                        matched=[]),
}


@pytest.mark.parametrize("case", list(PAIRS_ONLY_CASES))
def test_pairs_only_route_leaves_what_the_decode_route_leaves(
        tmp_path, monkeypatch, case):
    p = dict(PAIRS_ONLY_CASES[case])
    source = p.pop("source")
    cond = p.pop("cond", "t.k = s.k")
    keys = p.pop("keys", ("k",))
    matched = p.pop("matched", [UP])
    then = p.pop("then", None)
    log_a, log_b = _dv_tables(tmp_path, **p)
    tcols = [f.name for f in log_a.update().metadata.schema.fields]
    for log in (log_a, log_b):
        _prebuild(log, cond, tcols, source.column_names)
    for src in [source] + ([then] if then is not None else []):
        cmd_a, _ = _both_routes(log_a, log_b, monkeypatch, src, cond,
                                matched, [INS], keys)
    if case == "second-round":
        assert _left_behind(log_a)[0]["v1#0"][0] == 3  # of the first round's five
    if case == "one-file-of-eight":
        assert cmd_a.metrics["numTargetFilesRemoved"] == 1
    if case == "int32-target-int64-source":
        assert cmd_a.metrics["numTargetRowsUpdated"] == 3
        assert cmd_a.metrics["numTargetRowsInserted"] == 2
    if case == "insert-only":
        assert cmd_a.metrics["numTargetRowsInserted"] == 3  # 900, 900, 901


def _overflowing_probe(monkeypatch):
    from delta_tpu.ops import key_cache as kc

    def probe_async(self, *a, **kw):
        def finalize():
            raise kc.DeltaProbeOverflow("candidate windows overflowed")
        return kc.PendingProbe(finalize)

    monkeypatch.setattr(kc.ResidentJoinKeys, "probe_async", probe_async)


def _file_of(log, k):
    """The path of the file the table was created with that holds key k."""
    for f in log.update().all_files:
        st = f.stats_dict()
        if (st["numRecords"] == 100
                and st["minValues"]["k"] <= k <= st["maxValues"]["k"]):
            return f.path


def _slab_lacks_a_file(log):
    [entry] = KeyCache.instance()._entries.values()
    del entry.slabs[_file_of(log, 150)]


@pytest.mark.parametrize("why", ["overflow", "slab-lacks-a-file"])
def test_pairs_only_decline_takes_the_decode_route(tmp_path, monkeypatch, why):
    """A designed decline decodes late: the path of today (which, with a
    probe that overflowed or a slab that lacks a file, ends in the host
    join) and the same table."""
    log_a, log_b = _dv_tables(tmp_path)
    source = _source([0, 150, 151, 400, 799, 900])
    _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], source.column_names)
    if why == "overflow":
        _overflowing_probe(monkeypatch)
    else:
        _slab_lacks_a_file(log_a)
    n0, d0 = _counts()
    cmd_a = _run(log_a, source, "t.k = s.k", [UP], [INS], "force")
    assert _counts() == (n0 + 1, d0 + 1)
    assert not cmd_a._pairs_only and cmd_a._join_path == "host"
    # the target was decoded after all, late: both phases ran twice
    assert cmd_a.phase_ms["decode_ms"] > 0
    cmd_b = _run(log_b, source, "t.k = s.k", [UP], [INS], "off")
    _same_outcome(log_a, log_b, cmd_a, cmd_b)


ONE_PROBE_ROUTES = {  # route -> (decision, pairs-only)
    "pairs-only": ("resident", True),
    "declined-then-decoded": ("host", False),
    "resident-decode": ("resident", False),
    "cold-build": ("device-cold", False),
}


@pytest.mark.parametrize("route", list(ONE_PROBE_ROUTES))
def test_a_statement_launches_the_resident_probe_at_most_once(tmp_path,
                                                              route):
    """The route is decided once: whichever the statement takes, the
    device probes a slab once (one `delta.merge.deviceProbe` span) — also
    where the pairs alone declined and the same probe maps onto the decoded
    rows — and the table is what the host run leaves."""
    log_a, log_b = _dv_tables(tmp_path)
    source = _source([0, 150, 151, 400, 799, 900])
    matched = [MergeClause("update", assignments={"v": "s.v"})
               if route == "resident-decode" else UP]
    if route != "cold-build":
        _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], source.column_names)
    if route == "declined-then-decoded":
        _slab_lacks_a_file(log_a)
    n0, d0 = _counts()
    telemetry.clear_events()
    cmd_a = _run(log_a, source, "t.k = s.k", matched, [INS], "force")
    assert len(telemetry.recent_events("delta.merge.deviceProbe")) == 1
    assert (cmd_a._join_path, cmd_a._pairs_only) == ONE_PROBE_ROUTES[route]
    tried = route in ("pairs-only", "declined-then-decoded")
    assert _counts() == (n0 + tried, d0 + (route == "declined-then-decoded"))
    cmd_b = _run(log_b, source, "t.k = s.k", matched, [INS], "off")
    _same_outcome(log_a, log_b, cmd_a, cmd_b, order=False)


def _generated_tables(tmp_path):
    from delta_tpu import DeltaTable
    from delta_tpu.schema.types import DoubleType, LongType, StringType, StructType

    schema = (StructType().add("k", LongType()).add("v", DoubleType())
              .add("tag", StringType())
              .add("k_twice", LongType(),
                   metadata={"delta.generationExpression": "k * 2"}))
    a = str(tmp_path / "a")
    t = DeltaTable.create(a, schema=schema, configuration=dict(DV_PROPS))
    for lo in range(0, 800, 100):
        WriteIntoDelta(t.delta_log, "append", _source(range(lo, lo + 100))).run()
    return t.delta_log


@pytest.mark.parametrize("why", ["non-star-update", "change-data-feed",
                                 "generated-column"])
def test_a_merge_that_needs_target_values_keeps_the_decode_route(
        tmp_path, why):
    """The route adapts to what the statement reads of the target: an
    explicit assignment keeps the unassigned columns, the change feed wants
    pre-images, a generated column may read any column. Each decodes the
    three files whose key statistics admit a source key, of eight, and maps
    the resident probe onto their rows."""
    from delta_tpu.utils import telemetry

    matched = [UP]
    if why == "generated-column":
        log = _generated_tables(tmp_path)
    else:
        props = ({"delta.enableChangeDataFeed": "true"}
                 if why == "change-data-feed" else None)
        log, _ = _dv_tables(tmp_path, props=props)
    if why == "non-star-update":
        matched = [MergeClause("update", assignments={"v": "s.v"})]
    assert len(log.update().all_files) == 8
    source = _source([3, 150, 400, 901])
    tcols = [f.name for f in log.update().metadata.schema.fields]
    _prebuild(log, "t.k = s.k", tcols, source.column_names)
    n0, d0 = _counts()
    telemetry.clear_events()
    cmd = _run(log, source, "t.k = s.k", matched, [INS], "force")
    assert _counts() == (n0, d0)
    assert cmd._join_path == "resident" and not cmd._pairs_only
    names = [e.op_type for e in telemetry.recent_events()]
    assert "delta.scan.read" in names  # the target was decoded
    [decode] = telemetry.recent_events("delta.dml.merge.rowDecode")
    assert decode.data["files"] == 3
    assert cmd.metrics["numTargetRowsUpdated"] == 3
    assert cmd.metrics["numTargetRowsInserted"] == 1


def _revive_deleted_rows(log):
    """A slab made stale by hand: the rows the first file's deletion vector
    covers read live again, and the tag still says the vector is applied."""
    [entry] = KeyCache.instance()._entries.values()
    assert entry._set_dv(_file_of(log, 5), np.empty(0, np.int64))


def _shrink_a_file(log):
    """Another way to be stale: the slab's first file is shorter than its
    matched rows, so a pair lies in no file of the snapshot."""
    [entry] = KeyCache.instance()._entries.values()
    path = _file_of(log, 20)
    off, _rows_ = entry.slabs[path]
    entry.slabs[path] = (off, 2)


@pytest.mark.parametrize("stale", ["matched-a-deleted-row",
                                   "pair-in-no-file"])
def test_pairs_only_stale_slab_does_not_commit(tmp_path, stale):
    """No decode vouches for the slab on this route, so what the decode
    route cross-checked is checked where it is free: the union of a file's
    old vector with the claimed positions must grow by every claimed row.
    A slab that matched a deleted row is dropped and the MERGE runs once
    more, from the files."""
    log_a, log_b = _dv_tables(tmp_path)
    first = _source([5, 6, 7, 650])
    _prebuild(log_a, "t.k = s.k", ["k", "v", "tag"], first.column_names)
    _run(log_a, first, "t.k = s.k", [UP], [INS], "force")
    _run(log_b, first, "t.k = s.k", [UP], [INS], "off")
    # keys 5 and 6 now live in the new file and lie deleted in the first
    second = _source([5, 6, 20, 700, 950])
    # the entry is advanced to the snapshot first, then broken
    MergeIntoCommand(log_a, second, "t.k = s.k", [UP], [INS], **ALIAS)
    tcols = ["k", "v", "tag"]
    _prebuild(log_a, "t.k = s.k", tcols, tcols)
    (_revive_deleted_rows if stale == "matched-a-deleted-row"
     else _shrink_a_file)(log_a)
    version = log_a.update().version
    n0, d0 = _counts()
    cmd_a = _run(log_a, second, "t.k = s.k", [UP], [INS], "force")
    assert _counts() == (n0 + 1, d0 + 1)
    assert log_a.update().version == version + 1  # one commit, the second run's
    assert cmd_a._join_path == "device-cold" and not cmd_a._pairs_only
    assert cmd_a.metrics["numTargetRowsUpdated"] == 4
    assert cmd_a.metrics["numTargetRowsInserted"] == 1
    cmd_b = _run(log_b, second, "t.k = s.k", [UP], [INS], "off")
    _same_outcome(log_a, log_b, cmd_a, cmd_b, order=False)


@pytest.mark.parametrize("update", ["assignments", "star"])
def test_a_slab_built_over_touched_files_only_is_not_the_tables(tmp_path,
                                                                update):
    """A slab built over only the files the source touches, registered as
    the table's, once answered a later insert-only MERGE for files it had
    never seen (duplicate rows). A cold build reads every file: whether the
    statement is pairs-only in shape (the star upsert) or not, the slab it
    registers is the table's, and the later insert agrees with the host. The
    row decode reads the one file the source touches, where the build's key
    tables do not serve it (the explicit assignment)."""
    log_a, log_b = _dv_tables(tmp_path)
    up = UP if update == "star" else MergeClause(
        "update", assignments={"v": "s.v", "tag": "s.tag"})
    telemetry.clear_events()
    _run(log_a, _source([10, 11]), "t.k = s.k", [up], [INS], "force")
    _run(log_b, _source([10, 11]), "t.k = s.k", [up], [INS], "off")
    decoded = [e.data["files"] for e in
               telemetry.recent_events("delta.dml.merge.rowDecode")]
    assert decoded == [8 if update == "star" else 1, 1]
    (slab,) = KeyCache.instance()._entries.values()
    assert len(slab.slabs) == 8 and slab.num_rows == 800
    again = _source([510, 511, 900])
    cmd_a = _run(log_a, again, "t.k = s.k", [], [INS], "force")
    cmd_b = _run(log_b, again, "t.k = s.k", [], [INS], "off")
    assert cmd_a.metrics["numTargetRowsInserted"] == 1
    assert _rows(log_a) == _rows(log_b)


# -- the vectors beside the write (ISSUE 39) ---------------------------------
#
# A MERGE that made both claimed rows to mark and rows to write runs its
# per-file vector jobs on worker threads while its own thread writes the data
# file, and joins them before it builds the commit. Which path runs follows
# from what the statement produced (`_writes_beside`; the tests patch that
# observable for the inline copy, never a conf).

OVERLAPPED = "merge.dv.overlapped"
DVS = "delta.dml.merge.deletionVectors"
COND = "t.k = s.k"


def _overlapped():
    return telemetry.counters("merge.dv").get(OVERLAPPED, 0)


@contextlib.contextmanager
def _inline_vectors(monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(MergeIntoCommand, "_writes_beside",
                  staticmethod(lambda claimed_tbl, out_blocks: False))
        yield


def _last_commit(log):
    """The last commit's file actions in their order: kind, the file (named
    by the commit that first added it and its place there, so that two
    copies compare) and its vector's cardinality."""
    from delta_tpu.protocol.actions import AddFile, FileAction

    label, last = {}, []
    for version, actions in log.get_changes(0):
        files = [a for a in actions if isinstance(a, FileAction)]
        for i, a in enumerate(a for a in files if isinstance(a, AddFile)
                              and a.path not in label):
            label[a.path] = f"v{version}#{i}"
        last = files
    return [(type(a).__name__, label[a.path],
             (getattr(a, "deletion_vector", None) or {}).get("cardinality"))
            for a in last]


def _data_files(log):
    import os

    return {os.path.join(d, f) for d, _, fs in os.walk(log.data_path)
            for f in fs if f.endswith(".parquet") and "_delta_log" not in d}


@pytest.mark.parametrize("mode", ["force", "off"])
def test_vectors_beside_the_write_commit_what_inline_commits(
        tmp_path, monkeypatch, mode):
    log_a, log_b = _dv_tables(tmp_path)
    tcols = ["k", "v", "tag"]
    if mode == "force":
        for log in (log_a, log_b):
            _prebuild(log, COND, tcols, tcols)
    # the second round unites with the first's vectors and probes its file
    for src in (_source([5, 6, 7, 650]),
                _source([5, 8, 150, 399, 400, 799, 900, 901])):
        c0 = _overlapped()
        telemetry.clear_events()
        cmd_a = _run(log_a, src, COND, [UP], [INS], mode)
        assert _overlapped() == c0 + 1
        [dv_a] = telemetry.recent_events(DVS)
        [root_a] = [e for e in telemetry.recent_events("delta.dml.merge")
                    if e.op_type == "delta.dml.merge"]
        assert dv_a.data["overlapped"] is True
        assert dv_a.thread_name.startswith("delta-merge-dv")
        assert dv_a.thread_id != root_a.thread_id
        telemetry.clear_events()
        with _inline_vectors(monkeypatch):
            cmd_b = _run(log_b, src, COND, [UP], [INS], mode)
        assert _overlapped() == c0 + 1
        [dv_b] = telemetry.recent_events(DVS)
        [root_b] = [e for e in telemetry.recent_events("delta.dml.merge")
                    if e.op_type == "delta.dml.merge"]
        assert dv_b.data["overlapped"] is False
        assert dv_b.thread_id == root_b.thread_id
        for key in ("files", "rows"):
            assert dv_a.data[key] == dv_b.data[key] > 0
        assert cmd_a._pairs_only == cmd_b._pairs_only == (mode == "force")
        # a file's id follows its (random) name, so the copies' new files
        # hold their rows, and their commits their files, in their own order
        _same_outcome(log_a, log_b, cmd_a, cmd_b, order=False)
        assert sorted(_last_commit(log_a), key=str) == sorted(
            _last_commit(log_b), key=str)
    for log in (log_a, log_b):
        # the removes, their re-adds in the same order, one data file
        last = _last_commit(log)
        assert [k for k, _, _ in last] == (
            ["RemoveFile"] * 6 + ["AddFile"] * 6 + ["AddFile"])
        files = [f for _, f, _ in last]
        assert files[:6] == files[6:12] and files[12] == "v2#0"
        assert {f: c for _, f, c in last[6:]} == {
            "v0#0": 4, "v1#0": 1, "v0#1": 1, "v0#3": 1, "v0#4": 1, "v0#7": 1,
            "v2#0": None}


@pytest.mark.parametrize("fault", ["stale-slab", "os-error",
                                   "os-error-and-the-write-fails",
                                   "the-write-fails-alone"])
def test_a_vector_job_that_raises_commits_nothing_and_leaves_no_data_file(
        tmp_path, monkeypatch, fault):
    """The vectors still decide: their error is the statement's, whatever
    the write did meanwhile, the data file the attempt wrote is taken off
    the directory, and nothing is committed."""
    import os

    from delta_tpu.commands import dml_common, merge as merge_mod

    log_a, _ = _dv_tables(tmp_path)
    tcols = ["k", "v", "tag"]
    _prebuild(log_a, COND, tcols, tcols)
    _run(log_a, _source([5, 6, 7, 650]), COND, [UP], [INS], "force")
    source = _source([5, 6, 20, 150, 700, 950])
    cmd = MergeIntoCommand(log_a, source, COND, [UP], [INS], **ALIAS)
    _prebuild(log_a, COND, tcols, tcols)  # advanced to the snapshot
    removed = []
    real_remove = MergeIntoCommand._remove_files
    monkeypatch.setattr(
        MergeIntoCommand, "_remove_files",
        lambda self, actions: (removed.extend(actions),
                               real_remove(self, actions))[1])
    if fault == "stale-slab":
        _revive_deleted_rows(log_a)
        error, text = merge_mod._StaleResidentSlab, "was deleted already"
    if fault.startswith("os-error"):
        real_mark, broken = dml_common.dv_mark_deleted, _file_of(log_a, 150)

        def mark(data_path, add, positions):
            if add.path == broken:
                raise OSError("no space left on the device")
            return real_mark(data_path, add, positions)

        monkeypatch.setattr(dml_common, "dv_mark_deleted", mark)
        error, text = OSError, "no space left"
    if fault.endswith("the-write-fails") or fault == "the-write-fails-alone":
        def write_files(*a, **k):
            raise RuntimeError("the encoder died")

        monkeypatch.setattr(merge_mod.write_exec, "write_files", write_files)
        if fault == "the-write-fails-alone":
            error, text = RuntimeError, "the encoder died"
    version, before, c0 = log_a.update().version, _data_files(log_a), _overlapped()
    with conf.set_temporarily(**{
            "delta.tpu.merge.devicePath.mode": "force",
            "delta.tpu.deletionVectors.enabled": True}):
        with pytest.raises(error, match=text) as raised:
            # one attempt: `run` would answer a stale slab with a second
            log_a.with_new_transaction(cmd._body)
    assert _overlapped() == c0 + 1
    assert log_a.update().version == version
    assert _data_files(log_a) == before
    if fault in ("stale-slab", "os-error"):
        [add] = removed  # the attempt had written its data file
        assert add.path.endswith(".parquet")
        assert not os.path.exists(os.path.join(log_a.data_path, add.path))
    else:
        assert removed == []
    if fault == "os-error-and-the-write-fails":
        assert "the encoder died" in str(raised.value.__context__)
    monkeypatch.undo()
    # the statement itself, afterwards: one commit, every row where it belongs
    cmd_a = _run(log_a, source, COND, [UP], [INS], "force")
    assert log_a.update().version == version + 1
    assert cmd_a.metrics["numTargetRowsUpdated"] == 5
    assert cmd_a.metrics["numTargetRowsInserted"] == 1
    assert len(_data_files(log_a)) == len(before) + 1


def test_spans_of_worker_threads_parent_under_the_merges_root(tmp_path):
    log_a, _ = _dv_tables(tmp_path)
    telemetry.clear_events()
    with conf.set_temporarily(**{"delta.tpu.write.targetFileRows": 3}):
        _run(log_a, _source([0, 3, 150, 399, 400, 799, 900, 901]), COND,
             [UP], [INS], "off")
    events = telemetry.recent_events()
    by_id = {e.span_id: e for e in events}
    [root] = [e for e in events if e.op_type == "delta.dml.merge"]
    [dv] = [e for e in events if e.op_type == DVS]
    [write] = [e for e in events if e.op_type == "delta.dml.merge.write"]
    [commit] = [e for e in events if e.op_type == "delta.commit"]
    assert dv.parent_id == write.parent_id == root.span_id
    assert write.thread_id == root.thread_id != dv.thread_id
    assert dv.data == {"files": 5, "rows": 6, "overlapped": True}
    assert dv.start_us + dv.duration_us <= commit.start_us + 1
    files = [e for e in events if e.op_type in ("delta.write.encode",
                                                "delta.write.stats")]
    assert len(files) == 2 * 3  # eight rows, three to a file
    for e in files:
        assert e.parent_id == write.span_id
        assert e.thread_name.startswith("delta-parquet-write")
    # no worker's span is an orphan root: each leads up to the MERGE's
    workers = [e for e in events if e.thread_name.startswith(
        ("delta-merge-dv", "delta-parquet-write"))]
    assert {e.op_type for e in workers} >= {DVS, "delta.write.encode"}
    for e in workers:
        while e.parent_id is not None and e is not root:
            e = by_id[e.parent_id]
        assert e is root


@pytest.mark.parametrize("mode", ["force", "off"])
@pytest.mark.parametrize("shape", ["rf1", "rf2"])
def test_a_merge_that_makes_vectors_or_rows_alone_opens_no_pool(
        tmp_path, shape, mode):
    """TPC-H's RF1 writes a file and no vector, RF2 a vector and no file:
    there is nothing to run beside, and today's inline path runs."""
    log_a, _ = _dv_tables(tmp_path)
    tcols = ["k", "v", "tag"]
    if mode == "force":
        _prebuild(log_a, COND, tcols, tcols)
    c0 = _overlapped()
    telemetry.clear_events()
    if shape == "rf1":
        cmd = _run(log_a, _source([5, 900, 901]), COND, [], [INS], mode)
        assert cmd.metrics["numTargetRowsInserted"] == 2
    else:
        cmd = _run(log_a, _source([5, 150, 151, 400, 900]), COND, [DEL], [],
                   mode)
        assert cmd.metrics["numTargetRowsDeleted"] == 4
        assert cmd.metrics["numTargetFilesAdded"] == 3  # the re-adds alone
    assert _overlapped() == c0
    events = telemetry.recent_events()
    [root] = [e for e in events if e.op_type == "delta.dml.merge"]
    assert not any(e.thread_name.startswith("delta-merge-dv") for e in events)
    dvs = [e for e in events if e.op_type == DVS]
    if shape == "rf1":
        assert dvs == []
    else:
        [dv] = dvs
        assert dv.data == {"files": 3, "rows": 4, "overlapped": False}
        assert dv.thread_id == root.thread_id
        assert dv.parent_id == root.span_id
