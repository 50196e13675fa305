"""Device ops: sharded replay kernel + data-skipping pruning.

The host `LogReplay` is the spec (PROTOCOL.md "Action Reconciliation");
the device kernel must compute identical alive/tombstone sets on random
action streams, single-device and sharded over the virtual 8-CPU mesh.
"""
import json
import random

import numpy as np
import pytest

from delta_tpu.log.replay import LogReplay
from delta_tpu.ops import pruning, replay_kernel, state_export
from delta_tpu.expr.parser import parse_predicate
from delta_tpu.parallel.mesh import state_mesh
from delta_tpu.protocol.actions import AddFile, Metadata, RemoveFile
from delta_tpu.schema.types import (
    DoubleType,
    IntegerType,
    LongType,
    StringType,
    StructType,
)


def _random_stream(seed, n_versions=40, n_paths=25):
    rng = random.Random(seed)
    versioned = []
    for v in range(n_versions):
        actions = []
        for _ in range(rng.randint(1, 6)):
            p = f"part-{rng.randrange(n_paths):05d}.parquet"
            if rng.random() < 0.7:
                actions.append(
                    AddFile(path=p, partition_values={}, size=rng.randrange(1, 1000),
                            modification_time=v, data_change=True)
                )
            else:
                actions.append(
                    RemoveFile(path=p, deletion_timestamp=v * 1000, data_change=True)
                )
        versioned.append((v, actions))
    return versioned


def _host_state(versioned, min_retention=0):
    replay = LogReplay(min_file_retention_timestamp=min_retention)
    for v, actions in versioned:
        replay.append(v, actions)
    alive = set(replay.active_files.keys())
    tombs = {r.path for r in replay.get_tombstones()}
    return alive, tombs


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_replay_kernel_matches_host(seed):
    versioned = _random_stream(seed)
    arrays = state_export.actions_to_arrays(versioned)
    result = replay_kernel.replay_alive_mask(arrays, min_retention_ts=0)
    alive_paths = {
        arrays.paths[arrays.path_id[i]]
        for i in range(arrays.num_rows)
        if bool(result.alive[i])
    }
    tomb_paths = {
        arrays.paths[arrays.path_id[i]]
        for i in range(arrays.num_rows)
        if bool(result.tombstone[i])
    }
    host_alive, host_tombs = _host_state(versioned)
    assert alive_paths == host_alive
    assert tomb_paths == host_tombs
    assert int(result.stats.num_files) == len(host_alive)


@pytest.mark.parametrize("seed", [0, 5])
def test_replay_sharded_matches_host(seed):
    versioned = _random_stream(seed, n_versions=60, n_paths=50)
    arrays = state_export.actions_to_arrays(versioned)
    mesh = state_mesh()
    result = replay_kernel.replay_sharded(arrays, mesh, min_retention_ts=0)
    alive_paths = {
        arrays.paths[arrays.path_id[i]]
        for i in range(arrays.num_rows)
        if bool(result.alive[i])
    }
    host_alive, _ = _host_state(versioned)
    assert alive_paths == host_alive
    assert int(result.stats.num_files) == len(host_alive)
    replay = LogReplay()
    for v, actions in versioned:
        replay.append(v, actions)
    assert int(result.stats.total_size) == sum(
        f.size for f in replay.active_files.values()
    )


def test_replay_tombstone_retention():
    versioned = [
        (0, [AddFile(path="a", partition_values={}, size=1, modification_time=0, data_change=True)]),
        (1, [RemoveFile(path="a", deletion_timestamp=500, data_change=True)]),
        (2, [AddFile(path="b", partition_values={}, size=2, modification_time=0, data_change=True)]),
    ]
    arrays = state_export.actions_to_arrays(versioned)
    kept = replay_kernel.replay_alive_mask(arrays, min_retention_ts=100)
    assert int(kept.stats.num_tombstones) == 1
    expired = replay_kernel.replay_alive_mask(arrays, min_retention_ts=1000)
    assert int(expired.stats.num_tombstones) == 0


# -- pruning ----------------------------------------------------------------

SCHEMA = (
    StructType()
    .add("id", LongType())
    .add("price", DoubleType())
    .add("name", StringType())
    .add("part", StringType())
)


def _meta():
    return Metadata(schema_string=SCHEMA.to_json(), partition_columns=["part"])


def _file(path, part, id_min, id_max, price_min, price_max, nulls_name=0, num=100):
    stats = {
        "numRecords": num,
        "minValues": {"id": id_min, "price": price_min, "name": "a"},
        "maxValues": {"id": id_max, "price": price_max, "name": "z"},
        "nullCount": {"id": 0, "price": 0, "name": nulls_name},
    }
    return AddFile(
        path=path,
        partition_values={"part": part},
        size=1000,
        modification_time=0,
        data_change=True,
        stats=json.dumps(stats),
    )


FILES = [
    _file("f1", "us", 0, 99, 1.0, 9.9),
    _file("f2", "us", 100, 199, 10.0, 19.9),
    _file("f3", "eu", 200, 299, 20.0, 29.9, nulls_name=100),
    _file("f4", "eu", 300, 399, 30.0, 39.9),
]


class _FakeSnapshot:
    version = 7
    all_files = FILES
    metadata = _meta()


def _scan(sql):
    return pruning.files_for_scan(_FakeSnapshot(), [parse_predicate(sql)])


def test_partition_pruning():
    scan = _scan("part = 'us'")
    assert [f.path for f in scan.files] == ["f1", "f2"]
    assert scan.partition.files == 2


def test_stats_eq_pruning():
    assert [f.path for f in _scan("id = 150").files] == ["f2"]


def test_stats_range_pruning():
    assert [f.path for f in _scan("price >= 25.0").files] == ["f3", "f4"]
    assert [f.path for f in _scan("id < 100").files] == ["f1"]


def test_stats_combined_partition_and_data():
    scan = _scan("part = 'eu' AND id <= 250")
    assert [f.path for f in scan.files] == ["f3"]


def test_stats_in_pruning():
    assert [f.path for f in _scan("id IN (5, 305)").files] == ["f1", "f4"]


def test_stats_null_count_pruning():
    assert [f.path for f in _scan("name IS NULL").files] == ["f3"]
    # f3 is all-null for name -> IS NOT NULL prunes it
    assert [f.path for f in _scan("name IS NOT NULL").files] == ["f1", "f2", "f4"]


def test_missing_stats_keeps_file():
    no_stats = AddFile(path="f5", partition_values={"part": "eu"}, size=10,
                       modification_time=0, data_change=True)

    class S:
        version = 1
        all_files = FILES + [no_stats]
        metadata = _meta()

    scan = pruning.files_for_scan(S(), [parse_predicate("id = 150")])
    assert [f.path for f in scan.files] == ["f2", "f5"]


def test_unsupported_predicate_keeps_all():
    scan = _scan("name LIKE '%x%'")
    assert len(scan.files) == 4


def test_string_stats_pruned_on_host():
    # string min/max can't ship to device; host Arrow path must still prune
    scan = _scan("name > 'zz'")
    assert scan.files == []


def test_startswith_pruning_astral_chars():
    # regression: prefix upper bound must cover code points above U+FFFF
    f = _file("fx", "us", 0, 9, 1.0, 2.0)
    st = json.loads(f.stats)
    st["minValues"]["name"] = st["maxValues"]["name"] = "ap\U0001F600"
    f = AddFile(path="fx", partition_values={"part": "us"}, size=1000,
                modification_time=0, data_change=True, stats=json.dumps(st))

    class S:
        version = 1
        all_files = [f]
        metadata = _meta()

    from delta_tpu.expr import ir
    scan = pruning.files_for_scan(
        S(), [ir.StartsWith(ir.Column("name"), ir.Literal("ap"))]
    )
    assert [x.path for x in scan.files] == ["fx"]
    scan2 = pruning.files_for_scan(
        S(), [ir.StartsWith(ir.Column("name"), ir.Literal("zz"))]
    )
    assert scan2.files == []


def test_int64_literal_falls_back_to_host():
    # regression: id > 2**31 must not crash scan planning
    scan = _scan("id > 2147483648")
    assert scan.files == []
    scan2 = _scan("id >= 2147483647")
    assert scan2.files == []


def test_null_partition_value_pruned():
    # a NULL partition verdict is constant for the file: prune strictly
    f = AddFile(path="fnull", partition_values={"part": None}, size=1,
                modification_time=0, data_change=True)

    class S:
        version = 1
        all_files = FILES + [f]
        metadata = _meta()

    scan = pruning.files_for_scan(S(), [parse_predicate("part = 'us'")])
    assert [x.path for x in scan.files] == ["f1", "f2"]


def test_mixed_partition_data_or_predicate():
    # regression: partition col inside an OR with a data col must not crash
    scan = _scan("part = 'us' OR id > 350")
    assert [f.path for f in scan.files] == ["f1", "f2", "f4"]


def test_int64_stats_beyond_float53_kept():
    # regression: int stats beyond 2^53 must not be pruned on rounded bounds
    big = 2**53
    f = _file("fbig", "us", 0, 0, 1.0, 2.0)
    st = json.loads(f.stats)
    st["minValues"]["id"] = big
    st["maxValues"]["id"] = big + 1
    f = AddFile(path="fbig", partition_values={"part": "us"}, size=1,
                modification_time=0, data_change=True, stats=json.dumps(st))

    class S:
        version = 1
        all_files = [f]
        metadata = _meta()

    scan = pruning.files_for_scan(S(), [parse_predicate(f"id > {big}")])
    assert [x.path for x in scan.files] == ["fbig"]


def test_prefix_upper_bound_surrogates():
    from delta_tpu.ops.pruning import _prefix_upper_bound

    assert _prefix_upper_bound("퟿") == ""
    assert _prefix_upper_bound("a") == "b"
    assert _prefix_upper_bound("a\U0010FFFF") == "b"
    assert _prefix_upper_bound("\U0010FFFF") is None


def test_sharded_replay_1m_actions_matches_host():
    """Scale test: 1M actions over the 8-device mesh; sharded result must
    equal the host reference replay exactly, with no per-shard Python loops
    in the bucketing/unscatter path (they are one argsort + scatters now)."""
    import time

    import numpy as np

    from delta_tpu.ops import replay_kernel
    from delta_tpu.ops.state_export import ReplayArrays
    from delta_tpu.parallel.mesh import state_mesh

    n = 1_000_000
    n_paths = 120_000
    rng = np.random.RandomState(13)
    path_id = rng.randint(0, n_paths, n).astype(np.int32)
    version = np.sort(rng.randint(0, 50_000, n).astype(np.int64))
    pos = np.arange(n, dtype=np.int64) % (1 << 20)
    seq = (version << 31) | pos
    is_add = rng.rand(n) < 0.8
    size = rng.randint(1, 1 << 20, n).astype(np.int64)
    del_ts = np.where(is_add, 0, 1 + version).astype(np.int64)
    arrays = ReplayArrays(
        paths=[], path_id=path_id, seq=seq, is_add=is_add, size=size,
        deletion_timestamp=del_ts,
    )

    # host reference: last action per path wins
    last = {}
    order = np.argsort(seq, kind="stable")
    for i in order:
        last[path_id[i]] = i
    expected_alive = np.zeros(n, bool)
    for p, i in last.items():
        if is_add[i]:
            expected_alive[i] = True

    t0 = time.perf_counter()
    res = replay_kernel.replay_sharded(arrays, state_mesh(), min_retention_ts=0)
    sharded_s = time.perf_counter() - t0
    got = np.asarray(res.alive)
    assert (got == expected_alive).all()
    assert int(res.stats.num_files) == int(expected_alive.sum())
    # tombstones: winning removes with deletion_ts > retention
    assert int(res.stats.num_tombstones) == sum(
        1 for p, i in last.items() if not is_add[i] and del_ts[i] > 0
    )
    print(f"sharded 1M replay: {sharded_s*1000:.0f}ms")


def _prune_tier(sql, files=FILES):
    """`prune_files` through the device tier (minFiles=1): kept paths and
    the tier the delta.scan.prune span names."""
    from delta_tpu.utils import telemetry
    from delta_tpu.utils.config import conf

    with conf.set_temporarily(**{"delta.tpu.device.pruning.minFiles": 1}), \
            telemetry.record_operation("delta.scan.prune") as ev:
        kept = pruning.prune_files(files, _meta(), [parse_predicate(sql)])
    return [f.path for f in kept], ev.data.get("tier")


@pytest.mark.parametrize("sql,kept,tier", [
    ("id = 150", ["f2"], "device"),
    ("price >= 25.0", ["f3", "f4"], "device"),
    ("price > 19.9", ["f3", "f4"], "device"),
    ("id IN (5, 305)", ["f1", "f4"], "device"),
    ("id < 100 OR price > 30.5", ["f1", "f4"], "device"),
    ("NOT (id >= 100)", ["f1"], "device"),
    ("id > 2147483648", [], "device"),
    # no exact device form / no lane: designed declines, the host serves
    ("id * price > 11000", ["f4"], "host"),
    ("name > 'zz'", [], "host"),
    ("name IS NULL", ["f3"], "host"),
    ("id > 9007199254740993", [], "host"),
])
def test_device_prune_tier_matches_the_host(sql, kept, tier):
    assert _prune_tier(sql) == (kept, tier)
    assert [f.path for f in pruning.prune_files(
        FILES, _meta(), [parse_predicate(sql)])] == kept  # host tier


def test_device_prune_tier_is_exact_on_float_bounds():
    """The min/max lanes ride as int64 order keys: a strict bound one ulp
    from a stat, and stats beyond float32's range (what a TPU's float64
    holds), prune exactly as the host does."""
    lo, hi = float(np.nextafter(0.1, 0)), float(np.nextafter(0.1, 1))
    files = [_file("a", "us", 0, 9, lo, 0.1), _file("b", "us", 0, 9, 0.1, hi),
             _file("c", "us", 0, 9, 1e300, 1.5e300),
             _file("d", "us", 0, 9, -1e-300, 1e-300)]
    for sql, kept in [("price > 0.1", ["b", "c"]), ("price < 0.1", ["a", "d"]),
                      (f"price >= {hi!r}", ["b", "c"]),
                      ("price > 1.2e300", ["c"]), ("price < 1e300", ["a", "b", "d"]),
                      ("price > 0 AND price < 1e-299", ["d"])]:
        assert _prune_tier(sql, files) == (kept, "device"), sql


def test_device_prune_failure_is_counted_with_its_text(monkeypatch):
    from delta_tpu.utils import telemetry

    def refuse(_pred):
        raise RuntimeError("chip says no")

    monkeypatch.setattr(pruning, "_compiled_skipping", refuse)
    before = telemetry.counters().get("scan.prune.deviceFallback", 0)
    from delta_tpu.utils.config import conf

    with conf.set_temporarily(**{"delta.tpu.device.pruning.minFiles": 1}), \
            telemetry.record_operation("delta.scan.prune") as ev:
        kept = pruning.prune_files(FILES, _meta(),
                                   [parse_predicate("price > 19.9")])
    assert [f.path for f in kept] == ["f3", "f4"]
    assert ev.data["tier"] == "host"
    assert ev.data["deviceError"] == "RuntimeError: chip says no"
    assert telemetry.counters()["scan.prune.deviceFallback"] == before + 1


def test_morton_order_matches_numpy_interleave():
    """The device bit-interleave against a plain numpy Morton key."""
    from delta_tpu.ops import zorder

    rng = np.random.RandomState(9)
    cols = [rng.randint(0, 1 << 40, 5000).astype(np.int64),
            rng.rand(5000), rng.randint(0, 7, 5000)]
    ranks = [zorder.rank_u16(c).astype(np.uint64) for c in cols]
    key = np.zeros(5000, np.uint64)
    for b in range(16):
        for c, r in enumerate(ranks):
            key |= ((r >> np.uint64(b)) & np.uint64(1)) << np.uint64(b * 3 + c)
    assert np.array_equal(zorder.morton_order(cols),
                          np.argsort(key, kind="stable"))
