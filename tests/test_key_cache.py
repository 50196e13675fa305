"""HBM-resident MERGE join keys (`ops/key_cache.py`): build/advance
lifecycle, deletion-vector validity (grow, shrink, re-add), probe parity
with the host join, and the resident path wired through MergeIntoCommand
(forced mode; parity against the host-pinned merge on a table copy)."""
import shutil

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.merge import MergeClause, MergeIntoCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.expr import ir
from delta_tpu.ops.key_cache import KeyCache, _pack_lanes
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_cache():
    KeyCache.reset()
    yield
    KeyCache.reset()


KEY_EXPRS = (ir.Column("k"),)
SIG = "test-k"


def _mk_table(path, lo=0, hi=200, files=4):
    log = DeltaLog.for_table(path)
    per = (hi - lo) // files
    rng = np.random.RandomState(5)
    for i in range(files):
        keys = np.arange(lo + i * per, lo + (i + 1) * per, dtype=np.int64)
        WriteIntoDelta(log, "append", pa.table({
            "k": keys, "v": rng.rand(per),
        })).run()
    return log


def _entry(log, **kw):
    snap = log.update()
    return KeyCache.instance().get(
        snap, SIG, ["k"], list(KEY_EXPRS), **kw)


def _source(keys, vals=None):
    keys = np.asarray(keys, np.int64)
    return pa.table({
        "k": keys,
        "v": np.asarray(vals if vals is not None else np.zeros(len(keys))),
    })


def _merge(log, source, mode="force"):
    with conf.set_temporarily(**{
        "delta.tpu.merge.devicePath.mode": mode,
        "delta.tpu.deletionVectors.enabled": True,
    }):
        cmd = MergeIntoCommand(
            log, source, "t.k = s.k",
            [MergeClause("update", assignments=None)],
            [MergeClause("insert", assignments=None)],
            source_alias="s", target_alias="t",
        )
        cmd.run()
    return cmd


# -- entry lifecycle --------------------------------------------------------


def test_build_and_probe_matches_membership(tmp_table):
    log = _mk_table(tmp_table)
    e = _entry(log)
    assert e is not None and e.num_rows == 200
    probe = e.probe_async(np.array([5, 150, 500], np.int64),
                          np.array([True, True, True]))
    res = probe.result()
    assert res.s_matched.tolist() == [True, True, False]
    assert res.t_bits.sum() == 2
    assert not res.any_multi


def test_probe_null_keys_never_match(tmp_table):
    log = _mk_table(tmp_table)
    e = _entry(log)
    res = e.probe_async(np.array([5, 0], np.int64),
                        np.array([True, False])).result()
    assert res.s_matched.tolist() == [True, False]


def test_tail_advance_append_and_remove(tmp_table):
    from delta_tpu.commands.delete import DeleteCommand

    log = _mk_table(tmp_table)
    e1 = _entry(log)
    v1 = e1.version
    # append a new file
    WriteIntoDelta(log, "append", pa.table({
        "k": np.arange(500, 550, dtype=np.int64), "v": np.zeros(50)})).run()
    # delete a whole file's rows (file removal, no DV since whole-file)
    e2 = _entry(log)
    assert e2 is e1 and e2.version > v1
    res = e2.probe_async(np.array([510], np.int64), np.array([True])).result()
    assert res.s_matched.tolist() == [True]


def test_dv_deleted_rows_do_not_match(tmp_table):
    """A row logically deleted via deletion vector must not count as a
    match — else its key's NOT MATCHED insert would be skipped. (The table
    property must be on BEFORE the entry builds: a rewrite-path delete
    would instead bump the key-cache epoch and force a rebuild.)"""
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.delete import DeleteCommand

    log = _mk_table(tmp_table)
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    e = _entry(log)
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        DeleteCommand(log, "k = 42").run()
    e2 = _entry(log)
    assert e2 is e
    res = e2.probe_async(np.array([42, 43], np.int64),
                         np.array([True, True])).result()
    assert res.s_matched.tolist() == [False, True]


def test_dv_shrink_revives_rows(tmp_table):
    """_set_dv recomputes validity exactly: removing the DV (RESTORE shape)
    brings rows back."""
    log = _mk_table(tmp_table, files=1)
    e = _entry(log)
    path = next(iter(e.slabs))
    e.ensure_resident()
    e._set_dv(path, np.array([3, 7], np.int64))
    res = e.probe_async(np.array([3], np.int64), np.array([True])).result()
    assert res.s_matched.tolist() == [False]
    e._set_dv(path, np.empty(0, np.int64))
    res = e.probe_async(np.array([3], np.int64), np.array([True])).result()
    assert res.s_matched.tolist() == [True]


def test_probe_sorted_kernel_fuzz_parity():
    """Direct slab fuzz of the sorted-slab probe kernel vs a numpy oracle:
    random keys with duplicates, kills, DV masks, null source rows — and
    both coarse-fine download paths (sparse hot blocks -> device gather;
    dense -> full live-prefix fetch)."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys

    rng = np.random.RandomState(7)
    n = 20000  # capacity 32768 -> 8 blocks of 4096
    keys = rng.randint(0, 15000, n).astype(np.int64)  # dense duplicates
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    half = n // 2
    e._append_file("f1", keys[:half], np.ones(half, bool))
    e._append_file("f2", keys[half:], np.ones(n - half, bool))
    # DV-mask some of f2, kill nothing (validity path)
    dv_pos = rng.choice(n - half, 500, replace=False).astype(np.int64)
    assert e._set_dv("f2", dv_pos)
    valid = np.ones(n, bool)
    valid[half + dv_pos] = False

    for label, s_keys, s_ok in [
        ("sparse", np.arange(100, 200, dtype=np.int64),
         np.ones(100, bool)),  # clusters into few blocks
        ("dense", rng.randint(0, 15000, 3000).astype(np.int64),
         rng.rand(3000) > 0.1),
        ("misses", np.arange(100000, 100050, dtype=np.int64),
         np.ones(50, bool)),
    ]:
        res = e.probe_async(s_keys, s_ok).result()
        valid_keys = set(keys[valid].tolist())
        exp_s = np.array([ok and (k in valid_keys)
                          for k, ok in zip(s_keys.tolist(), s_ok)], bool)
        src_member = set(s_keys[exp_s].tolist())
        exp_t = np.array([v and (k in src_member)
                          for k, v in zip(keys.tolist(), valid)], bool)
        assert (res.s_matched == exp_s).all(), label
        assert (res.t_bits == exp_t).all(), label
        # multi: some valid slab row matched by >=2 source rows
        matched_counts = {}
        for k, ok in zip(s_keys[s_ok & exp_s].tolist(), [1] * int(exp_s.sum())):
            matched_counts[k] = matched_counts.get(k, 0) + 1
        exp_multi = any(c >= 2 for c in matched_counts.values())
        assert res.any_multi == exp_multi, label


def _oracle(keys, valid, s_keys, s_ok):
    """The host join's answer in numpy: (s_matched, any_multi, physical rows
    ascending, each with the minimal original source row of its key)."""
    keys, s_keys = np.asarray(keys, np.int64), np.asarray(s_keys, np.int64)
    ok_rows = np.nonzero(s_ok)[0]
    order = ok_rows[np.argsort(s_keys[ok_rows], kind="stable")]
    uniq, first, count = np.unique(s_keys[order], return_index=True,
                                   return_counts=True)
    s_matched = s_ok & np.isin(s_keys, keys[valid])
    phys = np.nonzero(valid & np.isin(keys, uniq))[0]
    at = np.searchsorted(uniq, keys[phys])
    any_multi = bool((count[at] > 1).any())
    return s_matched, any_multi, phys, order[first][at]


def _wide(rng, n):
    """Keys that need int64, with duplicates among them."""
    return (rng.randint(0, n // 3, n).astype(np.int64) << 33) + 7


def _probe_case(case):
    """(slab keys, dead rows, source keys, source ok, insert_only)."""
    rng = np.random.RandomState(11)
    n = 20000
    keys = rng.permutation(n).astype(np.int64) * 3
    dead = np.empty(0, np.int64)
    s = rng.choice(keys, 300, replace=False)
    s_ok = np.ones(300, bool)
    if case == "long-runs":
        # three keys held 3, 700 and 5,000 times: runs longer than a block,
        # over several block edges
        keys[:3], keys[3:703], keys[703:5703] = 60001, 60004, 60007
        s = np.array([60007, 60001, 5, 60004, 60010], np.int64)
    elif case == "dead-and-live":
        # four versions of 200 keys, the live one in a place of its own; 50
        # keys all of whose versions are dead
        keys[:800] = np.repeat(keys[1000:1200], 4)
        dead = np.concatenate([np.arange(800)[np.arange(800) % 4 != 2],
                               np.arange(1000, 1250)])
        s = keys[1000:1300].copy()
    elif case == "source-duplicates":
        s = np.concatenate([s[:100], s[:50], s[:10], s[:100] + 1])
    elif case == "all-miss-above":
        s = keys.max() + 1 + np.arange(300, dtype=np.int64)
    elif case == "all-miss-below":
        s = -1 - np.arange(300, dtype=np.int64)
    elif case == "beyond-int32":
        keys = _wide(rng, n)
        s = np.concatenate([keys[:200], keys[:100] + 1])
    elif case == "beyond-int32-negative":
        keys = -_wide(rng, n)
        s = np.concatenate([keys[:200], keys[:100] + 1])
    elif case == "not-ok-rows":
        s_ok = rng.rand(300) > 0.3
    elif case == "one-row":
        s = keys[77:78].copy()
    elif case == "one-row-miss":
        s = np.array([1], np.int64)
    elif case == "odd-m":
        s = np.concatenate([rng.choice(keys, 700, replace=False),
                            rng.randint(0, 3 * n, 637).astype(np.int64)])
    elif case == "quarter-of-the-slab":
        # two chunks of gathered blocks; every other source key a miss
        n = 1_000_000
        keys = rng.permutation(n).astype(np.int64) * 2
        keys[:40000] = keys[40000:80000]
        dead = np.arange(0, 60000, 3)
        s = rng.permutation(2 * n)[:250_000].astype(np.int64)
    elif case == "insert-only":
        s = np.concatenate([s, s[:5] + 1])
    elif case == "block-edges":
        # every key twice, so a run lies over every block's edge
        keys = np.repeat(np.arange(1, n + 1, 2, dtype=np.int64), 2)[1:]
        keys = np.append(keys, 0)
        s = np.arange(0, n, 7, dtype=np.int64)
    elif case == "whole-slab-one-key":
        keys[:] = 5
        dead = np.arange(0, n, 2)
        s = np.array([4, 5, 6, 5], np.int64)
    if len(s_ok) != len(s):
        s_ok = np.ones(len(s), bool)
    return keys, dead, s, s_ok, case == "insert-only"


PROBE_CASES = [
    "long-runs", "dead-and-live", "source-duplicates", "all-miss-above",
    "all-miss-below", "beyond-int32", "beyond-int32-negative", "not-ok-rows",
    "one-row", "one-row-miss", "odd-m", "quarter-of-the-slab", "insert-only",
    "block-edges", "whole-slab-one-key", "empty-slab",
]


@pytest.mark.parametrize("case", PROBE_CASES)
def test_probe_equals_the_numpy_oracle(case):
    """`probe_async(...).result()` against the host join's answer: the
    matched flags of every source row, `any_multi`, every valid slab row
    whose key is in the valid source as a pair with the minimal original
    source row, physical rows ascending, and the bits made from the pairs."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys

    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    if case == "empty-slab":
        res = e.probe_async(np.array([3, 4], np.int64), np.ones(2, bool)).result()
        assert res.s_matched.tolist() == [False, False] and not res.any_multi
        assert len(res.t_pairs[0]) == 0 and len(res.t_bits) == 0
        return
    keys, dead, s, s_ok, insert_only = _probe_case(case)
    n, half = len(keys), len(keys) // 2
    e._append_file("f1", keys[:half], np.ones(half, bool))
    e._append_file("f2", keys[half:], np.ones(n - half, bool))
    e.ensure_resident()
    assert e._set_dv("f1", dead[dead < half])
    assert e._set_dv("f2", dead[dead >= half] - half)
    valid = np.ones(n, bool)
    valid[dead] = False
    res = e.probe_async(s, s_ok, insert_only=insert_only).result()
    exp_s, exp_multi, exp_phys, exp_src = _oracle(keys, valid, s, s_ok)
    assert (res.s_matched == exp_s).all()
    assert res.any_multi == exp_multi
    assert res.num_rows == n
    if insert_only:
        assert res.t_pairs is None and res.t_bits is None
        return
    assert (res.t_pairs[0] == exp_phys).all()
    assert (res.t_pairs[1] == exp_src).all()
    assert (np.nonzero(res.t_bits)[0] == exp_phys).all()


def test_probe_declines_counted_when_candidates_pass_the_scratch(monkeypatch):
    """The one decline the probe keeps: more candidate slab rows than the
    pair kernel's scratch may hold raise `DeltaProbeOverflow` (the caller
    joins on the host) and are counted; an insert-only probe, which makes
    no pairs, is served all the same. The span says how widely the probe
    engaged."""
    from delta_tpu.ops import key_cache as kc
    from delta_tpu.utils import telemetry

    e = kc.ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("f", np.arange(5000, dtype=np.int64), np.ones(5000, bool))
    s, ok = np.arange(4000, 4600, dtype=np.int64), np.ones(600, bool)
    res = e.probe_async(s, ok).result()
    assert len(res.t_pairs[0]) == 600
    data = telemetry.recent_events("delta.merge.deviceProbe")[-1].data
    assert data["matched"] == 600 and data["candidateRows"] == 600
    tail = kc._tail_capacity(e.capacity)
    assert data["blockRows"] == kc._probe_block(e.capacity, 1024)
    assert data["tailBlockRows"] == kc._probe_block(tail, 1024)
    assert data["candidates"] == (
        (1024 + e.capacity // data["blockRows"]) * data["blockRows"]
        + (1024 + tail // data["tailBlockRows"]) * data["tailBlockRows"])
    monkeypatch.setattr(kc, "_PROBE_SCRATCH_BYTES", 16 * 2048 - 1)
    before = telemetry.counters("merge.resident.probe").get(
        "merge.resident.probe.overflow", 0)
    with pytest.raises(kc.DeltaProbeOverflow):
        e.probe_async(s, ok).result()
    assert telemetry.counters("merge.resident.probe")[
        "merge.resident.probe.overflow"] == before + 1
    res = e.probe_async(s, ok, insert_only=True).result()
    assert res.s_matched.all() and res.t_pairs is None
    assert len(e.probe_async(s[:100], ok[:100]).result().t_pairs[0]) == 100


def test_probe_many_above_max_misses_no_overflow():
    """Source keys above the slab maximum (inserts) fall into NO block's
    candidate window — the padding tail must not swallow them into the
    boundary block and trip the overflow tiers."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys

    n = 20000
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("f", np.arange(n, dtype=np.int64) * 2, np.ones(n, bool))
    s = np.concatenate([
        np.arange(50000, 60000, dtype=np.int64),  # 10k above-max misses
        np.array([10, 20], np.int64),
    ])
    res = e.probe_async(s, np.ones(len(s), bool)).result()
    assert res.s_matched[-2:].tolist() == [True, True]
    assert not res.s_matched[:-2].any()
    assert res.t_bits.sum() == 2


def test_probe_after_kill_and_append_resorts(tmp_table):
    """A key append leaves the tail of the sorted view behind the lanes, and
    the big run live; kills leave neither behind. Both must still probe
    correctly afterwards."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys

    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("a", np.array([10, 20, 30], np.int64), np.ones(3, bool))
    e.ensure_resident()
    r = e.probe_async(np.array([20], np.int64), np.array([True])).result()
    assert r.s_matched.tolist() == [True]
    assert e._stale is None and e._sorted_n == 3
    e._kill_file("a")  # validity flip only: no resort needed
    assert e._stale is None
    r = e.probe_async(np.array([20], np.int64), np.array([True])).result()
    assert r.s_matched.tolist() == [False]
    e._append_file("b", np.array([40, 20], np.int64), np.ones(2, bool))
    assert e._stale == "tail"  # key rows changed, after the big run's
    r = e.probe_async(np.array([20, 10, 40], np.int64),
                      np.ones(3, bool)).result()
    assert r.s_matched.tolist() == [True, False, True]
    assert e._stale is None and e._sorted_n == 3


# what a slab holds on the device once a probe has sorted it
_BOTH_RUNS = {"keys", "valid", "sorted_keys", "perm", "sorted_valid",
              "tail_keys", "tail_perm", "tail_valid"}

SORT_CASES = [
    "duplicates-valid-and-dead", "padding", "int64-max-key", "all-invalid",
    "one-row", "narrowed-to-int32", "full-capacity",
]


def _sort_case(case):
    """(keys, dead rows) of one slab of capacity 1024."""
    rng = np.random.RandomState(13)
    n = 700
    keys = (rng.randint(0, 200, n).astype(np.int64) << 33) - 5  # duplicates
    dead = rng.choice(n, 150, replace=False)
    if case == "padding":
        # rows past n hold key 0 on the device and belong at the tail: real
        # keys on both sides of it
        keys = np.concatenate([keys[:100], -keys[:100]])
        dead = np.empty(0, np.int64)
    elif case == "int64-max-key":
        # shares the padding's run; one of the three is dead
        keys[[3, 300, 699]] = np.iinfo(np.int64).max
        dead = np.append(dead[(dead != 3) & (dead != 699)], 300)
    elif case == "all-invalid":
        dead = np.arange(n)
    elif case == "one-row":
        keys, dead = keys[:1], np.empty(0, np.int64)
    elif case == "narrowed-to-int32":
        keys = rng.randint(-50, 50, n).astype(np.int64)
    elif case == "full-capacity":
        keys = np.resize(keys, 1024)
    return keys, np.asarray(dead, np.int64)


@pytest.mark.parametrize("case", SORT_CASES)
def test_sort_equals_the_numpy_oracle(case):
    """The whole slab's sort against numpy: the big run's `perm` is the
    stable argsort of the encoded keys (padding as int64.max; ties in
    physical-row order, valid or dead), `sorted_keys` the encoded keys
    through it, `sorted_valid` the live rows through it; the tail it leaves
    is all padding — and nothing else is resident."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys, _tail_capacity

    keys, dead = _sort_case(case)
    n = len(keys)
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("f", keys, np.ones(n, bool))
    assert e._set_dv("f", dead)
    e.ensure_resident()
    e._ensure_sorted()
    cap = e.capacity
    assert e._dev["keys"].dtype == np.int64 and e._dev["keys"].shape == (cap,)
    enc = np.full(cap, np.iinfo(np.int64).max)
    enc[:n] = keys
    live = np.zeros(cap, bool)
    live[:n] = True
    live[dead] = False
    perm = np.argsort(enc, kind="stable")
    assert np.asarray(e._dev["perm"]).dtype == np.int32
    assert (np.asarray(e._dev["perm"]) == perm).all()
    assert (np.asarray(e._dev["sorted_keys"]) == enc[perm]).all()
    assert (np.asarray(e._dev["sorted_valid"]) == live[perm]).all()
    assert set(e._dev) == _BOTH_RUNS
    tail = _tail_capacity(cap)
    assert e._dev["tail_keys"].shape == e._dev["tail_perm"].shape == (tail,)
    assert (np.asarray(e._dev["tail_keys"]) == np.iinfo(np.int64).max).all()
    assert not np.asarray(e._dev["tail_valid"]).any()
    assert e._sorted_n == n and e._stale is None
    assert e.device_bytes == 22 * cap + 13 * tail


def test_slab_capacity_leaves_the_payload_its_validity_bit():
    """Row ids share their int32 with the validity bit in the sort."""
    from delta_tpu.ops.key_cache import _slab_capacity

    assert _slab_capacity(1) == 1024
    assert _slab_capacity(28_800_991) == 29_360_128
    assert _slab_capacity(1 << 30) == 1 << 30
    with pytest.raises(AssertionError, match="30 bits"):
        _slab_capacity((1 << 30) + 1)


def _flip_counts():
    """(flips mirrored in a live sorted view by search, flips that dropped
    the view instead), the process's counts so far."""
    from delta_tpu.utils import telemetry

    c = telemetry.counters("merge.keyCache")
    return (c.get("merge.keyCache.flipSearches", 0),
            c.get("merge.keyCache.flipResorts", 0))


def _probe_bits(e, keys):
    return e.probe_async(np.asarray(keys, np.int64),
                         np.ones(len(keys), bool)).result().s_matched.tolist()


LOCATE_CASES = [
    "unique-keys", "runs-of-1-to-7", "run-longer-than-a-probe-block",
    "every-key-equal", "int64-max-beside-the-padding", "dead-rows-in-runs",
    "rows-padded-with-cap", "length-3-times-a-power-of-two",
    "length-1000-full", "length-of-the-refresh-slab-over-2-to-the-15th",
    "length-of-the-refresh-slab-over-2-to-the-11th", "one-row",
]


def _locate_case(case):
    """(keys and validity of a slab's ``cap`` device rows, its row count,
    the rows to find, padded as a flip pads them)."""
    rng = np.random.RandomState(len(case))
    cap, n = 1024, 700
    if case == "length-3-times-a-power-of-two":
        cap, n = 1536, 1100
    elif case == "length-1000-full":
        cap, n = 1000, 1000
    elif case == "length-of-the-refresh-slab-over-2-to-the-15th":
        cap, n = 60_817_408 >> 15, 1800  # 1,856 = 2^6 x 29: pads a node
    elif case == "length-of-the-refresh-slab-over-2-to-the-11th":
        # 29,696 rows: two levels of nodes, the upper one padded (232 of
        # 256), under a top of two entries; the slab's own tree has three
        cap, n = 60_817_408 >> 11, 29_000
    elif case == "run-longer-than-a-probe-block":
        cap, n = 4096, 3000
    elif case == "one-row":
        n = 1
    keys = np.repeat(rng.permutation(n).astype(np.int64) * 5 - 900,
                     rng.randint(1, 8, n))[:n]  # runs of 1-7, in row order
    keys = keys[rng.permutation(n)]
    valid = np.ones(n, bool)
    if case == "unique-keys":
        keys = rng.permutation(n).astype(np.int64) << 34
    elif case == "run-longer-than-a-probe-block":
        keys[rng.choice(n, 1500, replace=False)] = 77  # blocks reach 1,024
    elif case == "every-key-equal":
        keys[:] = -3
    elif case == "int64-max-beside-the-padding":
        keys[[0, 300, n - 1]] = np.iinfo(np.int64).max
    if case in ("dead-rows-in-runs", "int64-max-beside-the-padding"):
        valid[rng.choice(n, n // 3, replace=False)] = False
        valid[300] = False
    k = min(n, 200)
    rows = rng.choice(n, k, replace=False).astype(np.int32)
    if case == "int64-max-beside-the-padding":
        rows[:3] = [0, 300, n - 1]
        rows = np.unique(rows)
    d = 256
    if case == "rows-padded-with-cap":
        rows = rows[:70]  # 186 of the 256 are padding
    dev_keys, dev_valid = np.zeros(cap, np.int64), np.zeros(cap, bool)
    dev_keys[:n], dev_valid[:n] = keys, valid
    padded = np.full(d, cap, np.int32)
    padded[:len(rows)] = rows
    return dev_keys, dev_valid, n, padded


@pytest.mark.parametrize("case", LOCATE_CASES)
def test_inverse_permutation_at_equals_the_numpy_oracle(case):
    """The search that a flip on a live sorted view runs, handed the
    sort's arrays directly (so a length need be no power of two, as the
    refresh slab's 60,817,408 is none): every real row's position is
    `argsort(perm)[row]`, valid or dead, alone or inside a run of equal
    keys; a padding row maps to the capacity, which a scatter drops."""
    from delta_tpu.ops.key_cache import _inverse_permutation_at, _sort_kernel
    from delta_tpu.utils.jaxcompat import enable_x64

    keys, valid, n, rows = _locate_case(case)
    cap = len(keys)
    with enable_x64():
        sk, pm, sv = _sort_kernel()(
            keys, valid, np.array([0, 0, n], np.int32), cap)
        got = np.asarray(_inverse_permutation_at()(sk, pm, keys, rows))
    assert got.dtype == np.int32 and got.shape == rows.shape
    real = rows < cap
    assert real.any() and (rows[real] < n).all()
    want = np.argsort(np.asarray(pm))
    assert (got[real] == want[rows[real]]).all()
    assert (got[~real] == cap).all()
    # what the flip relies on: the rows stand where the search says
    assert (np.asarray(pm)[got[real]] == rows[real]).all()
    assert (np.asarray(sv)[got[real]] == valid[rows[real]]).all()


def _sort_counts():
    """(sorts of the tail alone, folds of a full tail into the big run), the
    process's counts so far."""
    from delta_tpu.utils import telemetry

    c = telemetry.counters("merge.keyCache")
    return (c.get("merge.keyCache.tailSorts", 0),
            c.get("merge.keyCache.folds", 0))


def _sorts(telemetry):
    return [(ev.data["rows"], ev.data["tier"], ev.data["cause"])
            for ev in telemetry.recent_events("delta.keyCache.sort")]


def test_a_flip_searches_the_big_run_and_never_the_tail():
    """(a) the advance's shape, an append that fits the tail and a kill of
    the big run's rows in one batch: the tail lags, the big run stays live
    and is searched for the kill, and the next probe sorts the tail alone.
    (b) a kill of the tail's rows searches nothing: the tail lags and its
    sort carries them. (c) a kill and a revive in the big run each search it
    once, one flip over both runs searches for the big run's rows only, and
    the probe reads them all back. (d) an append the tail has no room for
    drops both runs, a flip then is a row-space flip, and one sort of the
    whole slab (the fold) follows; through all of it the accounts read 22 B
    a row and 13 B a row of the tail."""
    from delta_tpu.obs import hbm_ledger
    from delta_tpu.ops.key_cache import ResidentJoinKeys, _tail_capacity
    from delta_tpu.utils import telemetry

    hbm_ledger.reset()
    telemetry.clear_events()
    rng = np.random.RandomState(17)
    a = rng.permutation(600).astype(np.int64)
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("a", a, np.ones(600, bool))
    e.ensure_resident()
    cap = e.capacity
    held = 22 * cap + 13 * _tail_capacity(cap)
    assert _tail_capacity(cap) == 64

    def accounts():
        return e.device_bytes, hbm_ledger.totals()["keyCache"]

    assert accounts() == (held, held)
    assert _probe_bits(e, [a[5], 1000]) == [True, False]
    assert e._stale is None and e._sorted_n == 600
    assert _sorts(telemetry) == [(600, "all", "append")]
    flips, sorts = _flip_counts(), _sort_counts()

    telemetry.clear_events()
    with e.device_batch():  # (a)
        e._append_file("b", np.arange(1000, 1040, dtype=np.int64),
                       np.ones(40, bool))
        assert e._set_dv("a", np.array([5, 7]))
    assert e._stale == "tail" and e._sorted_n == 600
    assert set(e._dev) == _BOTH_RUNS
    assert _flip_counts() == (flips[0] + 1, flips[1])
    assert _probe_bits(e, [a[5], a[6], a[7], 1000]) == [False, True, False, True]
    assert _sorts(telemetry) == [(40, "tail", "append")]
    assert _sort_counts() == (sorts[0] + 1, sorts[1])
    assert e._stale is None and e._sorted_n == 600
    found = telemetry.recent_events("delta.keyCache.locate")
    assert [(ev.data["rows"], ev.data["flips"], ev.data["steps"])
            for ev in found] == [(640, 2, 1)]

    telemetry.clear_events()
    with e.device_batch():  # (b): an advance that flips the tail's rows
        assert e._set_dv("b", np.array([10]))
    assert e._stale == "tail" and _flip_counts() == (flips[0] + 1, flips[1])
    assert not telemetry.recent_events("delta.keyCache.locate")
    assert _probe_bits(e, [1009, 1010, 1011]) == [True, False, True]
    assert _sorts(telemetry) == [(40, "tail", "flips")]
    assert _sort_counts() == (sorts[0] + 2, sorts[1])

    telemetry.clear_events()
    assert e._set_dv("a", np.array([7]))  # (c) revives row 5
    assert _flip_counts() == (flips[0] + 2, flips[1]) and e._stale is None
    e._dev_kill(np.array([9, 610, 639], np.int32))  # one flip, both runs
    assert _flip_counts() == (flips[0] + 3, flips[1]) and e._stale == "tail"
    found = telemetry.recent_events("delta.keyCache.locate")
    assert [(ev.data["rows"], ev.data["flips"], ev.data["steps"])
            for ev in found] == [(640, 1, 1), (640, 1, 1)]
    assert _probe_bits(e, [a[5], a[7], a[9], 1010, 1011, 1039]) == [
        True, False, False, False, True, False]
    assert _sorts(telemetry) == [(40, "tail", "flips")]
    assert set(e._dev) == _BOTH_RUNS and accounts() == (held, held)

    telemetry.clear_events()
    e._append_file("c", np.arange(2000, 2025, dtype=np.int64),
                   np.ones(25, bool))  # (d): 65 rows past the big run's
    assert e._stale == "all" and set(e._dev) == {"keys", "valid"}
    assert accounts() == (held, held)
    searched = _flip_counts()
    e._kill_file("b")  # no sorted view: a row-space flip, nothing searched
    assert _flip_counts() == searched
    assert _probe_bits(e, [2000, 1011, a[5]]) == [True, False, True]
    assert _sorts(telemetry) == [(665, "all", "fold")]
    assert _sort_counts() == (sorts[0] + 3, sorts[1] + 1)
    assert e._sorted_n == 665 and set(e._dev) == _BOTH_RUNS
    e.drop_device()
    assert hbm_ledger.totals()["keyCache"] == 0
    hbm_ledger.reset()


TWO_RUN_CASES = [
    "runs-of-1-to-7-over-both-runs", "dead-in-the-big-run-live-in-the-tail",
    "null-and-dead-keys-on-both-sides", "int64-max-in-both-runs",
    "tail-of-one-row", "empty-tail", "tail-exactly-full",
    "tail-window-reaches-back-over-the-big-run", "flip-lands-in-the-tail",
    "flip-over-both-runs", "flip-too-large-to-search-for",
    "second-append-into-a-sorted-tail",
]


def _two_run_case(case):
    """A slab's life in steps, each ("file", name, keys, null_ok) or
    ("dv", name, dead positions) or ("probe",): everything before the first
    "probe" goes into the big run, what follows into the tail (capacity
    1024 or 4096, a tail of 64 or 256 rows). With them the source keys and
    their ok flags."""
    rng = np.random.RandomState(len(case))
    big = np.repeat(rng.permutation(200).astype(np.int64) * 7 - 300,
                    rng.randint(1, 8, 200))[:700]
    big = big[rng.permutation(700)]
    ok_big = np.ones(700, bool)
    tail = rng.choice(big, 40)  # more rows of keys the big run holds
    tail[::5] = 5000 + np.arange(8)  # and some it does not
    ok_tail = np.ones(40, bool)
    steps = [("file", "a", big, ok_big), ("probe",),
             ("file", "b", tail, ok_tail)]
    s = np.concatenate([rng.choice(big, 150), tail[:20], [5003, 5003, -1]])
    s_ok = np.ones(len(s), bool)
    if case == "dead-in-the-big-run-live-in-the-tail":
        # an upsert's shape: every tail row re-states a row of the big run,
        # whose versions there all die in the same advance
        tail = np.unique(big)[:20]
        steps = steps[:2] + [("file", "b", tail, ok_tail[:20]),
                             ("dv", "a", np.nonzero(np.isin(big, tail))[0])]
        s = np.concatenate([tail, np.unique(big)[20:40]])
    elif case == "null-and-dead-keys-on-both-sides":
        ok_big = rng.rand(700) > 0.1
        ok_tail = rng.rand(40) > 0.2
        steps = [("file", "a", big, ok_big),
                 ("dv", "a", rng.choice(700, 90, replace=False)), ("probe",),
                 ("file", "b", tail, ok_tail), ("dv", "b", np.array([3, 4]))]
        s_ok = rng.rand(len(s)) > 0.2
    elif case == "int64-max-in-both-runs":
        big, tail = big.copy(), tail.copy()
        big[[0, 350, 699]] = tail[[1, 39]] = np.iinfo(np.int64).max
        steps = [("file", "a", big, ok_big), ("dv", "a", np.array([350])),
                 ("probe",), ("file", "b", tail, ok_tail)]
        s = np.concatenate([s, [np.iinfo(np.int64).max]])
    elif case == "tail-of-one-row":
        steps[2] = ("file", "b", big[:1], ok_tail[:1])
    elif case == "empty-tail":
        steps = steps[:2]
    elif case == "tail-exactly-full":
        tail = rng.choice(big, 64)
        steps[2] = ("file", "b", tail, np.ones(64, bool))
    elif case == "tail-window-reaches-back-over-the-big-run":
        # 1,000 rows in the big run of a 1,024-row slab: the tail's window
        # starts 40 rows inside it
        big = np.concatenate([big, big[:300] + 1])
        steps = [("file", "a", big, np.ones(1000, bool)), ("probe",),
                 ("file", "b", tail[:24], ok_tail[:24])]
    elif case == "flip-lands-in-the-tail":
        steps += [("probe",), ("dv", "b", np.array([1, 2, 7, 39]))]
    elif case == "flip-over-both-runs":
        steps += [("probe",), ("kill", np.array([3, 650, 699, 700, 739]))]
    elif case == "flip-too-large-to-search-for":
        steps += [("probe",), ("dv", "a", np.arange(0, 700, 2))]
    elif case == "second-append-into-a-sorted-tail":
        steps += [("probe",), ("file", "c", tail[:20] + 7, ok_tail[:20])]
    if len(s_ok) != len(s):
        s_ok = np.ones(len(s), bool)
    return steps, s, s_ok


@pytest.mark.parametrize("case", TWO_RUN_CASES)
def test_a_two_run_probe_equals_one_whole_sort(case):
    """A slab whose sorted view is a big run and a tail answers a probe
    exactly as the same rows under one whole sort do, and as numpy does:
    the matched flags of every source row, `any_multi`, the pairs (physical
    row ascending, minimal original source row), the count of pairs. Its
    candidate rows are no more than one run's (a key's dead versions in a
    run that holds no live one are no candidates) but for the tail's own
    padding, which a source key equal to int64.max meets."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys, _tail_capacity
    from delta_tpu.utils import telemetry

    steps, s, s_ok = _two_run_case(case)
    two = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    one = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    telemetry.clear_events()
    keys, valid = np.empty(0, np.int64), np.empty(0, bool)
    for step in steps:
        if step[0] == "probe":
            two.probe_async(s, s_ok).result()
            continue
        for e in (two, one):
            if step[0] == "file":
                e._append_file(step[1], step[2], step[3])
            elif step[0] == "dv":
                assert e._set_dv(step[1], step[2])
            elif e._dev is not None:  # "kill": rows of the slab, at once
                e._dev_kill(step[1].astype(np.int32))
        if step[0] == "file":
            keys = np.concatenate([keys, step[2]])
            valid = np.concatenate([valid, step[3]])
        elif step[0] == "dv":
            off, rows = two.slabs[step[1]]
            valid[off:off + rows] = two.h_nullok[off:off + rows]
            valid[off + step[2]] = False
        else:
            valid[step[1]] = False
            two.h_valid[step[1]] = one.h_valid[step[1]] = False
    big_rows = len(steps[0][2])
    got = two.probe_async(s, s_ok).result()
    got_data = telemetry.recent_events("delta.merge.deviceProbe")[-1].data
    tiers = [ev.data["tier"]
             for ev in telemetry.recent_events("delta.keyCache.sort")]
    if case == "flip-too-large-to-search-for":
        assert tiers == ["all", "tail", "all"] and two._sorted_n == len(keys)
    else:
        assert tiers[0] == "all" and set(tiers[1:]) <= {"tail"}
        assert two._sorted_n == big_rows
        assert len(tiers) == {"empty-tail": 1, "flip-lands-in-the-tail": 3,
                              "flip-over-both-runs": 3,
                              "second-append-into-a-sorted-tail": 3}.get(
                                  case, 2)
    want = one.probe_async(s, s_ok).result()
    want_data = telemetry.recent_events("delta.merge.deviceProbe")[-1].data
    assert one._sorted_n == len(keys)  # one whole sort, an empty tail
    exp_s, exp_multi, exp_phys, exp_src = _oracle(keys, valid, s, s_ok)
    for res in (got, want):
        assert (res.s_matched == exp_s).all()
        assert res.any_multi == exp_multi
        assert res.num_rows == len(keys)
        assert (res.t_pairs[0] == exp_phys).all()
        assert (res.t_pairs[1] == exp_src).all()
    assert got_data["matched"] == want_data["matched"] == len(exp_phys)
    room = _tail_capacity(two.capacity) if "int64-max" in case else 0
    assert len(exp_phys) <= got_data["candidateRows"] \
        <= want_data["candidateRows"] + room
    assert len(exp_phys) > 0


@pytest.mark.parametrize("over", [0, 1])
def test_the_tail_takes_appends_until_it_is_full_and_then_folds(over):
    """Appends after the big run's rows are sorted into the tail, each by
    one sort of the tail alone, until one finds no room: a tail exactly full
    is still a tail, one row over it is one sort of the whole slab (the
    fold), which leaves the tail empty for the appends that follow. The
    rule reads the capacity and the tail's fill alone."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys, _tail_capacity
    from delta_tpu.utils import telemetry

    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("a", np.arange(3000, dtype=np.int64) * 2, np.ones(3000, bool))
    e.ensure_resident()
    tail = _tail_capacity(e.capacity)
    assert (e.capacity, tail) == (4096, 256)
    assert _probe_bits(e, [10, 11]) == [True, False]
    before = _sort_counts()
    telemetry.clear_events()
    e._append_file("b", np.arange(100, dtype=np.int64) * 2 + 1,
                   np.ones(100, bool))
    assert e._stale == "tail"
    assert _probe_bits(e, [10, 11, 199, 201]) == [True, True, True, False]
    rest = tail - 100 + over
    e._append_file("c", np.arange(rest, dtype=np.int64) * 2 + 7001,
                   np.ones(rest, bool))
    assert e._stale == ("all" if over else "tail")
    assert _probe_bits(e, [11, 7001, 7001 + 2 * (rest - 1), 7001 + 2 * rest]) \
        == [True, True, True, False]
    if over:
        assert _sorts(telemetry) == [(100, "tail", "append"),
                                     (3100 + rest, "all", "fold")]
        assert _sort_counts() == (before[0] + 1, before[1] + 1)
        assert e._sorted_n == 3100 + rest
        assert not np.asarray(e._dev["tail_valid"]).any()
    else:
        assert _sorts(telemetry) == [(100, "tail", "append"),
                                     (tail, "tail", "append")]
        assert _sort_counts() == (before[0] + 2, before[1])
        assert e._sorted_n == 3000
        assert np.asarray(e._dev["tail_valid"]).all()
    # the next append goes into the tail either way, or folds a full one
    e._append_file("d", np.array([-5], np.int64), np.ones(1, bool))
    assert e._stale == ("tail" if over else "all")
    assert _probe_bits(e, [-5, 11, 7001]) == [True, True, True]
    assert _sort_counts() == (before[0] + 2, before[1] + 1)


def test_kill_then_revive_on_one_live_view_reads_back_through_the_probe():
    """Runs of equal keys, some rows dead before the view was sorted: a
    vector grows, shrinks and goes (RESTORE's shape), the view is never
    re-sorted, and after every flip the probe's pairs are the live rows of
    the probed keys."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys
    from delta_tpu.utils import telemetry

    rng = np.random.RandomState(23)
    keys = rng.randint(0, 120, 900).astype(np.int64)  # runs of ~7
    first = np.array([0, 1, 2, 450, 899])
    grown = np.array([0, 1, 2, 3, 7, 450, 451, 899])
    null_ok = rng.rand(900) < 0.9
    null_ok[grown] = True
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("f", keys, null_ok)
    e.ensure_resident()
    probe = np.arange(120, dtype=np.int64)

    def live_rows():
        pairs = e.probe_async(probe, np.ones(120, bool)).result().t_pairs
        return pairs[0].tolist()

    def want(dead):
        ok = null_ok.copy()
        ok[dead] = False
        return np.nonzero(ok)[0].tolist()

    none = np.empty(0, np.int64)
    assert live_rows() == want(none)
    telemetry.clear_events()
    searches, resorts = _flip_counts()
    for dead, flips in ((first, 1), (grown, 1), (first, 1), (none, 1),
                        (none, 0)):
        assert e._set_dv("f", dead)
        searches += flips  # a kill or a revive; none where nothing changed
        assert _flip_counts() == (searches, resorts) and e._stale is None
        assert live_rows() == want(dead)
    assert not telemetry.recent_events("delta.keyCache.sort")


def test_a_flip_too_large_to_search_for_drops_the_view_and_the_sort_carries_it():
    """Both sides of `_flip_by_search`, which reads the flips and the
    capacity alone: the largest flip it admits is mirrored in the live big
    run, one row more stays in row space, drops both runs and counts a
    re-sort, and the next probe answers from a sort of the whole slab whose
    span says `cause=flips`."""
    from delta_tpu.obs import hbm_ledger
    from delta_tpu.ops.key_cache import (
        ResidentJoinKeys, _flip_by_search, _search_steps, _tail_capacity)
    from delta_tpu.utils import telemetry

    hbm_ledger.reset()
    n = 3000
    e = ResidentJoinKeys("log", "mid", 0, "sig", ["k"])
    e._append_file("f", np.arange(n, dtype=np.int64) // 3, np.ones(n, bool))
    e.ensure_resident()
    cap = e.capacity
    assert cap == 4096 and _search_steps(cap) == 1
    most = max(d for d in range(1, n) if _flip_by_search(d, cap))
    assert 8 <= most < 1000 and not _flip_by_search(most + 1, cap)
    assert _probe_bits(e, [0, 999]) == [True, True]
    telemetry.clear_events()
    before = _flip_counts()

    held = 22 * cap + 13 * _tail_capacity(cap)
    assert e._set_dv("f", np.arange(most))  # rows 0..most-1 die
    assert e._stale is None and set(e._dev) == _BOTH_RUNS
    assert _flip_counts() == (before[0] + 1, before[1])
    assert e.device_bytes == hbm_ledger.totals()["keyCache"] == held

    alive = np.arange(most + 1) + most  # revives `most` rows, kills one more
    assert e._set_dv("f", alive)
    # the batchless diff is a kill (most + 1 rows) and a revive (most rows)
    assert e._stale == "all" and set(e._dev) == {"keys", "valid"}
    assert _flip_counts() == (before[0] + 1, before[1] + 1)
    assert e.device_bytes == hbm_ledger.totals()["keyCache"] == held
    assert not telemetry.recent_events("delta.keyCache.sort")
    keys = np.arange(1000, dtype=np.int64)
    dead = np.zeros(n, bool)
    dead[alive] = True
    want = [bool((~dead[3 * k:3 * k + 3]).any()) for k in keys]
    assert _probe_bits(e, keys) == want
    sorts = telemetry.recent_events("delta.keyCache.sort")
    assert [(ev.data["rows"], ev.data["tier"], ev.data["cause"])
            for ev in sorts] == [(n, "all", "flips")]
    assert len(telemetry.recent_events("delta.keyCache.locate")) == 1
    assert e.device_bytes == hbm_ledger.totals()["keyCache"] == held
    e.drop_device()
    hbm_ledger.reset()


@pytest.mark.parametrize("flips,cap,steps,search", [
    (65_536, 60_817_408, 3, True),     # a refresh function's rows at SF10
    (105_000, 60_817_408, 3, True),    # the most an RF2 can delete there
    (4_000_000, 60_817_408, 3, False),  # a whole file's rows
    (1_000_000, 37_748_736, 3, True),  # a 1M-row upsert's vectors
    (2_000_000, 37_748_736, 3, False),
    (128, 1024, 1, True),
    (129, 1024, 1, False),
    (10**6, 128, 0, True),  # the dense top alone
    (1, 16_385, 2, True),
])
def test_the_search_or_resort_rule_reads_the_flips_and_the_capacity(
        flips, cap, steps, search):
    from delta_tpu.ops.key_cache import _flip_by_search, _search_steps

    assert _search_steps(cap) == steps
    assert _flip_by_search(flips, cap) is search


def test_set_dv_out_of_range_positions_signal_rebuild(tmp_table):
    """DV positions beyond the slab's recorded row count mean the slab and
    the file disagree; masking them would let deleted rows keep matching
    (suppressing NOT MATCHED inserts). _set_dv must refuse (r4 advisor)."""
    log = _mk_table(tmp_table, files=1)
    e = _entry(log)
    rows = e.num_rows
    assert e._set_dv(next(iter(e.slabs)),
                     np.array([0, rows + 5], np.int64)) is False
    # in-range still succeeds
    assert e._set_dv(next(iter(e.slabs)), np.array([0], np.int64)) is True
    # and a DV for an unknown file is likewise a consistency failure
    assert e._set_dv("no-such-file", np.array([0], np.int64)) is False


def test_failed_advance_poisons_version(tmp_table, monkeypatch):
    """A mid-tail failure leaves half-applied mirrors; the entry must not
    stay probe-able at its old version (r4 advisor: stale-version probe of
    a half-advanced slab produced spurious NOT MATCHED inserts)."""
    from delta_tpu.ops import key_cache as kc_mod

    log = _mk_table(tmp_table, files=2)
    e = _entry(log)
    v0 = e.version
    # grow the log, then make the key read fail mid-advance
    WriteIntoDelta(log, "append", pa.table({
        "k": np.arange(500, 520, dtype=np.int64), "v": np.zeros(20),
    })).run()
    snap = log.update()
    orig_file_keys = kc_mod._file_keys
    monkeypatch.setattr(kc_mod, "_file_keys",
                        lambda *a, **k: None)
    assert KeyCache.instance()._advance(e, snap, ["k"], list(KEY_EXPRS)) is False
    assert e.version not in (v0, snap.version)
    # a thread that cached `e` before the failure now fails its guard
    assert e.probe_async(np.array([5], np.int64), np.array([True]),
                         expected_version=v0) is None

    # an EXCEPTION mid-apply (not a clean False) must poison too — it
    # propagates past get()'s pop-on-failure, so the poisoned version is
    # the only thing stopping a stale-version probe
    monkeypatch.setattr(kc_mod, "_file_keys", orig_file_keys)
    e2 = _entry(log)  # rebuilds at snap.version
    assert e2 is not None and e2.version == snap.version
    v1 = e2.version
    WriteIntoDelta(log, "append", pa.table({
        "k": np.arange(600, 610, dtype=np.int64), "v": np.zeros(10),
    })).run()
    snap2 = log.update()

    def boom(*a, **k):
        raise ValueError("corrupt")

    monkeypatch.setattr(kc_mod, "_file_keys", boom)
    with pytest.raises(ValueError):
        KeyCache.instance()._advance(e2, snap2, ["k"], list(KEY_EXPRS))
    assert e2.version not in (v1, snap2.version)
    assert e2.probe_async(np.array([5], np.int64), np.array([True]),
                          expected_version=v1) is None


def test_metadata_change_invalidates(tmp_table):
    from delta_tpu.commands.alter import set_table_properties

    log = _mk_table(tmp_table)
    e1 = _entry(log)
    set_table_properties(log, {"delta.appendOnly": "false"})
    e2 = _entry(log)
    assert e2 is not e1 and e2.version == log.update().version


def test_composite_pack_parity():
    tab = pa.table({"a": pa.array([1, 2, None], pa.int64()),
                    "b": pa.array([10, -3, 5], pa.int64())})
    from delta_tpu.expr.vectorized import evaluate

    packed = _pack_lanes(tab, [ir.Column("a"), ir.Column("b")], evaluate)
    keys, ok = packed
    assert ok.tolist() == [True, True, False]
    assert keys[0] == (1 << 32) | 10
    assert keys[1] == (2 << 32) | (np.int64(-3) & 0xFFFFFFFF)


# -- resident path through MERGE -------------------------------------------


def _copy_table(src_path, dst_path):
    shutil.copytree(src_path, dst_path)
    return DeltaLog.for_table(dst_path)


def test_resident_merge_parity(tmp_path):
    """Forced resident merge == host-pinned merge, end to end (DV mode)."""
    import pyarrow.compute as pc

    from delta_tpu.exec.scan import scan_to_table

    a_path, b_path = str(tmp_path / "a"), str(tmp_path / "b")
    log_a = _mk_table(a_path)
    _copy_table(a_path, b_path)
    log_b = DeltaLog.for_table(b_path)

    sig_exprs = None  # built by the command's signature, seeded below
    # seed the resident entry for table a using the merge's own key exprs
    snap = log_a.update()
    cmd_probe = MergeIntoCommand(
        log_a, _source([1]), "t.k = s.k",
        [MergeClause("update", assignments=None)],
        [MergeClause("insert", assignments=None)],
        source_alias="s", target_alias="t",
    )
    cond = cmd_probe._resolve(cmd_probe.condition, ["k", "v"], ["k", "v"])
    equi, _res = cmd_probe._split_equi_keys(cond)
    t_exprs = [t for t, _ in equi]
    sig = MergeIntoCommand._key_signature(t_exprs)
    e = KeyCache.instance().get(snap, sig, ["k"], t_exprs)
    assert e is not None

    src_keys = [5, 50, 150, 400, 401]  # 3 updates, 2 inserts
    vals = [1.0, 2.0, 3.0, 4.0, 5.0]
    cmd_a = _merge(log_a, _source(src_keys, vals), mode="force")
    cmd_b = _merge(log_b, _source(src_keys, vals), mode="off")
    assert cmd_a._device_join is not None
    assert cmd_a._join_path == "resident"
    assert cmd_a.metrics["numTargetRowsUpdated"] == 3
    assert cmd_a.metrics["numTargetRowsInserted"] == 2
    for k in ("numTargetRowsUpdated", "numTargetRowsInserted",
              "numTargetRowsCopied"):
        assert cmd_a.metrics[k] == cmd_b.metrics[k], k

    ta = scan_to_table(log_a.update()).sort_by("k")
    tb = scan_to_table(log_b.update()).sort_by("k")
    assert ta.column("k").to_pylist() == tb.column("k").to_pylist()
    assert ta.column("v").to_pylist() == tb.column("v").to_pylist()


def test_resident_merge_after_dv_round(tmp_path):
    """Second resident merge after the first created DVs: deleted rows must
    not block inserts, updated values must land (the CDC steady state)."""
    from delta_tpu.exec.scan import scan_to_table

    a_path = str(tmp_path / "a")
    log = _mk_table(a_path)
    snap = log.update()
    cmd0 = MergeIntoCommand(
        log, _source([1]), "t.k = s.k",
        [MergeClause("update", assignments=None)],
        [MergeClause("insert", assignments=None)],
        source_alias="s", target_alias="t",
    )
    cond = cmd0._resolve(cmd0.condition, ["k", "v"], ["k", "v"])
    equi, _ = cmd0._split_equi_keys(cond)
    t_exprs = [t for t, _ in equi]
    sig = MergeIntoCommand._key_signature(t_exprs)
    KeyCache.instance().get(snap, sig, ["k"], t_exprs)

    cmd1 = _merge(log, _source([10, 20, 300], [1.0, 2.0, 3.0]))
    assert cmd1._join_path == "resident"
    # second merge: hits rows now carrying DVs + the fresh insert file
    cmd2 = _merge(log, _source([10, 300, 301], [7.0, 8.0, 9.0]))
    assert cmd2._join_path == "resident"
    assert cmd2.metrics["numTargetRowsUpdated"] == 2
    assert cmd2.metrics["numTargetRowsInserted"] == 1
    t = scan_to_table(log.update())
    got = dict(zip(t.column("k").to_pylist(), t.column("v").to_pylist()))
    assert got[10] == 7.0 and got[300] == 8.0 and got[301] == 9.0
    assert t.num_rows == 202  # 200 original + 300 + 301


def test_resident_multi_match_errors(tmp_path):
    from delta_tpu.utils.errors import DeltaUnsupportedOperationError

    log = _mk_table(str(tmp_path / "a"))
    snap = log.update()
    e = KeyCache.instance().get(
        snap, MergeIntoCommand._key_signature([ir.Column("k")]),
        ["k"], [ir.Column("k")])
    assert e is not None
    with pytest.raises(DeltaUnsupportedOperationError, match="multiple source"):
        _merge(log, _source([5, 5], [1.0, 2.0]))


def test_background_build_after_merge(tmp_table):
    import time

    log = _mk_table(tmp_table)
    with conf.set_temporarily(**{"delta.tpu.merge.residentKeys.minRows": "1"}):
        cmd = _merge(log, _source([5, 400], [1.0, 2.0]), mode="auto")
        sig = None
        # the command recorded + consumed the candidate; poll the cache
        for _ in range(100):
            entries = list(KeyCache.instance()._entries.values())
            if entries:
                break
            time.sleep(0.05)
    assert entries, "background build after an eligible merge"
    assert entries[0].version == log.update().version


def test_probe_absent_key_sharing_lo_with_member(tmp_table):
    """A member key Z and an absent key Y with searchsorted lo(Y) == lo(Z)
    must not race in the mark scatter: Z stays matched (round-4 review —
    mixed True/False scatter to one index has unspecified winner on XLA)."""
    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", pa.table({
        "k": np.array([100, 200, 300], np.int64), "v": np.zeros(3)})).run()
    e = _entry(log)
    # many interleaved probes: absent keys just below each member key share
    # the member's lo; order inside the scatter must not matter
    s = np.array([99, 100, 199, 200, 299, 300, 150, 250], np.int64)
    res = e.probe_async(s, np.ones(len(s), bool)).result()
    assert res.s_matched.tolist() == [False, True, False, True, False, True,
                                      False, False]
    assert res.t_bits.tolist() == [True, True, True]


def test_batched_advance_append_plus_dv_same_file(tmp_table):
    """A file appended AND DV-masked within one tail batch: the flush must
    apply the row scatter before the kills (append captures pre-DV
    validity)."""
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.delete import DeleteCommand

    log = _mk_table(tmp_table, files=1)
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    e1 = _entry(log)
    e1.ensure_resident()
    # in one tail window: append a file, then DV-delete some of its rows
    WriteIntoDelta(log, "append", pa.table({
        "k": np.arange(1000, 1050, dtype=np.int64), "v": np.zeros(50)})).run()
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        DeleteCommand(log, "k = 1010").run()
    e2 = _entry(log)
    assert e2 is e1 and e2.is_resident
    res = e2.probe_async(np.array([1010, 1011], np.int64),
                         np.array([True, True])).result()
    assert res.s_matched.tolist() == [False, True]


# -- rewrite invalidation (epoch bump) --------------------------------------


def test_optimize_bumps_epoch_and_drops_entry(tmp_table):
    """OPTIMIZE rewrites files: the resident entry must be dropped (never
    advanced-through or served) and the table's epoch must move."""
    from delta_tpu.commands.optimize import OptimizeCommand

    log = _mk_table(tmp_table)
    e = _entry(log)
    assert e is not None
    kc = KeyCache.instance()
    epoch0 = kc.epoch(log.log_path)
    OptimizeCommand(log, min_file_size=1 << 30).run()
    assert kc.epoch(log.log_path) == epoch0 + 1
    assert kc.peek(log.log_path, SIG) is None
    # a rebuild at the post-rewrite snapshot serves correct members
    e2 = _entry(log)
    assert e2 is not e and e2.version == log.update().version
    res = e2.probe_async(np.array([5, 500], np.int64),
                         np.ones(2, bool)).result()
    assert res.s_matched.tolist() == [True, False]


def test_stale_entry_cannot_serve_after_rewrite(tmp_table):
    """Even if a buggy path re-inserts a pre-rewrite entry, the epoch guard
    refuses to serve it, and version-poisoning fails any in-flight holder's
    expected-version probe — a stale resident cache can never serve a
    post-rewrite MERGE."""
    from delta_tpu.commands.optimize import OptimizeCommand

    log = _mk_table(tmp_table)
    e = _entry(log)
    v0 = e.version
    kc = KeyCache.instance()
    OptimizeCommand(log, min_file_size=1 << 30).run()
    # the bump poisoned the dropped entry: in-flight holders fail their guard
    assert e.probe_async(np.array([5], np.int64), np.array([True]),
                         expected_version=v0) is None
    # simulate a buggy re-insert of the stale entry
    with kc._lock:
        kc._entries[(log.log_path, SIG)] = e
    assert kc.get(log.update(), SIG, ["k"], list(KEY_EXPRS),
                  build_if_missing=False) is None


def test_update_rewrite_bumps_epoch_dv_mark_does_not(tmp_table):
    """UPDATE in rewrite mode invalidates; UPDATE in DV mode advances the
    entry incrementally (the CDC steady state must not lose residency)."""
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.update import UpdateCommand

    log = _mk_table(tmp_table)
    kc = KeyCache.instance()
    epoch0 = kc.epoch(log.log_path)
    # rewrite mode (no DV property): epoch bumps
    UpdateCommand(log, {"v": "0.5"}, "k = 10").run()
    assert kc.epoch(log.log_path) == epoch0 + 1
    # DV mode: no bump, existing entry advances in place
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    e = _entry(log)
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        UpdateCommand(log, {"v": "0.7"}, "k = 11").run()
    assert kc.epoch(log.log_path) == epoch0 + 1
    e2 = _entry(log)
    assert e2 is e and e2.version == log.update().version


def test_concurrent_resident_merges_chaos(tmp_path):
    """Two threads merging DISJOINT key sets into one table with the
    resident lane forced: OCC retries serialize the commits, the lane
    advances through both tails, and the final table state is exactly the
    union — no lost updates, no phantom inserts (the advance-vs-probe race
    the entry lock + expected-version guard protect)."""
    import threading

    from delta_tpu.exec.scan import scan_to_table

    path = str(tmp_path / "c")
    log = _mk_table(path, files=4)
    snap = log.update()
    sig = MergeIntoCommand._key_signature([ir.Column("k")])
    e = KeyCache.instance().get(snap, sig, ["k"], [ir.Column("k")])
    e.ensure_resident()

    errors_seen = []

    def worker(base):
        try:
            for rnd in range(3):
                src = _source([base + rnd * 2, 1000 + base + rnd],
                              [float(base + rnd), float(base + rnd) + 0.5])
                for attempt in range(8):
                    try:
                        _merge(log, src)
                        break
                    except Exception as exc:
                        name = type(exc).__name__
                        if "Concurrent" in name or "Commit" in name:
                            continue  # OCC conflict: retry
                        raise
                else:
                    raise RuntimeError("merge retries exhausted")
        except Exception as exc:
            errors_seen.append(exc)

    t1 = threading.Thread(target=worker, args=(0,))
    t2 = threading.Thread(target=worker, args=(100,))
    t1.start(); t2.start()
    t1.join(30); t2.join(30)
    assert not errors_seen, errors_seen

    t = scan_to_table(log.update())
    got = dict(zip(t.column("k").to_pylist(), t.column("v").to_pylist()))
    # updates landed (last writer per key within each thread's sequence)
    for base in (0, 100):
        for rnd in range(3):
            assert got[base + rnd * 2] == float(base + rnd), (base, rnd)
            assert got[1000 + base + rnd] == float(base + rnd) + 0.5
    assert t.num_rows == 200 + 6  # 200 original + 3 inserts per thread


# -- device-memory soft budget (ISSUE 7: obs/hbm_ledger pressure) ------------


def test_hbm_budget_pressure_evicts_lru_first():
    """With delta.tpu.device.hbmBudgetBytes set, KeyCache eviction prices
    itself against budget - stateCache - scratch and drops device copies in
    LRU order — least-recently-used entries lose residency first, the MRU
    survivor keeps it, and scratch growth tightens the allowance further."""
    import gc

    from delta_tpu.obs import hbm_ledger
    from delta_tpu.ops.key_cache import ResidentJoinKeys

    gc.collect()
    hbm_ledger.reset()
    cache = KeyCache.instance()
    entries = []
    for i in range(3):
        e = ResidentJoinKeys(f"/hbm-log-{i}", "mid", 0, "sig", ["k"])
        e.h_keys = np.arange(10, dtype=np.int64)
        e.h_valid = np.ones(10, bool)
        e.h_nullok = np.ones(10, bool)
        e.h_min, e.h_max = 0, 9
        e.num_rows = 10
        e.ensure_resident()
        assert cache.register(e), f"entry {i} failed to register"
        entries.append(e)
    per_entry = entries[0].device_bytes
    assert hbm_ledger.totals()["keyCache"] == 3 * per_entry
    # budget fits ONE entry (plus slack): the two least-recently-registered
    # lose their device copies, the most recent keeps residency
    with conf.set_temporarily(**{
        "delta.tpu.device.hbmBudgetBytes": per_entry + per_entry // 2,
    }):
        cache._evict(keep=None)
        assert [e.is_resident for e in entries] == [False, False, True]
        assert hbm_ledger.totals()["keyCache"] == per_entry
        # scratch pressure shrinks the allowance below one entry: the last
        # resident copy goes too (host mirrors keep serving)
        hbm_ledger.adjust("scratch", per_entry)
        cache._evict(keep=None)
        assert [e.is_resident for e in entries] == [False, False, False]
        assert hbm_ledger.totals()["keyCache"] == 0
        hbm_ledger.adjust("scratch", -per_entry)
    # without a budget the default keyCache.maxBytes (1 GiB) evicts nothing
    entries[0].ensure_resident()
    cache._evict(keep=None)
    assert entries[0].is_resident
    hbm_ledger.reset()


def test_an_append_writes_the_mirrors_in_place_and_a_regrow_copies_them():
    """The host mirrors are as long as the slab: a file's keys go into the
    room the backing arrays have (no copy of what is there), the arrays are
    replaced when they are outgrown, and mirrors set from outside (as tests
    do) are taken over at the next append."""
    from delta_tpu.ops.key_cache import ResidentJoinKeys, _slab_capacity

    e = ResidentJoinKeys("/mirror-log", "mid", 0, "sig", ["k"])
    e.capacity = _slab_capacity(3000)
    want_k, want_v = [], []
    rng = np.random.default_rng(4)
    bases = set()
    for i in range(5):
        keys = rng.integers(-50, 50, 700)
        valid = rng.random(700) < 0.9
        assert e._append_file(f"f{i}", keys, valid)
        want_k.append(keys)
        want_v.append(valid)
        bases.add(e.h_keys.base.ctypes.data)
        assert len(e.h_keys) == len(e.h_valid) == len(e.h_nullok) == e.num_rows
        assert np.array_equal(e.h_keys, np.concatenate(want_k))
        assert np.array_equal(e.h_valid, np.concatenate(want_v))
        assert np.array_equal(e.h_nullok, np.concatenate(want_v))
    # 3,500 rows are inside the capacity of 4,096: one backing array so far
    assert e.num_rows == 3500 and len(bases) == 1
    assert len(e.h_keys.base) >= e.capacity
    # a vector flips validity through the view, in the backing array
    assert e._set_dv("f1", np.array([0, 5]))
    want_v[1] = want_v[1].copy()
    want_v[1][[0, 5]] = False
    assert np.array_equal(e.h_valid, np.concatenate(want_v))
    # outgrown: the capacity steps, the mirrors move once, nothing is lost
    assert e._append_file("big", np.arange(2000), np.ones(2000, bool))
    assert e.capacity > 4096 and e.h_keys.base.ctypes.data not in bases
    assert np.array_equal(e.h_keys[:3500], np.concatenate(want_k))
    assert np.array_equal(e.h_keys[3500:], np.arange(2000))
    # mirrors assigned from outside have no room behind them
    e2 = ResidentJoinKeys("/mirror-log-2", "mid", 0, "sig", ["k"])
    e2.h_keys, e2.h_valid, e2.h_nullok = (np.arange(10, dtype=np.int64),
                                          np.ones(10, bool), np.ones(10, bool))
    e2.num_rows = 10
    assert e2._append_file("g", np.array([7, 8]), np.array([True, False]))
    assert e2.h_keys.tolist() == list(range(10)) + [7, 8]
    assert e2.h_valid.tolist() == [True] * 11 + [False]
