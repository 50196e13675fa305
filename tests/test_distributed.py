"""Sharded execution plane (`parallel/distributed`, `parallel/executor`,
sharded scan planning in `ops/state_cache`): byte-weighted LPT vs the strided
partitioner on a zipf-100k file population, the work-stealing executor's
ordering/abort/steal semantics, shard_map plan identity on the virtual
8-device mesh, per-device HBM attribution + the doctor's worst-device flag,
and parallel OPTIMIZE result identity."""
import time

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.optimize import OptimizeCommand
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.expr.parser import parse_expression
from delta_tpu.obs import hbm_ledger
from delta_tpu.ops import pruning
from delta_tpu.ops.state_cache import DeviceStateCache, ResidentState, extract_ranges
from delta_tpu.parallel.distributed import bytes_skew, host_shard_indices, lpt_assign
from delta_tpu.parallel.executor import run_sharded
from delta_tpu.storage.faults import SimulatedCrash
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_ledger():
    hbm_ledger.reset()
    DeviceStateCache.reset()
    yield
    DeviceStateCache.reset()
    hbm_ledger.reset()


# -- LPT partitioner --------------------------------------------------------


def test_lpt_assign_tiles_and_is_deterministic():
    sizes = [5, 3, 3, 2, 2, 1, 1, 1]
    a = lpt_assign(sizes, 3)
    # tiling without overlap, every bucket sorted
    flat = sorted(j for b in a for j in b)
    assert flat == list(range(len(sizes)))
    assert all(b == sorted(b) for b in a)
    # pure function of (sizes, count): recompute == first run (the RPC-free
    # contract — every host derives the identical assignment)
    assert a == lpt_assign(sizes, 3)
    # count=1 degenerates to everything on host 0
    assert lpt_assign(sizes, 1) == [list(range(len(sizes)))]


def test_lpt_beats_strided_on_zipf_100k():
    """Regression for the strided partitioner's hot-shard failure: on a
    zipf-like 100k file population the strided slices concentrate the head
    of the distribution on one host (max/mean bytes well above 1), while the
    size-weighted LPT assignment stays within a percent of perfectly even."""
    n = 100_000
    sizes = [1_000_000 // (i + 1) + 1 for i in range(n)]  # zipf s=1 head
    hosts = 8
    strided = [list(range(h, n, hosts)) for h in range(hosts)]
    lpt = lpt_assign(sizes, hosts)
    s_skew = bytes_skew(sizes, strided)
    l_skew = bytes_skew(sizes, lpt)
    assert s_skew > 1.3, s_skew  # strided inherits the hot shard
    assert l_skew < 1.01, l_skew  # LPT is near-perfectly balanced
    assert l_skew < s_skew
    # the sized host_shard_indices slices agree with lpt_assign exactly
    for h in range(hosts):
        assert host_shard_indices(n, h, hosts, sizes=sizes) == lpt[h]


def test_host_shard_indices_strided_default_unchanged():
    # sizes=None keeps the legacy strided contract (vacuum/scan composition
    # in test_multihost relies on the exact indices)
    assert host_shard_indices(10, 1, 3) == [1, 4, 7]
    with pytest.raises(ValueError):
        host_shard_indices(10, 0, 2, sizes=[1, 2, 3])  # length mismatch


# -- work-stealing executor -------------------------------------------------


def test_run_sharded_preserves_order_and_steals():
    items = list(range(10))
    # LPT over 2 workers: the hot item owns worker 0's whole deque, the 9
    # small ones queue on worker 1 — worker 0 drains first and must steal
    sizes = [10_000] + [1] * 9

    def fn(x):
        time.sleep(0.08 if x == 0 else 0.03)
        return x * 2

    before = telemetry.counters("dist")
    rep = run_sharded(items, fn, sizes=sizes, workers=2, label="t")
    after = telemetry.counters("dist")
    assert rep.results == [x * 2 for x in items]  # index-ordered
    assert rep.workers == 2
    assert rep.steals >= 1
    assert rep.per_worker[0].stolen >= 1
    assert sum(s.items for s in rep.per_worker.values()) == len(items)
    assert sum(s.bytes for s in rep.per_worker.values()) == sum(sizes)
    assert after.get("dist.jobs", 0) == before.get("dist.jobs", 0) + 1
    assert after.get("dist.items", 0) == before.get("dist.items", 0) + 10
    assert after.get("dist.steals", 0) >= before.get("dist.steals", 0) + 1
    rows = rep.timings()
    assert [r["worker"] for r in rows] == [0, 1]
    assert all(r["busy_s"] > 0 for r in rows)


def test_run_sharded_stealing_conf_gate():
    with conf.set_temporarily(**{"delta.tpu.distributed.workStealing.enabled": False}):
        rep = run_sharded(list(range(8)), lambda x: x, sizes=[100] + [1] * 7,
                          workers=2, label="t")
    assert rep.results == list(range(8))
    assert rep.steals == 0


def test_run_sharded_inline_single_worker():
    rep = run_sharded([3, 1, 2], lambda x: x + 1, workers=1, label="t")
    assert rep.results == [4, 2, 3]
    assert rep.workers == 1 and rep.steals == 0 and rep.skew == 1.0


def test_run_sharded_crash_aborts_and_reraises():
    """A SimulatedCrash on one worker mid-job pierces the pool: the first
    failure aborts the remaining queue and re-raises on the caller — no
    partial result is ever returned to commit from."""
    ran = []

    def fn(x):
        if x == 0:
            raise SimulatedCrash("dist.item")
        time.sleep(0.01)
        ran.append(x)
        return x

    with pytest.raises(SimulatedCrash):
        run_sharded(list(range(32)), fn, sizes=[1000] + [1] * 31,
                    workers=4, label="t")
    assert len(ran) < 32  # the abort actually cut the queue short


# -- sharded scan planning (shard_map on the virtual 8-device mesh) ---------


def _entry(n=5000, seed=7):
    rng = np.random.RandomState(seed)
    lo = np.sort(rng.rand(2, n) * 100.0, axis=0)
    hi = lo + rng.rand(2, n) * 10.0
    return ResidentState(
        "mem://t", "mid", 0, ["a", "b"], [f"p{i}" for i in range(n)],
        {"min": lo, "max": hi, "size": np.ones(n, np.int64)},
    )


def _ranges(entry, exprs):
    out = []
    for e in exprs:
        pred = pruning.skipping_predicate(parse_expression(e), frozenset())
        r = extract_ranges(pred, entry.columns)
        assert r is not None, e
        out.append(r)
    return out


def test_sharded_plan_identity_on_8_devices():
    """The shard_map plan kernel (lanes split along the file axis over the
    8-device mesh) returns EXACTLY the host planner's rows: the coarse
    per-shard block cull all-gathers, and the fine pass runs on the same
    float64 mirrors in both routes."""
    entry = _entry()
    rs = _ranges(entry, ["a >= 10 AND a <= 30", "b <= 20", "a = 50",
                         "a >= 99 AND b <= 1", "b >= 1000"])
    host = entry.plan_ranges(rs, k=10_000, use_device=False)
    before = telemetry.counters("dist")
    with conf.set_temporarily(**{
        "delta.tpu.distributed.plan.mode": "force",
        "delta.tpu.stateCache.devicePlan.mode": "force",
    }):
        dev = entry.plan_ranges(rs, k=10_000, use_device=True)
    assert entry.resident_shards == 8  # 8192-capacity lanes over 8 devices
    for hp, dp in zip(host, dev):
        assert list(dp.rows) == list(hp.rows)
        assert dp.count == hp.count
        if dp.via != "verdict":
            assert dp.via == "device-sharded"
    after = telemetry.counters("dist")
    assert after.get("dist.plan.sharded", 0) > before.get("dist.plan.sharded", 0)


def test_sharded_residency_accounts_per_device():
    entry = _entry()
    with conf.set_temporarily(**{"delta.tpu.distributed.plan.mode": "force"}):
        entry.ensure_resident(entry._feasible_shards())
    per = hbm_ledger.device_totals()
    assert sorted(per) == list(range(8))
    assert len(set(per.values())) == 1  # even split of the lane bytes
    assert sum(per.values()) <= entry.device_bytes
    # the labeled gauge rides next to the unlabeled aggregate
    g = telemetry.gauges("device.hbm.stateCacheBytes")
    labeled = {k[1] for k in g if k[1]}
    assert (("device", "0"),) in labeled
    assert ((), ) not in labeled and ("device.hbm.stateCacheBytes", ()) in g
    worst = hbm_ledger.worst_device()
    assert worst is not None and worst[0] == 0  # even split ties -> lowest
    entry.drop_device()
    assert hbm_ledger.device_totals() == {} or \
        all(v == 0 for v in hbm_ledger.device_totals().values())


def test_small_capacity_is_not_shardable():
    # 6 paths -> capacity 8: cannot split into whole 1024-file BLOCKs
    entry = _entry(n=6)
    assert entry._feasible_shards() == 1
    with conf.set_temporarily(**{"delta.tpu.distributed.plan.enabled": False}):
        big = _entry()
        assert big._feasible_shards() == 1


# -- doctor: worst-device dimension -----------------------------------------


def test_doctor_flags_worst_device():
    from delta_tpu.obs.doctor import _dim_device

    hbm_ledger.adjust("stateCache", 800, device=0)
    hbm_ledger.adjust("stateCache", 100, device=1)
    with conf.set_temporarily(**{"delta.tpu.device.hbmBudgetBytes": 1000}):
        dim = _dim_device()
    # aggregate pressure 0.9 would only warn; device 0 at 1.6x its fair
    # share (500) is the real OOM candidate and drives severity
    assert dim.metrics["worstDevice"] == 0
    assert dim.metrics["worstDeviceBytes"] == 800
    assert dim.metrics["worstDevicePressure"] == pytest.approx(1.6)
    assert dim.severity == "critical"
    assert "worst device 0" in dim.detail


# -- parallel OPTIMIZE ------------------------------------------------------


def _rows(log, sort="id"):
    from delta_tpu.exec.scan import scan_to_table

    t = scan_to_table(log.update())
    return t.sort_by(sort).to_pylist()


def _mk_partitioned(path, parts=4, files_per=3, rows=16):
    log = DeltaLog.for_table(str(path))
    for p in range(parts):
        for f in range(files_per):
            base = (p * files_per + f) * rows
            WriteIntoDelta(log, "append", pa.table({
                "id": np.arange(base, base + rows, dtype=np.int64),
                "part": np.full(rows, f"p{p}"),
                "v": np.arange(base, base + rows, dtype=np.float64),
            }), partition_columns=["part"]).run()
    return log


def test_parallel_optimize_identity(tmp_path):
    seq_log = _mk_partitioned(tmp_path / "seq")
    par_log = _mk_partitioned(tmp_path / "par")
    before = _rows(seq_log)
    c1 = OptimizeCommand(seq_log, min_file_size=1 << 30)
    c1.run()
    c4 = OptimizeCommand(par_log, min_file_size=1 << 30, workers=4)
    c4.run()
    # same rows, same file topology, same metrics — worker count is invisible
    assert _rows(seq_log) == before
    assert _rows(par_log) == before
    assert c1.metrics["numRemovedFiles"] == c4.metrics["numRemovedFiles"] == 12
    assert c1.metrics["numAddedFiles"] == c4.metrics["numAddedFiles"] == 4
    assert c4.shard_report is not None
    assert c4.shard_report.workers == 4
    assert [r for r in c4.shard_report.results if r is None] == []
    DeltaLog.clear_cache()
    assert DeltaLog.for_table(str(tmp_path / "par")).update().num_of_files == 4


def test_optimize_workers_conf_default(tmp_path):
    log = _mk_partitioned(tmp_path / "t", parts=2, files_per=2)
    with conf.set_temporarily(**{"delta.tpu.distributed.optimize.workers": 2}):
        cmd = OptimizeCommand(log, min_file_size=1 << 30)
        cmd.run()
    assert cmd.shard_report is not None and cmd.shard_report.workers == 2
    assert telemetry.counters("dist").get("dist.optimize.groups", 0) >= 2
