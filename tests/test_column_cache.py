"""Device-resident scan column cache + jitted residual path
(`ops/column_cache.py`, `expr/jaxeval.compile_residual`): result identity
with the Arrow path across the predicate matrix (strings, IN, temporals,
NULLs, partitions, DVs, schema evolution), rewrite-epoch invalidation
(OPTIMIZE / UPDATE / DELETE-rewrite / RESTORE can never be served stale
lanes), LRU + HBM-budget eviction, router pricing/audit, and the
``columnCache.*`` / ``scan.device.*`` observability."""
import datetime as dt

import numpy as np
import pyarrow as pa
import pytest

from delta_tpu import DeltaLog
from delta_tpu.commands.write import WriteIntoDelta
from delta_tpu.exec.scan import scan_to_table
from delta_tpu.expr import ir, jaxeval
from delta_tpu.expr.parser import parse_predicate
from delta_tpu.obs import hbm_ledger
from delta_tpu.ops.column_cache import ColumnCache, ResidentColumn
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf


@pytest.fixture(autouse=True)
def _fresh_cache():
    ColumnCache.reset()
    yield
    ColumnCache.reset()


FORCE = {"delta.tpu.read.deviceResidual.mode": "force"}
OFF = {"delta.tpu.read.deviceResidual.mode": "off"}


def _mk_table(path, files=3, n=400, partition=False, seed=7):
    log = DeltaLog.for_table(path)
    rng = np.random.RandomState(seed)
    for i in range(files):
        tbl = pa.table({
            "id": np.arange(i * n, (i + 1) * n, dtype=np.int64),
            "cat": pa.array(rng.choice(
                ["alpha", "beta", "gamma", None], n).tolist()),
            "x": rng.rand(n),
            "d": pa.array([dt.date(2024, 1, 1) + dt.timedelta(days=int(v))
                           for v in rng.randint(0, 400, n)]),
            "ts": pa.array([dt.datetime(2024, 1, 1)
                            + dt.timedelta(seconds=int(v))
                            for v in rng.randint(0, 86400 * 30, n)],
                           pa.timestamp("us")),
            "p": np.full(n, i % 2, dtype=np.int32),
        })
        WriteIntoDelta(log, "append", tbl,
                       partition_columns=["p"] if partition else ()).run()
    return log


def _both(log, pred):
    with conf.set_temporarily(**OFF):
        host = scan_to_table(log.update(), [pred]).sort_by("id")
    with conf.set_temporarily(**FORCE):
        dev = scan_to_table(log.update(), [pred]).sort_by("id")
    return host, dev


# -- result identity: device mask vs Arrow path -----------------------------


IDENTITY_PREDS = [
    "cat = 'alpha' AND x > 0.5",
    "cat != 'beta'",
    "cat <=> 'gamma'",
    "cat IN ('beta', 'gamma')",
    "cat IN ('nosuchvalue')",
    "cat IS NULL",
    "cat IS NOT NULL AND id < 300",
    "d >= '2024-06-01'",
    "ts < '2024-01-15 12:30:00'",
    "year(d) = 2024 AND month(ts) = 1",
    "to_date(ts) = '2024-01-15'",
    "hour(ts) >= 12",
    "id > 900 OR cat = 'missingvalue'",
    "x BETWEEN 0.2 AND 0.4",
]


@pytest.mark.parametrize("pred", IDENTITY_PREDS)
def test_device_scan_identity(tmp_table, pred):
    log = _mk_table(tmp_table)
    host, dev = _both(log, pred)
    assert host.equals(dev), pred


def test_device_scan_engages_and_counts(tmp_table):
    log = _mk_table(tmp_table)
    c0 = dict(telemetry.counters())
    host, dev = _both(log, "cat = 'alpha'")
    assert host.equals(dev)
    c1 = telemetry.counters()
    assert c1.get("scan.device.engaged", 0) > c0.get("scan.device.engaged", 0)
    assert c1.get("columnCache.misses", 0) > c0.get("columnCache.misses", 0)
    # warm pass: same lanes serve from residency
    with conf.set_temporarily(**FORCE):
        scan_to_table(log.update(), ["cat = 'alpha'"])
    c2 = telemetry.counters()
    assert c2.get("columnCache.hits", 0) > c1.get("columnCache.hits", 0)
    assert c2.get("columnCache.misses", 0) == c1.get("columnCache.misses", 0)
    assert hbm_ledger.totals()["columnCache"] > 0
    assert ColumnCache.instance().resident_bytes() > 0


def test_device_scan_report_attribution(tmp_table):
    from delta_tpu.obs.scan_report import last_scan_report

    log = _mk_table(tmp_table)
    with conf.set_temporarily(**FORCE):
        scan_to_table(log.update(), ["cat = 'alpha'"])
    rep = last_scan_report()
    assert rep is not None and rep.device_residual == "device"
    d = rep.to_dict()
    assert d["deviceResidual"] == "device"
    assert d["bytesDeviceSurvivor"] > 0


def test_device_mask_skips_all_false_row_groups(tmp_table):
    """A row group whose footer stats cover the value but whose rows never
    match skips decode entirely on the device path (stats can't see gaps;
    the mask can)."""
    log = DeltaLog.for_table(tmp_table)
    with conf.set_temporarily(**{"delta.tpu.write.rowGroupRows": 100}):
        WriteIntoDelta(log, "append", pa.table({
            "id": np.arange(0, 600, 2, dtype=np.int64),  # evens only
            "v": np.ones(300),
        })).run()
    c0 = dict(telemetry.counters())
    host, dev = _both(log, "id = 51")  # inside group 0's range, never present
    assert host.num_rows == dev.num_rows == 0
    c1 = telemetry.counters()
    assert c1.get("scan.rowgroups.deviceSkipped", 0) \
        > c0.get("scan.rowgroups.deviceSkipped", 0)
    assert c1.get("scan.bytes.deviceSkipped", 0) \
        > c0.get("scan.bytes.deviceSkipped", 0)


def test_identity_with_typed_partition_column(tmp_table):
    log = _mk_table(tmp_table, partition=True)
    for pred in ["p = 0 AND cat = 'alpha'", "p = 1 OR x < 0.1"]:
        host, dev = _both(log, pred)
        assert host.equals(dev), pred


def test_identity_with_deletion_vectors(tmp_table):
    from delta_tpu.commands.alter import set_table_properties
    from delta_tpu.commands.delete import DeleteCommand

    log = _mk_table(tmp_table)
    set_table_properties(log, {"delta.tpu.enableDeletionVectors": "true"})
    with conf.set_temporarily(**{"delta.tpu.deletionVectors.enabled": True}):
        DeleteCommand(log, "id % 7 = 0").run()
    host, dev = _both(log, "cat = 'alpha' AND x > 0.3")
    assert host.equals(dev)
    assert not any(v % 7 == 0 for v in dev.column("id").to_pylist())


def test_identity_after_schema_evolution(tmp_table):
    """Files that predate a column bind an all-invalid lane: NULL semantics
    must match the host's appended-null columns exactly."""
    from delta_tpu.commands.alter import add_columns
    from delta_tpu.schema.types import StringType, StructField

    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", pa.table({
        "id": np.arange(100, dtype=np.int64), "v": np.ones(100)})).run()
    add_columns(log, [StructField("tag", StringType())])
    WriteIntoDelta(log, "append", pa.table({
        "id": np.arange(100, 200, dtype=np.int64), "v": np.ones(100),
        "tag": pa.array(["new"] * 100)})).run()
    for pred in ["tag = 'new'", "tag IS NULL", "tag != 'new' OR id < 20"]:
        host, dev = _both(log, pred)
        assert host.equals(dev), pred


def test_mode_off_never_engages(tmp_table):
    log = _mk_table(tmp_table, files=1)
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**OFF):
        scan_to_table(log.update(), ["cat = 'alpha'"])
    c1 = telemetry.counters()
    for k in ("scan.device.engaged", "scan.device.declined",
              "scan.device.fallback"):
        assert c1.get(k, 0) == c0.get(k, 0)
    assert ColumnCache.instance().resident_bytes() == 0


def test_auto_mode_declines_on_slow_link_and_audits(tmp_table):
    from delta_tpu.obs import router_audit
    from delta_tpu.parallel import link

    log = _mk_table(tmp_table, files=1)
    link.reset()
    c0 = dict(telemetry.counters())
    try:
        with conf.set_temporarily(**{
            "delta.tpu.read.deviceResidual.mode": "auto",
            "delta.tpu.link.uploadMBps": 0.0001,
            "delta.tpu.link.downloadMBps": 0.0001,
        }):
            host = scan_to_table(log.update(), ["cat = 'alpha'"])
    finally:
        link.reset()
    c1 = telemetry.counters()
    assert c1.get("scan.device.declined", 0) > c0.get(
        "scan.device.declined", 0)
    assert c1.get("scan.device.engaged", 0) == c0.get(
        "scan.device.engaged", 0)
    last = router_audit.last_audit()
    assert last is not None and last.op == "scan.residual"
    assert last.decision == "host"
    assert host.num_rows > 0


def test_host_fallback_on_uncompilable_residual(tmp_table):
    """A residual with no device lowering (string ordering) falls back to
    the host path — identical results, fallback counter bumped."""
    log = _mk_table(tmp_table, files=1)
    c0 = dict(telemetry.counters())
    host, dev = _both(log, "cat > 'b'")
    assert host.equals(dev)
    assert telemetry.counters().get("scan.device.fallback", 0) \
        > c0.get("scan.device.fallback", 0)


# -- rewrite invalidation (epoch bump) --------------------------------------


def _resident_after_scan(log):
    with conf.set_temporarily(**FORCE):
        scan_to_table(log.update(), ["cat = 'alpha'"])
    cache = ColumnCache.instance()
    assert cache.resident_bytes() > 0
    return cache


def test_optimize_bumps_epoch_and_drops_lanes(tmp_table):
    from delta_tpu.commands.optimize import OptimizeCommand

    log = _mk_table(tmp_table)
    cache = _resident_after_scan(log)
    epoch0 = cache.epoch(log.log_path)
    c0 = dict(telemetry.counters())
    OptimizeCommand(log, min_file_size=1 << 30).run()
    assert cache.epoch(log.log_path) == epoch0 + 1
    assert cache.resident_bytes() == 0
    assert telemetry.counters().get("columnCache.invalidations", 0) \
        > c0.get("columnCache.invalidations", 0)
    host, dev = _both(log, "cat = 'alpha'")
    assert host.equals(dev)


def test_update_rewrite_cannot_serve_stale_lane(tmp_table):
    """After an UPDATE rewrite, a device scan must see the NEW values —
    the pre-rewrite lanes can never mask a post-rewrite scan."""
    from delta_tpu.commands.update import UpdateCommand

    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", pa.table({
        "id": np.arange(100, dtype=np.int64),
        "cat": pa.array(["old"] * 100)})).run()
    cache = ColumnCache.instance()
    with conf.set_temporarily(**FORCE):
        t0 = scan_to_table(log.update(), ["cat = 'old'"])
    assert t0.num_rows == 100 and cache.resident_bytes() > 0
    epoch0 = cache.epoch(log.log_path)
    UpdateCommand(log, {"cat": "'new'"}, "id < 50").run()
    assert cache.epoch(log.log_path) == epoch0 + 1
    with conf.set_temporarily(**FORCE):
        t_new = scan_to_table(log.update(), ["cat = 'new'"]).sort_by("id")
        t_old = scan_to_table(log.update(), ["cat = 'old'"]).sort_by("id")
    assert t_new.column("id").to_pylist() == list(range(50))
    assert t_old.column("id").to_pylist() == list(range(50, 100))


def test_delete_rewrite_cannot_serve_stale_lane(tmp_table):
    from delta_tpu.commands.delete import DeleteCommand

    log = _mk_table(tmp_table, files=2)
    cache = _resident_after_scan(log)
    epoch0 = cache.epoch(log.log_path)
    DeleteCommand(log, "id < 100").run()  # rewrite mode (no DV conf)
    assert cache.epoch(log.log_path) == epoch0 + 1
    with conf.set_temporarily(**FORCE):
        t = scan_to_table(log.update(), ["id < 200"])
    assert min(t.column("id").to_pylist()) >= 100
    host, dev = _both(log, "cat = 'beta'")
    assert host.equals(dev)


def test_restore_cannot_serve_stale_lane(tmp_table):
    from delta_tpu.commands.restore import RestoreCommand

    log = DeltaLog.for_table(tmp_table)
    WriteIntoDelta(log, "append", pa.table({
        "id": np.arange(100, dtype=np.int64),
        "cat": pa.array(["v0"] * 100)})).run()
    v0 = log.update().version
    WriteIntoDelta(log, "append", pa.table({
        "id": np.arange(100, 200, dtype=np.int64),
        "cat": pa.array(["v1"] * 100)})).run()
    cache = ColumnCache.instance()
    with conf.set_temporarily(**FORCE):
        t = scan_to_table(log.update(), ["cat IN ('v0', 'v1')"])
    assert t.num_rows == 200 and cache.resident_bytes() > 0
    epoch0 = cache.epoch(log.log_path)
    RestoreCommand(log, version=v0).run()
    assert cache.epoch(log.log_path) == epoch0 + 1
    with conf.set_temporarily(**FORCE):
        t = scan_to_table(log.update(), ["cat IN ('v0', 'v1')"])
    assert t.num_rows == 100
    assert set(t.column("cat").to_pylist()) == {"v0"}


def test_register_refused_when_epoch_moved():
    """A decode racing a rewrite is served but never cached: register under
    a stale epoch is refused, and a slipped-in stale entry is dropped by
    the get-side guard."""
    cache = ColumnCache.instance()
    lp = "/tbl/_delta_log"
    e = ResidentColumn(lp, "part-0.parquet", "c",
                       np.arange(8, dtype=np.int64), np.ones(8, bool),
                       None, epoch=cache.epoch(lp))
    cache.bump_epoch(lp)
    assert cache.register(e) is False
    assert cache.get(lp, "part-0.parquet", "c") is None
    # belt-and-braces: force a stale entry in and read through the guard
    e2 = ResidentColumn(lp, "part-1.parquet", "c",
                        np.arange(8, dtype=np.int64), np.ones(8, bool),
                        None, epoch=0)
    with cache._lock:
        cache._entries[(lp, "part-1.parquet", "c")] = e2
    assert cache.get(lp, "part-1.parquet", "c") is None
    assert not e2.is_resident


# -- eviction ----------------------------------------------------------------


def test_lru_eviction_under_max_bytes():
    cache = ColumnCache.instance()
    lp = "/tbl/_delta_log"
    entries = [
        ResidentColumn(lp, f"part-{i}.parquet", "c",
                       np.arange(4096, dtype=np.int64), np.ones(4096, bool),
                       None, epoch=0)
        for i in range(4)
    ]
    one = entries[0].nbytes
    c0 = dict(telemetry.counters())
    with conf.set_temporarily(**{
            "delta.tpu.columnCache.maxBytes": one * 2}):
        for e in entries:
            cache.register(e)
    assert cache.resident_bytes() <= one * 2
    # LRU order: the earliest-registered entries lost residency first
    assert not entries[0].is_resident and not entries[1].is_resident
    assert entries[3].is_resident
    assert telemetry.counters().get("columnCache.evictions", 0) \
        > c0.get("columnCache.evictions", 0)


def test_hbm_budget_pressure_applies_to_column_cache():
    cache = ColumnCache.instance()
    lp = "/tbl/_delta_log"
    e = ResidentColumn(lp, "part-0.parquet", "c",
                       np.arange(4096, dtype=np.int64), np.ones(4096, bool),
                       None, epoch=0)
    cache.register(e)
    assert hbm_ledger.column_cache_allowance() is None  # no budget set
    with conf.set_temporarily(**{"delta.tpu.device.hbmBudgetBytes": 16}):
        assert hbm_ledger.column_cache_allowance() is not None
        assert hbm_ledger.over_budget()
        assert hbm_ledger.maybe_relieve()
    assert cache.resident_bytes() == 0
    assert not e.is_resident


def test_residency_gauge_published():
    from delta_tpu.obs import fleet

    cache = ColumnCache.instance()
    lp = "/tbl/_delta_log"
    e = ResidentColumn(lp, "part-0.parquet", "c",
                       np.arange(64, dtype=np.int64), np.ones(64, bool),
                       None, epoch=0)
    cache.register(e)
    label = fleet.table_label("/tbl")
    g = telemetry.gauges("columnCache.residentBytes")
    assert any(dict(k[1]).get("table") == label and v == e.nbytes
               for k, v in g.items())
    cache.bump_epoch(lp)
    g = telemetry.gauges("columnCache.residentBytes")
    assert any(dict(k[1]).get("table") == label and v == 0
               for k, v in g.items())


# -- compile_residual lowering ----------------------------------------------


TYPES = None


def _types():
    from delta_tpu.schema.types import (DateType, DecimalType, DoubleType,
                                        IntegerType, StringType,
                                        TimestampType)

    return {"a": IntegerType(), "s": StringType(), "d": DateType(),
            "ts": TimestampType(), "x": DoubleType(),
            "m": DecimalType(10, 2), "w": DecimalType(20, 2)}


def test_residual_string_literals_become_code_binds():
    plan = jaxeval.compile_residual(
        parse_predicate("s = 'foo' AND s != 'bar'"), _types(), ())
    assert len(plan.str_binds) == 2
    assert {b[2] for b in plan.str_binds} == {"foo", "bar"}
    assert all(b[1] == "s" for b in plan.str_binds)
    assert plan.refs == frozenset({"s"})


def test_residual_temporal_literals_become_epoch_ints():
    plan = jaxeval.compile_residual(
        parse_predicate("d >= '2024-01-01'"), _types(), ())
    assert plan.expr.sql() == "(d >= 19723)"
    plan = jaxeval.compile_residual(
        parse_predicate("ts < '2024-06-01 12:00:00'"), _types(), ())
    us = int(dt.datetime(2024, 6, 1, 12,
                         tzinfo=dt.timezone.utc).timestamp() * 1_000_000)
    assert plan.expr.sql() == f"(ts < {us})"


def test_residual_date_vs_timestamp_midnight_combine():
    # date literal against a timestamp lane coerces at midnight UTC
    plan = jaxeval.compile_residual(
        parse_predicate("ts >= '2024-03-05'"), _types(), ())
    us = int(dt.datetime(2024, 3, 5,
                         tzinfo=dt.timezone.utc).timestamp() * 1_000_000)
    assert plan.expr.sql() == f"(ts >= {us})"


@pytest.mark.parametrize("bad", [
    "s < 'm'",                 # string ordering has no code semantics
    "upper(s) = 'A'",          # string function
    "m * 2 > 5",               # decimal arithmetic stays on host
    "m > 5e0",                 # an inexact literal against a decimal lane
    "w > 5",                   # decimal(20, 2): no int64 lane
    "d = ts",                  # mixed temporal compare
    "a = 'five'",              # string literal vs numeric lane
])
def test_residual_gates_raise(bad):
    with pytest.raises(jaxeval.NotDeviceCompilable):
        jaxeval.compile_residual(parse_predicate(bad), _types(), ())


def test_residual_string_partition_column_gated():
    from delta_tpu.schema.types import StringType

    with pytest.raises(jaxeval.NotDeviceCompilable):
        jaxeval.compile_residual(parse_predicate("pc = 'x'"),
                                 {"pc": StringType()}, ("pc",))


def test_civil_kernel_matches_python_calendar():
    """The Hinnant civil-from-days lowering must agree with datetime for
    dates across eras, leap years, and the epoch boundary."""
    import jax.numpy as jnp

    from delta_tpu.utils.jaxcompat import enable_x64

    days = np.array(
        [-719162, -1, 0, 1, 59, 60, 19723, 20514,
         (dt.date(2000, 2, 29) - dt.date(1970, 1, 1)).days,
         (dt.date(2100, 3, 1) - dt.date(1970, 1, 1)).days,
         (dt.date(1900, 2, 28) - dt.date(1970, 1, 1)).days],
        dtype=np.int32)
    expect = [dt.date(1970, 1, 1) + dt.timedelta(days=int(v)) for v in days]
    for fn_name, attr in (("year", "year"), ("month", "month"),
                          ("day", "day")):
        plan = jaxeval.compile_residual(
            parse_predicate(f"{fn_name}(d) >= -99999"), _types(), ())
        kernel = jaxeval.compile_expr(plan.expr.children[0])
        with enable_x64():
            env = {"d": jaxeval.DeviceColumn(jnp.asarray(days),
                                             jnp.ones(len(days), bool))}
            got = np.asarray(kernel(env).values)
        assert got.tolist() == [getattr(e, attr) for e in expect], fn_name


def test_residual_plan_is_jit_cache_key():
    """Two scans with the same predicate shape share one jitted kernel:
    the rewritten expression hashes stably."""
    p1 = jaxeval.compile_residual(parse_predicate("a > 5"), _types(), ())
    p2 = jaxeval.compile_residual(parse_predicate("a > 5"), _types(), ())
    assert hash(p1.expr) == hash(p2.expr)
    from delta_tpu.ops.column_cache import _mask_kernel

    assert _mask_kernel(p1.expr) is _mask_kernel(p2.expr)


# -- float lanes are int64 order keys (a TPU's float64 is not IEEE) -----------


def test_f64_order_key_is_the_float_order():
    rng = np.random.RandomState(3)
    x = np.concatenate([
        rng.randn(2000) * 10.0 ** rng.randint(-300, 300, 2000),
        [0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 1e300, -1e300,
         0.5, np.nextafter(0.5, 0), np.nextafter(0.5, 1)],
    ])
    keys = jaxeval.f64_order_key(x)
    assert keys.dtype == np.int64
    i, j = rng.randint(0, len(x), (2, 20000))
    assert np.array_equal(x[i] < x[j], keys[i] < keys[j])
    assert np.array_equal(x[i] == x[j], keys[i] == keys[j])
    assert jaxeval.f64_order_key(-0.0) == jaxeval.f64_order_key(0.0) == 0


def _mk_float_table(path):
    log = DeltaLog.for_table(path)
    specials = [0.5, np.nextafter(0.5, 0), np.nextafter(0.5, 1), 0.0, -0.0,
                float("nan"), -float("nan"), 1e300, -1e300, 5e-324, 3.5e38,
                float("inf"), -float("inf"), 0.2, 0.4, None]
    rng = np.random.RandomState(11)
    for i in range(2):
        vals = specials + rng.rand(300 - len(specials)).tolist()
        WriteIntoDelta(log, "append", pa.table({
            "id": np.arange(i * 300, (i + 1) * 300, dtype=np.int64),
            "x": pa.array(vals, pa.float64()),
            "f": pa.array(vals, pa.float64()).cast(pa.float32(), safe=False),
        })).run()
    return log


FLOAT_KEY_PREDS = [
    "x > 0.5", "x >= 0.5", "x < 0.5", "x <= 0.5", "x = 0.5", "x != 0.5",
    "0.5 < x", "x <=> 0.5", "x IN (0.5, 0.0, 7)", "x IS NULL",
    "x IS NOT NULL AND x <= 0", "x BETWEEN 0.2 AND 0.4", "x > 1e299",
    "x > 3.4e38 AND id < 400", "NOT (x >= 0.5)", "NOT (x < 0.5)",
    "x >= 0 OR id > 500", "f > 0.5", "f <= 0.2",
]


@pytest.mark.parametrize("pred", FLOAT_KEY_PREDS)
def test_device_compares_float_lanes_as_order_keys(tmp_table, pred):
    log = _mk_float_table(tmp_table)
    engaged = telemetry.counters().get("scan.device.engaged", 0)
    host, dev = _both(log, pred)
    # ids name the rows (a NaN row never equals itself under Table.equals)
    assert host.column("id").equals(dev.column("id")), pred
    assert telemetry.counters().get("scan.device.engaged", 0) == engaged + 1
    lanes = [e for (_l, _f, c), e in ColumnCache.instance()._entries.items()
             if c in ("x", "f")]
    assert lanes and all(str(e.values.dtype) == "int64" for e in lanes)


@pytest.mark.parametrize("pred", [
    "x * 2 > 1", "x / 2 > 0.1", "sqrt(x) > 0.5", "id > 5.5", "x > id",
    "CAST(id AS DOUBLE) > 2", "id / 2 > 3", "coalesce(x, 0.0) > 0.5",
    ir.Ne(ir.Column("x"), ir.Literal(float("nan"))),
])
def test_device_declines_float_arithmetic(pred):
    from delta_tpu.schema.types import DoubleType, LongType

    types = {"x": DoubleType(), "id": LongType()}
    if isinstance(pred, str):
        pred = parse_predicate(pred)
    with pytest.raises(jaxeval.NotDeviceCompilable, match="not exact"):
        jaxeval.compile_residual(pred, types, ())
