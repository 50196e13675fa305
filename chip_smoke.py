#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the engine's device paths start
and answer correctly on an attached TPU.

One process drives the public API once at the repo's harness deployment
(`BASELINE.json` config 2: an 8-column TPC-DS ``store_sales`` slice,
10,000,000 target rows in >=16 files with deletion vectors on, 1,000,000-row
upsert sources, half existing keys and half fresh): three MERGEs (device
cold, device resident, auto-routed), forced device-planned filtered scans
with device residual masks (cold lanes, then cached), float64 predicates
that only an exact device answers (bounds one ulp from a stored value, and a
small table of doubles beyond float32's range — a TPU's float64 is a float32
pair) through the device file-prune tier, OPTIMIZE ZORDER BY
(ss_item_sk, ss_sold_date_sk), one more scan, and — with more than one
device visible — one mesh MERGE. Everything that comes out is compared,
row for row, against a plain numpy/pyarrow reference on the generator's
in-memory output (concatenate, last write per key wins; filters via
``pyarrow.compute``); MERGE metrics, history and the device counters are
checked too.

Data is generated from ``--seed``; nothing is read from the network, a home
directory or an earlier run; tables live in a temp dir that is removed.
Exit code 0 only if JAX found a TPU and every check held. The last two
stdout lines are ``summary: {...}`` (the preflight facts, sizes, per-step
seconds, join path per MERGE, counters, peak bytes) and the verdict, one
JSON object with exactly these keys:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``.
Without a TPU neither is printed. Wall seconds in the summary are smoke
readings (one run, compiles included where labelled ``first``), not
benchmark numbers.

    python3 chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time
import traceback
from typing import Any, Dict, List, Tuple

import numpy as np

N_TARGET = 10_000_000
N_SOURCE = 1_000_000
N_FILES = 16
KEY = "ss_item_sk"
MERGE_ON = f"t.{KEY} = s.{KEY}"
ZORDER_BY = (KEY, "ss_sold_date_sk")
FORCE_MERGE = {"delta.tpu.merge.devicePath.mode": "force"}
FORCE_SCAN = {
    "delta.tpu.read.deviceResidual.mode": "force",
    "delta.tpu.stateCache.devicePlan.mode": "force",
}
# a strict bound does not lower exactly to the resident planner's ranges:
# the generic prune tier plans it, on the device at any file count with this
FORCE_PRUNE = {"delta.tpu.device.pruning.minFiles": 1}
Terms = List[Tuple[str, str, Any]]
# non-strict bounds lower EXACTLY to the resident planner's ranges, so the
# device plan kernel (not the generic prune) serves these scans
_INT_RANGE = [("ss_customer_sk", ">=", 100_000), ("ss_customer_sk", "<=", 150_000)]
_FLOAT_CMP = [("ss_sales_price", ">=", 95.0)]
_DATE_RANGE = [("ss_sold_date_sk", ">=", 2_450_500), ("ss_sold_date_sk", "<=", 2_451_000)]
FILTERS: Dict[str, Terms] = {
    "int64_range": _INT_RANGE,
    "float64_cmp": _FLOAT_CMP,
    "conjunction": _INT_RANGE + _FLOAT_CMP + _DATE_RANGE,
}
# over `float_edges()`: each loses or gains a row where float64 compares
# round to ~48 mantissa bits or to float32's exponent range
EDGE_FILTERS: Dict[str, Terms] = {
    "gt_tenth": [("x", ">", 0.1)],
    "lt_tenth": [("x", "<", 0.1)],
    "ne_tenth": [("x", "!=", 0.1)],
    "gt_1e299": [("x", ">", 1e299)],
    "tiny": [("x", ">", 0.0), ("x", "<", 1e-299)],
}


class SmokeFailure(AssertionError):
    """A check against the reference or the expected device path failed."""


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- preflight -----------------------------------------------------------------


def _cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def preflight() -> Dict[str, Any]:
    """Refuse to run without a TPU — before any table is built — and say
    what was found. JAX itself falls back to the CPU with one log line."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"chip_smoke: no TPU: jax.devices()[0].platform={platform!r} "
            f"({len(devices)} device(s)); refusing to run")
    from importlib import metadata

    import jaxlib

    from delta_tpu.utils import jaxcache

    jaxcache.ensure_compilation_cache()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    cache_dir = jax.config.jax_compilation_cache_dir
    facts = {
        "device": {"platform": platform, "kind": devices[0].device_kind,
                   "count": len(devices)},
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "cache": {"dir": cache_dir,
                  "entries_before": _cache_entries(cache_dir)},
    }
    print("preflight:", json.dumps(facts), flush=True)
    return facts


# -- data + the plain reference --------------------------------------------


def store_sales(keys: np.ndarray, rng: np.random.Generator):
    """The harness's 8-column store_sales slice: int64 key, four int64
    dimensions, three float64 measures."""
    import pyarrow as pa

    n = len(keys)
    return pa.table({
        KEY: keys.astype(np.int64),
        "ss_customer_sk": rng.integers(0, 1_000_000, n, dtype=np.int64),
        "ss_sold_date_sk": rng.integers(2_450_000, 2_452_000, n, dtype=np.int64),
        "ss_store_sk": rng.integers(0, 500, n, dtype=np.int64),
        "ss_quantity": rng.integers(1, 100, n, dtype=np.int64),
        "ss_sales_price": rng.random(n) * 100,
        "ss_ext_discount_amt": rng.random(n) * 10,
        "ss_net_paid": rng.random(n) * 90,
    })


def ref_upsert(ref, source):
    """Reference MERGE: concatenate, last write per key wins; sorted by key."""
    import pyarrow as pa

    both = pa.concat_tables([ref, source])
    keys = both.column(KEY).to_numpy()
    _, first_from_end = np.unique(keys[::-1], return_index=True)
    return both.take(pa.array(len(keys) - 1 - first_from_end))


def float_edges(rng: np.random.Generator):
    """A small table of the doubles an inexact device float64 gets wrong:
    neighbours one ulp apart, magnitudes beyond float32's range, NaN, the
    infinities, signed zeros and NULL, among random fill."""
    import pyarrow as pa

    edges = [0.1, np.nextafter(0.1, 0), np.nextafter(0.1, 1), 0.0, -0.0,
             np.nan, 1e300, -1e300, 5e-324, 1e-300, 3.5e38, np.inf, -np.inf,
             None]
    x = edges + rng.random(4096 - len(edges)).tolist()
    return pa.table({KEY: np.arange(len(x), dtype=np.int64),
                     "x": pa.array(x, pa.float64())})


def ref_filter(ref, terms):
    import pyarrow.compute as pc

    ops = {">=": pc.greater_equal, "<=": pc.less_equal, ">": pc.greater,
           "<": pc.less, "!=": pc.not_equal}
    mask = None
    for col, op, value in terms:
        m = ops[op](ref.column(col), value)
        mask = m if mask is None else pc.and_(mask, m)
    return ref.filter(mask)


def filter_sql(terms) -> str:
    return " AND ".join(f"{c} {op} {v!r}" for c, op, v in terms)


def same_rows(got, ref, what: str) -> None:
    """``got`` (any row order) must be row-identical to ``ref`` (sorted by
    key; keys are unique)."""
    got = got.select(ref.column_names).sort_by(KEY)
    _check(got.num_rows == ref.num_rows,
           f"{what}: {got.num_rows} rows, reference has {ref.num_rows}")
    import pyarrow as pa

    for name in ref.column_names:
        a, b = got.column(name), ref.column(name)
        _check(a.type == b.type, f"{what}: {name} is {a.type}, not {b.type}")
        same = a.equals(b)
        if not same and pa.types.is_floating(a.type):  # NaN is not NaN
            same = a.is_null().equals(b.is_null()) and np.array_equal(
                a.to_numpy(), b.to_numpy(), equal_nan=True)
        _check(same, f"{what}: column {name} differs from the reference")


# -- the run ----------------------------------------------------------------


class Smoke:
    """One smoke run: the table, the reference state, and what was seen."""

    def __init__(self, workdir: str, seed: int = 0, n_target: int = N_TARGET,
                 n_source: int = N_SOURCE, n_files: int = N_FILES):
        from delta_tpu.utils import telemetry

        self.path = os.path.join(workdir, "store_sales")
        self.seed, self.n_target, self.n_source = seed, n_target, n_source
        self.n_files = n_files
        self.rng = np.random.default_rng(seed)
        self.table = None  # DeltaTable
        self.ref = None  # expected table state, sorted by key
        self.next_fresh = 2 * n_target  # target keys live in [0, 2n)
        self.steps_s: Dict[str, float] = {}
        self.merges: List[Dict[str, Any]] = []
        self.scans: List[Dict[str, Any]] = []
        self.forced_scans = self.resident_scans = 0
        self._counters0 = telemetry.counters()

    def counters(self, *names: str) -> Dict[str, int]:
        from delta_tpu.utils import telemetry

        now = telemetry.counters()
        return {n: now.get(n, 0) - self._counters0.get(n, 0) for n in names}

    def _timed(self, step: str, fn):
        t0 = time.perf_counter()
        out = fn()
        self.steps_s[step] = round(time.perf_counter() - t0, 3)
        print(f"  {step}: {self.steps_s[step]} s", flush=True)
        return out

    # -- load ----------------------------------------------------------------

    def load(self) -> None:
        from delta_tpu import DeltaTable

        n = self.n_target
        keys = self.rng.permutation(2 * n)[:n]
        target = self._timed("generate", lambda: store_sales(keys, self.rng))

        def write():
            per = -(-n // self.n_files)
            self.table = DeltaTable.create(
                self.path, data=target.slice(0, per),
                configuration={"delta.tpu.enableDeletionVectors": "true"})
            for start in range(per, n, per):
                self.table.write(target.slice(start, per))

        self._timed("load", write)
        files = len(self.table.delta_log.update().all_files)
        _check(files >= self.n_files, f"load wrote {files} files")
        self.ref = target.sort_by(KEY)

    # -- MERGE ---------------------------------------------------------------

    def _source(self):
        """A new upsert batch: half existing keys, half fresh."""
        half = self.n_source // 2
        existing = self.rng.choice(self.ref.column(KEY).to_numpy(), half,
                                   replace=False)
        fresh = np.arange(self.next_fresh,
                          self.next_fresh + self.n_source - half)
        self.next_fresh += len(fresh)
        keys = np.concatenate([existing, fresh])
        self.rng.shuffle(keys)
        return store_sales(keys, self.rng), half, len(fresh)

    def merge(self, label: str, confs: Dict[str, Any], expect: str = None):
        """One upsert through the public builder; returns its record
        (router event, audit, metrics). ``expect`` pins the join path."""
        from delta_tpu.obs import router_audit
        from delta_tpu.utils import telemetry
        from delta_tpu.utils.config import conf

        source, n_upd, n_ins = self._source()

        def run():
            with conf.set_temporarily(**confs):
                return (self.table.alias("t")
                        .merge(source, MERGE_ON, source_alias="s")
                        .when_matched_update_all()
                        .when_not_matched_insert_all()
                        .execute())

        metrics = self._timed(label, run)
        router = dict(telemetry.recent_events("delta.merge.router")[-1].data)
        audit = router_audit.last_audit()
        phases = (audit.extra.get("phases", {})
                  if audit is not None and audit.op == "merge.join" else {})
        rec = {
            "merge": label, "conf": confs, "decision": router.get("decision"),
            "router": router,
            "key_decode_plus_join_ms": round(
                phases.get("key_decode_ms", 0.0) + phases.get("join_ms", 0.0), 1),
            "predicted_ms": (audit.to_dict()["predictedMs"]
                             if phases else None),
            "updated": metrics["numTargetRowsUpdated"],
            "inserted": metrics["numTargetRowsInserted"],
            "wall_s": self.steps_s[label],
        }
        self.merges.append(rec)
        print(f"  {label}: decision={rec['decision']} router={router} "
              f"key_decode+join={rec['key_decode_plus_join_ms']} ms "
              f"predicted={rec['predicted_ms']}", flush=True)
        _check((rec["updated"], rec["inserted"]) == (n_upd, n_ins),
               f"{label}: updated/inserted {rec['updated']}/{rec['inserted']}"
               f", expected {n_upd}/{n_ins}")
        if expect is not None:
            _check(rec["decision"] == expect,
                   f"{label}: decision={rec['decision']}, expected {expect} "
                   f"(router event: {router})")
        self.ref = ref_upsert(self.ref, source)
        return rec

    # -- filtered scans --------------------------------------------------

    def scan(self, label: str, terms: Terms, table=None, ref=None
             ) -> Dict[str, Any]:
        """One forced device-masked filtered scan, checked against the
        reference — and that the device picked its files: the ``resident``
        plan kernel, or, where a strict bound keeps the predicate from
        lowering exactly to its ranges, the generic tier's ``device-prune``."""
        from delta_tpu.utils import telemetry
        from delta_tpu.utils.config import conf

        table = self.table if table is None else table
        names = ("columnCache.hits", "columnCache.misses",
                 "stateCache.scan.resident")
        before = self.counters(*names)

        def run():
            with conf.set_temporarily(**FORCE_SCAN, **FORCE_PRUNE):
                return table.to_arrow(filters=[filter_sql(terms)])

        got = self._timed(label, run)
        self.forced_scans += 1
        d = {n.split(".")[-1]: v - before[n]
             for n, v in self.counters(*names).items()}
        same_rows(got, ref_filter(self.ref if ref is None else ref, terms),
                  label)
        if d["resident"]:
            self.resident_scans += 1
            planned = "resident"
        else:
            prune = telemetry.recent_events("delta.scan.prune")[-1].data
            planned = f"{prune.get('tier')}-prune"
        plan = ("device-prune" if any(op in ("<", ">") for _, op, _ in terms)
                else "resident")
        _check(planned == plan, f"{label}: files planned by {planned}, "
                                f"expected {plan}")
        rec = {"scan": label, "rows": got.num_rows, "plan": planned,
               "wall_s": self.steps_s[label], "lane_hits": d["hits"],
               "lane_misses": d["misses"]}
        self.scans.append(rec)
        return rec

    def scans_cold_then_cached(self) -> None:
        for name, terms in FILTERS.items():
            self.scan(f"scan_{name}_first", terms)
            warm = self.scan(f"scan_{name}_warm", terms)
            _check(warm["lane_hits"] > 0 and warm["lane_misses"] == 0,
                   f"scan {name}: second run did not serve from resident "
                   f"lanes ({warm})")

    def float_scans(self) -> None:
        """float64 predicates only an exact device answers: the window one
        ulp either side of a stored price must hold that row and `!=` its
        neighbour must keep it; then `EDGE_FILTERS` over `float_edges`."""
        from delta_tpu import DeltaTable

        mid = self.ref.slice(self.ref.num_rows // 2, 1)
        v = mid.column("ss_sales_price")[0].as_py()
        lo, hi = float(np.nextafter(v, -np.inf)), float(np.nextafter(v, np.inf))
        window = self.scan("scan_float64_ulp_window", [
            ("ss_sales_price", ">", lo), ("ss_sales_price", "<", hi)])
        _check(window["rows"] >= 1, f"no row holds {v!r}: {window}")
        c = mid.column("ss_customer_sk")[0].as_py()
        self.scan("scan_float64_ne_neighbour", [
            ("ss_customer_sk", ">=", c), ("ss_customer_sk", "<=", c + 50_000),
            ("ss_sales_price", "!=", hi)])
        edges = float_edges(self.rng)
        table = DeltaTable.create(self.path + "_float_edges", data=edges)
        for name, terms in EDGE_FILTERS.items():
            self.scan(f"scan_edges_{name}", terms, table=table, ref=edges)

    # -- OPTIMIZE ZORDER, read back ---------------------------------------

    def optimize(self) -> None:
        metrics = self._timed(
            "optimize_zorder",
            lambda: self.table.optimize().execute_z_order_by(*ZORDER_BY))
        _check(metrics.get("numRemovedFiles", 0) >= self.n_files,
               f"OPTIMIZE rewrote {metrics}")

    def read_back(self) -> None:
        from delta_tpu import DeltaLog, DeltaTable

        DeltaLog.clear_cache()
        fresh = DeltaTable.for_path(self.path)
        got = self._timed("read_back", fresh.to_arrow)
        same_rows(got, self.ref, "read back")
        versions = [h["version"] for h in fresh.history()]
        _check(versions == list(range(len(versions) - 1, -1, -1)),
               f"history is not consecutive: {versions}")

    def run(self) -> Dict[str, Any]:
        """Every phase in order; raises on the first failed check. Returns
        what the ``summary:`` line carries."""
        import jax

        self.load()
        self.merge("merge1_force_first", FORCE_MERGE, expect="device-cold")
        self.merge("merge2_force_resident", FORCE_MERGE, expect="resident")
        merge_counters = self.counters(
            "merge.device.engaged", "merge.device.declined",
            "merge.device.fallback", "merge.device.cacheHit")
        _check(merge_counters["merge.device.engaged"] == 2
               and merge_counters["merge.device.declined"] == 0
               and merge_counters["merge.device.fallback"] == 0,
               f"after two forced MERGEs: {merge_counters}")
        # the routed leg: printed, not asserted (cost constants are stale)
        self.merge("merge3_auto", {"delta.tpu.merge.devicePath.mode": "auto"})
        self.scans_cold_then_cached()
        self.float_scans()
        self.optimize()
        self.scan("scan_after_optimize_first", FILTERS["conjunction"])
        n_dev = len(jax.devices())
        if n_dev > 1:
            # the shard_map join: no resident lane may pre-empt the mesh
            self.merge("merge_mesh_force_first", dict(
                FORCE_MERGE, **{
                    "delta.tpu.merge.devicePath.preferMesh": True,
                    "delta.tpu.merge.keyCache.enabled": False}),
                expect="device-upload")
        self.read_back()
        scan_counters = self.counters(
            "scan.device.engaged", "scan.device.fallback",
            "scan.device.declined", "scan.prune.deviceFallback",
            "dist.degraded.plan", "stateCache.scan.resident")
        _check(scan_counters["scan.device.engaged"] == self.forced_scans
               and scan_counters["scan.device.fallback"] == 0
               and scan_counters["scan.prune.deviceFallback"] == 0
               and scan_counters["dist.degraded.plan"] == 0
               and scan_counters["stateCache.scan.resident"] == self.resident_scans,
               f"after {self.forced_scans} forced scans "
               f"({self.resident_scans} resident-planned): {scan_counters}")
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                 for d in jax.devices()]
        if n_dev > 1 and jax.devices()[0].platform == "tpu":
            _check(all(p for p in peaks),
                   f"a device never held memory: peak_bytes_in_use={peaks}")
        return {
            "sizes": {"target_rows": self.n_target,
                      "source_rows": self.n_source, "files": self.n_files,
                      "final_rows": self.ref.num_rows},
            "seed": self.seed,
            "steps_s": self.steps_s,
            "merges": self.merges,
            "scans": self.scans,
            "counters": dict(
                merge_counters, **scan_counters,
                **self.counters("merge.keyCache.builds",
                                "merge.keyCache.advances",
                                "columnCache.hits", "columnCache.misses")),
            "peak_bytes_in_use": peaks,
        }


def link_profile() -> Dict[str, float]:
    """What `parallel/link._probe` measures on this host↔device link."""
    from delta_tpu.parallel import link

    p = link.profile()
    return {"up_MBps": round(p.up_mbps, 1), "down_MBps": round(p.down_mbps, 1),
            "latency_ms": round(p.latency_s * 1e3, 3)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    result: Dict[str, Any] = {"ok": False, **preflight()}
    t0 = time.perf_counter()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        result["link"] = link_profile()
        print("link:", json.dumps(result["link"]), flush=True)
        smoke = Smoke(workdir, args.seed)
        try:
            result.update(smoke.run())
        finally:
            # what was seen so far, for whoever reads a failed run's output
            result.setdefault("steps_s", smoke.steps_s)
            result.setdefault("merges", smoke.merges)
        result["ok"] = True
    except Exception as e:  # noqa: BLE001 — exit boundary: report and fail
        traceback.print_exc()
        result["error"] = f"{type(e).__name__}: {e}"[:500]
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    result["cache"]["entries_after"] = _cache_entries(result["cache"]["dir"])
    result["wall_s"] = round(time.perf_counter() - t0, 1)
    print("summary:", json.dumps(result), flush=True)
    # the verdict: the last stdout line, exactly these two keys
    print(json.dumps({"ok": result["ok"], "device": result["device"]}),
          flush=True)
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
