"""Host↔device link calibration — the cost model behind executor routing.

The reference never needs this: its data plane and control plane share one
JVM address space, and Spark's planner assumes executor-local data. A
TPU-native engine has a real boundary instead — host Arrow buffers vs
device HBM — and the profitability of a device kernel is decided by the
*link*, not the FLOPs. On a PCIe/DMA-attached chip host↔device moves
10-50 GB/s and every sizable kernel wins; behind a slow link bulk
transfers dominate everything, and the only winning device kernels are
the ones whose operands already live in HBM or fit in a few MB.

So executors ask this module before shipping operands:

    est = link.estimate(up_bytes, down_bytes, device_flop_rows)
    if est.device_s < host_estimate_s: ...launch device kernel...

Calibration runs once per process, lazily, *after* forcing a trivial XLA
execution (so we measure the steady-state link, not the fresh-process fast
path), and costs two ~1 MB probes. `delta.tpu.link.uploadMBps` /
`downloadMBps` override the probe for tests and known deployments.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Optional, Sequence

from delta_tpu.utils import telemetry
from delta_tpu.utils.jaxcache import ensure_compilation_cache

__all__ = [
    "LinkProfile", "Estimate", "profile", "estimate_device_s", "reset",
    "KERNEL_S_PER_ROW", "HOST_JOIN_S_PER_ROW",
    "HOST_PRUNE_S_PER_CELL", "DEVICE_PRUNE_S_PER_CELL",
    "HOST_KEY_DECODE_S_PER_ROW", "RESIDENT_PROBE_S_PER_ROW",
    "RESIDENT_PROBE_FIXED_S", "RESIDENT_FINALIZE_S_PER_ROW",
    "RESIDENT_PAIR_S_PER_ROW", "DEVICE_SORT_S_PER_ROW",
    "HOST_RESIDUAL_S_PER_CELL", "DEVICE_RESIDUAL_S_PER_CELL",
    "SHARD_DISPATCH_S", "SHARD_GATHER_S_PER_SHARD", "DIST_ITEM_S",
    "resident_probe_device_s", "cold_merge_device_s",
    "host_residual_filter_s", "device_residual_mask_s",
    "sharded_plan_device_s", "dist_execute_s",
    "CALIBRATABLE", "constant", "set_calibrated", "calibrated_constants",
    "clear_calibrated", "to_device", "to_host",
]

_PROBE_BYTES = 1 << 20  # 1 MB
# sort-merge probe throughput on one chip, measured: ~1.8s for 17.8M rows.
# Comparable per-row to the host hash join on one core — a single chip wins
# on the join itself only by freeing the host; the real speedup is the mesh
# (per-shard sort is rows/p) and link-resident operands.
KERNEL_S_PER_ROW = 1.1e-7
# Arrow hash join, one host core, measured: ~1.1s for 11M rows
HOST_JOIN_S_PER_ROW = 1.0e-7
# batched min/max pruning, host numpy: ~0.6s for 100 preds x 1M files x 4
# stat columns (DRAM-bound boolean reductions)
HOST_PRUNE_S_PER_CELL = 1.5e-9
# projected Parquet key-column decode, host Arrow: ~260ms for 10M rows —
# the cost the resident-key probe avoids and the host join must pay
HOST_KEY_DECODE_S_PER_ROW = 2.6e-8
# resident-key membership probe kernel (ops/key_cache._probe_sorted_kernel,
# r5 block-bucketed brute design): measured 0.43s at 10M and 0.68-0.71s at
# 100M slab rows on one v5e — a ~0.4s dispatch floor plus ~3e-9 s/row of
# VPU compare/reduce work. The old per-probe-sort kernel cost 3.2e-8 s/row.
RESIDENT_PROBE_S_PER_ROW = 3.0e-9
# fixed per-probe device overhead EXCLUDING round trips (those are charged
# via the latency terms in resident_probe_device_s): kernel launch chain +
# the m<=1M source sort
RESIDENT_PROBE_FIXED_S = 0.3
# LEGACY (pre-fused path) host-side finalize work per TARGET row: bitmask
# unpack + bits_for_file mapping + host first-match pairing recovery. The
# fused probe computes the pairing on device and downloads O(matched)
# pairs instead; kept exported for calibration comparisons.
RESIDENT_FINALIZE_S_PER_ROW = 3.0e-8
# fused-path host finalize per MATCHED pair: positions searchsorted +
# scatter into t_first_s (estimate pending on-device recalibration)
RESIDENT_PAIR_S_PER_ROW = 1.0e-7
# device slab sort (lax.sort of the key lane + permutation), amortized per
# row — paid once per cold build / tail append, not per probe
DEVICE_SORT_S_PER_ROW = 5.0e-8
# residual predicate over decoded Arrow columns, host compute kernels
# (`expr/vectorized`): DRAM-bound compares + Kleene combines per cell
HOST_RESIDUAL_S_PER_CELL = 1.5e-8
# the same residual from HBM-resident SoA lanes (`ops/column_cache`), one
# fused jitted pass: VPU elementwise compares at HBM bandwidth
DEVICE_RESIDUAL_S_PER_CELL = 5.0e-10
# fixed per-dispatch overhead of a shard_map launch over the mesh: program
# dispatch + the all-gather of the surviving-bitmap shards. Dominates tiny
# plans — the router must not shard a 10k-file table over 8 devices.
SHARD_DISPATCH_S = 2.0e-3
# incremental gather cost per participating shard (each shard contributes
# its packed survivor bitmap to the ICI all-gather)
SHARD_GATHER_S_PER_SHARD = 2.0e-4
# per-item scheduling overhead of the distributed executor (deque push/pop,
# steal checks, timing capture) — charged when pricing a fan-out against
# running the same items inline
DIST_ITEM_S = 5.0e-5


# -- self-calibration --------------------------------------------------------
#
# The per-row/per-cell constants above were fit on ONE CPU host; on
# different hardware the router silently prices the wrong side. The router
# audit ledger (`obs/router_audit`) measures every routed decision against
# its prediction, and the EWMA calibrator (`obs/calibration`) re-fits these
# constants from observed samples — opt-in via
# ``delta.tpu.router.calibration.enabled`` — by installing overrides here.
# Cost functions and routers read the constants through :func:`constant`, so
# a calibrated value takes effect everywhere at once.

#: Constant names the calibrator may override.
CALIBRATABLE = frozenset({
    "KERNEL_S_PER_ROW", "HOST_JOIN_S_PER_ROW", "HOST_PRUNE_S_PER_CELL",
    "DEVICE_PRUNE_S_PER_CELL", "HOST_KEY_DECODE_S_PER_ROW",
    "RESIDENT_PROBE_S_PER_ROW", "RESIDENT_PAIR_S_PER_ROW",
    "DEVICE_SORT_S_PER_ROW", "HOST_RESIDUAL_S_PER_CELL",
    "DEVICE_RESIDUAL_S_PER_CELL",
    "SHARD_DISPATCH_S", "SHARD_GATHER_S_PER_SHARD", "DIST_ITEM_S",
})

_calibrated: dict = {}


def constant(name: str) -> float:
    """The live value of a cost-model constant: the calibrated override when
    one is installed, else the module default."""
    v = _calibrated.get(name)
    return v if v is not None else globals()[name]


def set_calibrated(name: str, value: float) -> None:
    """Install a calibrated override (``obs/calibration``). Rejects unknown
    names and non-positive values — a bad sample must not poison routing."""
    if name not in CALIBRATABLE:
        raise ValueError(f"{name!r} is not a calibratable link constant")
    value = float(value)
    if not value > 0.0:
        raise ValueError(f"calibrated {name} must be positive, got {value}")
    _calibrated[name] = value


def calibrated_constants() -> dict:
    """The installed overrides (empty when running on module defaults)."""
    return dict(_calibrated)


def clear_calibrated() -> None:
    """Back to module defaults (tests, `calibration.reset`)."""
    _calibrated.clear()


# -- counted transfers ---------------------------------------------------------
#
# What actually crossed the link, counted where it crosses: the device
# caches and kernels move their operands through this pair, so `/metrics`
# and the open span say how many bytes a request shipped each way.


def to_device(host, sharding=None):
    """``jax.device_put(host)``, counted: ``link.h2d.bytes`` (exact: the
    device array's ``nbytes``) and ``link.h2d.count``, and ``h2dBytes`` on
    the innermost open span. As asynchronous as ``device_put`` is — nothing
    here waits for the copy, so an upload has bytes and a count, no time."""
    import jax

    out = jax.device_put(host, sharding)
    nbytes = int(out.nbytes)
    telemetry.bump_counter("link.h2d.bytes", nbytes)
    telemetry.bump_counter("link.h2d.count")
    telemetry.add_span_counts(h2dBytes=nbytes)
    return out


def to_host(dev):
    """``np.asarray(dev)`` of a device array, counted: ``link.d2h.bytes``,
    ``link.d2h.count``, ``d2hBytes`` on the innermost open span, and
    ``link.d2h.waitUs``, the wall time of this blocking fetch — which holds
    the wait for the kernel that makes ``dev`` before the copy itself."""
    import numpy as np

    t0 = time.perf_counter_ns()
    out = np.asarray(dev)
    wait_us = (time.perf_counter_ns() - t0) // 1000
    nbytes = int(out.nbytes)
    telemetry.bump_counter("link.d2h.bytes", nbytes)
    telemetry.bump_counter("link.d2h.count")
    telemetry.bump_counter("link.d2h.waitUs", wait_us)
    telemetry.add_span_counts(d2hBytes=nbytes)
    return out


def resident_probe_device_s(n: int, m: int, p: "LinkProfile") -> float:
    """The router's cost model for one steady-state resident MERGE probe
    (n resident target rows, m source rows) on the FUSED path: source
    upload (int32-narrowed, optimistic), the head download (s_bits +
    matched count), the probe kernel, the compacted pair download
    (matched count unknown pre-probe: modeled at the upsert-typical m/2
    pairs x 8 bytes), the O(matched) host pair mapping, a fixed dispatch
    floor, and the probe's sequential round trips. ONE definition, called by the
    production router (`commands/merge.py`)."""
    est_pairs = m // 2
    return (
        p.upload_s(m * 4)
        + p.download_s(m // 8 + 6)
        + (n + m) * constant("RESIDENT_PROBE_S_PER_ROW")
        + p.download_s(est_pairs * 8)
        + est_pairs * constant("RESIDENT_PAIR_S_PER_ROW")
        + RESIDENT_PROBE_FIXED_S
        + 3 * p.latency_s
    )


def cold_merge_device_s(n: int, m: int, p: "LinkProfile") -> float:
    """Cost of the COLD fused device MERGE (no resident entry): the tiled
    slab upload (int32-narrowed, optimistic — in the live pipeline it
    overlaps the host Parquet key decode, so this is conservative), the
    one-time device sort, then a steady-state probe. Priced separately
    from the cache-hit case (`resident_probe_device_s`) — the router must
    not charge a hot table for an upload it will skip."""
    return (
        p.upload_s(n * 4)
        + n * constant("DEVICE_SORT_S_PER_ROW")
        + resident_probe_device_s(n, m, p)
    )
# the same cells on-device from HBM-resident f32 lanes (see ops/state_cache):
# ~2 f32 reads/cell at HBM bandwidth, fused compares
DEVICE_PRUNE_S_PER_CELL = 2.0e-11


def host_residual_filter_s(rows: int, ncols: int) -> float:
    """The router's cost model for evaluating a scan's residual predicate on
    host over already-decoded Arrow columns. Residual *evaluation* only —
    the host decode of non-predicate projection columns is common to both
    sides and cancels. ONE definition, called by `ops/column_cache`."""
    return rows * ncols * constant("HOST_RESIDUAL_S_PER_CELL")


def device_residual_mask_s(cold_rows: int, resident_rows: int, ncols: int,
                           p: "LinkProfile") -> float:
    """Cost model for the device residual-mask pass: cold predicate-column
    decode on host (resident rows skip it — that's the cache's winnings),
    the cold lane upload, one fused elementwise kernel over every row, the
    bool-mask download (~1 byte/row), and the dispatch round trips. Priced
    against :func:`host_residual_filter_s`; audited as ``scan.residual``."""
    rows = cold_rows + resident_rows
    return (
        cold_rows * ncols * constant("HOST_KEY_DECODE_S_PER_ROW")
        + p.upload_s(cold_rows * ncols * 8)
        + rows * ncols * constant("DEVICE_RESIDUAL_S_PER_CELL")
        + p.download_s(rows)
        + 2 * p.latency_s
    )


def sharded_plan_device_s(cells: int, shards: int, p: "LinkProfile") -> float:
    """Cost model for the shard_map pruning plan: each device evaluates the
    predicate over its 1/shards slice of the stat lanes in parallel, then the
    packed survivor bitmaps all-gather over ICI and the merged bitmap
    downloads (~cells/8 per predicate batch is already folded into the
    per-cell constant's fit). Priced against the single-device plan
    (``cells * DEVICE_PRUNE_S_PER_CELL``) and the host plan — the
    ``scan.plan`` router audit records which side actually won. ONE
    definition, called by `ops/state_cache` routing."""
    shards = max(int(shards), 1)
    return (
        (cells / shards) * constant("DEVICE_PRUNE_S_PER_CELL")
        + constant("SHARD_DISPATCH_S")
        + shards * constant("SHARD_GATHER_S_PER_SHARD")
        + p.latency_s
    )


def dist_execute_s(item_s: Sequence[float], workers: int) -> float:
    """Makespan estimate for fanning per-item costs out over ``workers``
    via the LPT executor (`parallel/executor`): the max per-worker load of
    the deterministic LPT assignment plus the per-item scheduling tax.
    ``workers<=1`` degrades to the inline sum — so the comparison
    ``dist_execute_s(costs, n) < dist_execute_s(costs, 1)`` is exactly the
    router's fan-out-or-not question, audited as ``dist.execute``."""
    costs = [max(float(c), 0.0) for c in item_s]
    overhead = len(costs) * constant("DIST_ITEM_S")
    if workers <= 1 or len(costs) <= 1:
        return sum(costs)
    from delta_tpu.parallel.distributed import lpt_assign

    scaled = [int(c * 1e9) for c in costs]
    buckets = lpt_assign(scaled, workers)
    return max((sum(costs[j] for j in b) for b in buckets), default=0.0) \
        + overhead


@dataclass(frozen=True)
class LinkProfile:
    up_mbps: float
    down_mbps: float
    latency_s: float
    probed: bool  # False when conf-overridden

    def upload_s(self, nbytes: int) -> float:
        return self.latency_s + nbytes / (self.up_mbps * 1e6)

    def download_s(self, nbytes: int) -> float:
        return self.latency_s + nbytes / (self.down_mbps * 1e6)


@dataclass(frozen=True)
class Estimate:
    device_s: float
    up_s: float
    down_s: float
    kernel_s: float


_lock = threading.Lock()
_profile: Optional[LinkProfile] = None


def reset() -> None:
    """Drop the cached profile (tests)."""
    global _profile
    with _lock:
        _profile = None


def profile() -> LinkProfile:
    """The process-wide link profile (conf override, else one-shot probe)."""
    global _profile
    with _lock:
        if _profile is not None:
            return _profile
        from delta_tpu.utils.config import conf

        up = conf.get("delta.tpu.link.uploadMBps", None)
        down = conf.get("delta.tpu.link.downloadMBps", None)
        if up is not None and down is not None:
            _profile = LinkProfile(float(up), float(down), 0.005, probed=False)
            return _profile
        _profile = _probe()
        return _profile


def _probe() -> LinkProfile:
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np

    # force one XLA execution first: the fresh-process link is 40-90x
    # faster than the steady state and would mis-route every kernel
    np.asarray(jax.jit(lambda a: a + 1)(jnp.arange(8)))

    # latency: tiny round trip
    t0 = time.perf_counter()
    np.asarray(jax.device_put(np.zeros(8, np.int32)))
    latency = time.perf_counter() - t0

    buf = np.random.randint(0, 1 << 30, _PROBE_BYTES // 4).astype(np.int32)
    up_best = down_best = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        dev = jax.device_put(buf)
        jax.block_until_ready(dev)
        up_best = min(up_best, time.perf_counter() - t0)
        t0 = time.perf_counter()
        np.asarray(dev)
        down_best = min(down_best, time.perf_counter() - t0)
        del dev
    # Subtracting a noisy latency sample from a fast transfer can go ~zero
    # and report effectively infinite bandwidth (seen under host contention:
    # 10 GB/s on a ~10 MB/s link), which mis-routes every kernel. Floor
    # the denominator at a quarter of the measured wall time so the derived
    # bandwidth can never exceed 4x what was actually observed.
    up_mbps = (_PROBE_BYTES / 1e6) / max(up_best - latency, up_best / 4, 1e-4)
    down_mbps = (_PROBE_BYTES / 1e6) / max(down_best - latency, down_best / 4, 1e-4)
    return LinkProfile(up_mbps, down_mbps, max(latency, 1e-4), probed=True)


def estimate_device_s(
    up_bytes: int, down_bytes: int, kernel_rows: int, shards: int = 1
) -> Estimate:
    """Wall-clock estimate for shipping operands + one sort-merge-class
    kernel + shipping results. ``kernel_rows`` is the per-shard row count
    when the caller already divided by the mesh; otherwise pass ``shards``
    and the kernel term scales 1/shards (the sort is shard-local)."""
    p = profile()
    up_s = p.upload_s(up_bytes)
    down_s = p.download_s(down_bytes)
    dispatch_s = 3 * p.latency_s  # put + exec + fetch round trips
    kernel_s = (kernel_rows / max(shards, 1)) * constant("KERNEL_S_PER_ROW") \
        + dispatch_s
    return Estimate(up_s + down_s + kernel_s, up_s, down_s, kernel_s)
