"""Device log replay: last-writer-wins reconciliation as a sharded sort.

The reference replays the action log with a per-partition hash map
(`actions/InMemoryLogReplay.scala:43-65`, driven by a 50-way Spark
repartition, `Snapshot.scala:88-111`). A hash map is the wrong shape for a
TPU; the same semantics vectorize as:

    sort rows by (path_id, seq)  →  the last row of each path run wins
    alive = winner AND is_add

which is one `lax.sort` (bitonic on TPU) plus elementwise ops — fully fused by
XLA. Sharding: rows are bucketed by ``path_id % n_shards`` (each path's whole
history lands on one shard, so per-shard replay is exact) and the per-shard
kernels run under `shard_map`; aggregate counts come back via `psum` over ICI.
This is the "sharded log-replay" component called out in SURVEY §2.8.

Tombstone expiry (`minFileRetentionTimestamp`) applies to *removes retained as
tombstones*, not to which add survives — handled by a mask on remove rows.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64, shard_map
from delta_tpu.ops.state_export import ReplayArrays
from delta_tpu.parallel.mesh import P, STATE_AXIS, shard_count

__all__ = [
    "ReplayResult",
    "replay_alive_mask",
    "replay_sharded",
    "ReplayStats",
    "replay_columns",
]


class ReplayStats(NamedTuple):
    num_files: jnp.ndarray  # int32 scalar
    total_size: jnp.ndarray  # int64/float scalar
    num_tombstones: jnp.ndarray  # int32 scalar


class ReplayResult(NamedTuple):
    alive: jnp.ndarray  # bool per input row: surviving AddFile
    tombstone: jnp.ndarray  # bool per input row: retained RemoveFile
    stats: ReplayStats


@functools.partial(jax.jit, static_argnames=())
def _replay_kernel(path_id, seq, is_add, size, deletion_ts, min_retention_ts):
    """Single-shard replay. Padding rows use path_id == -1 (never win)."""
    valid = path_id >= 0
    # Sort by (path, seq): bitonic sort on TPU, one pass.
    idx = jnp.arange(path_id.shape[0], dtype=jnp.int32)
    s_path, s_seq, s_idx = jax.lax.sort((path_id, seq, idx), num_keys=2)
    # Winner = last row of each equal-path run.
    next_differs = jnp.concatenate(
        [s_path[1:] != s_path[:-1], jnp.ones((1,), bool)]
    )
    s_valid = s_path >= 0
    winner_sorted = next_differs & s_valid
    # Scatter back to input order.
    winner = jnp.zeros_like(is_add).at[s_idx].set(winner_sorted)
    alive = winner & is_add & valid
    tombstone = winner & ~is_add & valid & (deletion_ts > min_retention_ts)
    stats = ReplayStats(
        num_files=jnp.sum(alive, dtype=jnp.int32),
        total_size=jnp.sum(jnp.where(alive, size, 0)),
        num_tombstones=jnp.sum(tombstone, dtype=jnp.int32),
    )
    return alive, tombstone, stats


def _next_pow2(n: int) -> int:
    p = 8
    while p < n:
        p *= 2
    return p


def _pad(col: np.ndarray, cap: int, fill) -> np.ndarray:
    out = np.full(cap, fill, dtype=col.dtype)
    out[: len(col)] = col
    return out


def replay_alive_mask(arrays: ReplayArrays, min_retention_ts: int = 0) -> ReplayResult:
    """Single-device replay of an action stream (tests + small tables).

    Inputs are padded to the next power of two so XLA compiles one kernel per
    size bucket, not per log length."""
    ensure_compilation_cache()
    n = arrays.num_rows
    cap = _next_pow2(n)
    # x64 scoped to the kernel: seq keys, sizes and retention timestamps are
    # genuine 64-bit lanes, but the process-global dtype default stays intact.
    with enable_x64():
        alive, tombstone, stats = _replay_kernel(
            jnp.asarray(_pad(arrays.path_id, cap, np.int32(-1))),
            jnp.asarray(_pad(arrays.seq, cap, np.int64(0))),
            jnp.asarray(_pad(arrays.is_add, cap, False)),
            jnp.asarray(_pad(arrays.size, cap, np.int64(0))),
            jnp.asarray(_pad(arrays.deletion_timestamp, cap, np.int64(0))),
            jnp.asarray(min_retention_ts, jnp.int64),
        )
    return ReplayResult(alive[:n], tombstone[:n], stats)


@jax.jit
def _winner_bits_kernel(path_id):
    """Last-row-of-each-path-run mask from the path column alone.

    Row order is the replay order (``log/columnar.SegmentColumns`` layout
    invariant), so the implicit iota is the sort tiebreaker — no seq column
    ever ships to the device. Input: one int32 lane (padding = -1); output:
    the winner mask packed to bits (n/8 bytes). Sized for the realistic
    deployment constraint that host↔device link latency/bandwidth — not the
    O(n log n) bitonic sort — dominates this kernel."""
    n = path_id.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    s_path, s_idx = jax.lax.sort((path_id, idx), num_keys=2)
    next_differs = jnp.concatenate([s_path[1:] != s_path[:-1], jnp.ones((1,), bool)])
    winner_sorted = next_differs & (s_path >= 0)
    winner = jnp.zeros((n,), bool).at[s_idx].set(winner_sorted)
    return jnp.packbits(winner)


def replay_columns(cols, min_retention_ts: int = 0, device: bool = True) -> ReplayResult:
    """Replay a :class:`delta_tpu.log.columnar.SegmentColumns` stream.

    The winner computation runs on device (``device=True``: one int32 column
    up, one bitmask down) or as the host scatter fallback; alive/tombstone
    masks and the aggregate stats are elementwise host numpy either way (they
    are O(n) band-limited and would only add transfer latency on device)."""
    winner = None
    if device:
        ensure_compilation_cache()
        n = len(cols.path_id)
        padded = np.full(_next_pow2(n), -1, np.int32)
        padded[:n] = cols.path_id
        bits = np.asarray(_winner_bits_kernel(jnp.asarray(padded)))
        winner = np.unpackbits(bits, count=n).astype(bool)
    alive, tombstone = cols.replay(min_retention_ts, winner=winner)
    stats = ReplayStats(
        num_files=np.int32(alive.sum()),
        total_size=np.int64(cols.size[alive].sum()),
        num_tombstones=np.int32(tombstone.sum()),
    )
    return ReplayResult(alive, tombstone, stats)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer: decorrelates shard choice from path-id locality
    (sequential dictionary codes would otherwise stripe shards unevenly
    whenever n_shards shares factors with the id assignment pattern)."""
    z = x.astype(np.uint64)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return z ^ (z >> np.uint64(31))


def _bucket_by_path(arrays: ReplayArrays, n_shards: int):
    """Host-side bucketing: row → shard ``mix(path_id) % n_shards`` (every
    action for a path lands on one shard), padded to equal per-shard length.
    Fully vectorized — one argsort + one scatter per column, no Python loop
    over shards (a true single-path hot spot still cannot be split: replay
    correctness requires a path's whole history on one shard; the mixer only
    protects against accidental clustering). Returns stacked (n_shards, cap)
    arrays + the flat destination map for unscattering."""
    bucket = (_mix64(arrays.path_id) % np.uint64(n_shards)).astype(np.int64)
    order = np.argsort(bucket, kind="stable")
    counts = np.bincount(bucket, minlength=n_shards)
    cap = _next_pow2(int(counts.max()) if len(counts) else 1)
    # position of each (ordered) row within its shard slab
    starts = np.cumsum(counts) - counts
    within = np.arange(len(order), dtype=np.int64) - np.repeat(starts, counts)
    dest = bucket[order] * cap + within  # flat index into (n_shards*cap)

    def stack(col, fill):
        out = np.full(n_shards * cap, fill, dtype=col.dtype)
        out[dest] = col[order]
        return out.reshape(n_shards, cap)

    cols = (
        stack(arrays.path_id, np.int32(-1)),
        stack(arrays.seq, np.int64(0)),
        stack(arrays.is_add, False),
        stack(arrays.size, np.int64(0)),
        stack(arrays.deletion_timestamp, np.int64(0)),
    )
    return cols, order, dest


def replay_sharded(
    arrays: ReplayArrays, mesh: Mesh, min_retention_ts: int = 0
) -> ReplayResult:
    """Replay sharded over a device mesh.

    Equivalent of `Snapshot.scala:88-111`'s repartition+replay: each shard
    owns a hash range of paths, replays independently, and the aggregate
    state counts are reduced with `psum` over ICI.
    """
    ensure_compilation_cache()
    n = shard_count(mesh)
    (path_id, seq, is_add, size, del_ts), order, dest = _bucket_by_path(arrays, n)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(STATE_AXIS), P(STATE_AXIS), P(STATE_AXIS), P(STATE_AXIS), P(STATE_AXIS)),
        out_specs=(P(STATE_AXIS), P(STATE_AXIS), P(), P(), P()),
    )
    def shard_replay(pid, sq, add, sz, dts):
        alive, tombstone, stats = _replay_kernel(
            pid[0], sq[0], add[0], sz[0], dts[0],
            jnp.asarray(min_retention_ts, dtype=sq.dtype),
        )
        num = jax.lax.psum(stats.num_files, STATE_AXIS)
        tot = jax.lax.psum(stats.total_size, STATE_AXIS)
        ntomb = jax.lax.psum(stats.num_tombstones, STATE_AXIS)
        return alive[None], tombstone[None], num, tot, ntomb

    with enable_x64():
        alive_sh, tomb_sh, num, tot, ntomb = jax.jit(shard_replay)(
            path_id, seq, is_add, size, del_ts
        )

    # Unscatter: stacked (n, cap) → original row order, one gather each.
    alive = np.zeros(arrays.num_rows, bool)
    tombstone = np.zeros(arrays.num_rows, bool)
    alive[order] = np.asarray(alive_sh).reshape(-1)[dest]
    tombstone[order] = np.asarray(tomb_sh).reshape(-1)[dest]
    return ReplayResult(
        jnp.asarray(alive),
        jnp.asarray(tombstone),
        ReplayStats(num, tot, ntomb),
    )
