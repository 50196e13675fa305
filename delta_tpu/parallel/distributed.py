"""Multi-host coordination: DCN-level fan-out around the ICI mesh.

SURVEY §2.8's distribution model, made explicit. The reference's data plane
fans out over Spark executors with driver⇄executor RPC; here the equivalent
split is:

* **intra-slice (ICI)** — `jax.lax` collectives under `shard_map` over the
  device mesh (`parallel/mesh.py`): the replay, join, and skipping kernels.
* **inter-host (DCN)** — `jax.distributed` + the deterministic per-host
  work partitioner below: every host computes the same assignment with no
  RPC — strided by default, size-weighted LPT when byte weights are known
  (see :func:`lpt_assign`). Consumers: VACUUM's delete fan-out (`commands/vacuum.py`),
  multi-host scan decode (`exec/scan.read_files_as_table(distribute=True)`),
  checkpoint part writing (`log/checkpoints.write_checkpoint` — proc 0
  publishes `_last_checkpoint` after all hosts' parts are visible), and
  CONVERT's footer/stats collection (`commands/convert.py` — fragments
  exchanged through the shared store, proc 0 commits). A real 2-process
  `jax.distributed` cluster exercises all of these in
  `tests/test_multihost.py`.
* **control plane** — unchanged from single-host: commits still serialize
  through the LogStore's atomic create, which is host-agnostic. There is
  deliberately no lock service (the reference's stance,
  `storage/LogStore.scala:30-43`).

On a single host every function degrades to a no-op/identity, so the same
program runs unchanged from a laptop to a multi-host slice.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

__all__ = [
    "initialize",
    "process_info",
    "host_partition",
    "host_shard_indices",
    "lpt_assign",
    "lpt_loads",
    "bytes_skew",
]


def initialize(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> Tuple[int, int]:
    """Join the multi-host runtime; returns (process_id, num_processes).

    With explicit arguments they are passed through. With none,
    `jax.distributed.initialize()` is attempted bare so its cluster
    AUTO-DETECTION (Cloud TPU metadata, SLURM, GKE) still applies; when no
    cluster environment is detected this degrades to single-host (0, 1)
    instead of raising — safe to call unconditionally at engine startup.
    """
    import jax

    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
        )
    except (RuntimeError, ValueError):
        if coordinator_address is not None or num_processes not in (None, 1):
            raise  # explicitly-requested cluster must not silently degrade
        return 0, 1
    return jax.process_index(), jax.process_count()


def process_info() -> Tuple[int, int]:
    """(process_index, process_count) of the current runtime — (0, 1) when
    no multi-host runtime was initialized."""
    import jax

    try:
        return jax.process_index(), jax.process_count()
    except RuntimeError:  # backend not initialized yet
        return 0, 1


def lpt_assign(sizes: Sequence[int], count: int) -> List[List[int]]:
    """Deterministic size-weighted LPT (longest-processing-time) assignment
    of ``len(sizes)`` items over ``count`` hosts; returns per-host item-index
    lists (each sorted ascending).

    The strided partition balances item *counts*; on a zipf-skewed file
    list one host inherits the hot shard's bytes and the whole job waits on
    it. LPT sorts by size descending (ties broken by index, so every host
    computes the identical assignment with no RPC) and gives each item to
    the currently least-loaded host (ties broken by host id) — the classic
    4/3-approximation to makespan, which is what a stride can't bound.
    """
    if count <= 1:
        return [list(range(len(sizes)))]
    loads = [0] * count
    buckets: List[List[int]] = [[] for _ in range(count)]
    order = sorted(range(len(sizes)), key=lambda j: (-int(sizes[j] or 0), j))
    for j in order:
        h = min(range(count), key=lambda i: (loads[i], i))
        loads[h] += int(sizes[j] or 0)
        buckets[h].append(j)
    for b in buckets:
        b.sort()
    return buckets


def lpt_loads(sizes: Sequence[int],
              assignment: Sequence[Sequence[int]]) -> List[int]:
    """Per-bin byte loads of an assignment — the LPT-predicted cost shares.
    The executor stamps these on its ``delta.dist.job`` span and the trace
    analyzer (`obs/trace_store.analyze_trace`) diffs each worker's measured
    busy time against its share, so a straggler shard is attributable to
    either byte skew (predicted) or per-byte slowness (not predicted)."""
    return [sum(int(sizes[j] or 0) for j in b) for b in assignment]


def bytes_skew(sizes: Sequence[int], assignment: Sequence[Sequence[int]]) -> float:
    """max/mean per-host bytes ratio of an assignment — 1.0 is perfectly
    balanced; the zipf-100k regression gate in tests watches this."""
    per_host = lpt_loads(sizes, assignment)
    if not per_host or sum(per_host) == 0:
        return 1.0
    mean = sum(per_host) / len(per_host)
    return max(per_host) / mean if mean else 1.0


def host_shard_indices(n_items: int, index: Optional[int] = None,
                       count: Optional[int] = None,
                       sizes: Optional[Sequence[int]] = None) -> List[int]:
    """This host's item positions in a global work list.

    Without ``sizes``: deterministic strided partition — host i takes items
    i, i+n, i+2n, … Every host computes the same assignment with no RPC,
    the DCN-free analogue of the reference's driver→executor task
    scheduling. With ``sizes`` (per-item byte weights): size-weighted LPT
    via :func:`lpt_assign`, still deterministic and RPC-free, so a
    zipf-skewed file list can't hand one host the hot shard's bytes.

    ``index``/``count`` must be given together (or neither, to use the
    runtime's process info).
    """
    if (index is None) != (count is None):
        raise ValueError("host partitioning needs both index and count (or neither)")
    if index is None:
        index, count = process_info()
    if count <= 1:
        return list(range(n_items))
    if sizes is not None:
        if len(sizes) != n_items:
            raise ValueError(
                f"sizes has {len(sizes)} entries for {n_items} items")
        return lpt_assign(sizes, count)[index]
    return list(range(index, n_items, count))


def host_partition(items: Sequence, index: Optional[int] = None,
                   count: Optional[int] = None,
                   sizes: Optional[Sequence[int]] = None) -> List:
    """This host's slice of a global work list (see
    :func:`host_shard_indices` for the assignment rule)."""
    return [items[j] for j in host_shard_indices(len(items), index, count,
                                                 sizes=sizes)]
