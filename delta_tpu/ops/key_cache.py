"""HBM-resident MERGE join keys — the data-plane sibling of
`ops/state_cache`.

The reference re-evaluates the join's target side from a fresh scan every
MERGE (`commands/MergeIntoCommand.scala:310-389`); on a TPU the dominant
cost of the device membership probe is *shipping the target keys* — 80 MB
for a 10M-row int64 lane dwarfs the 0.1 s device sort at any realistic
link. A CDC upsert loop merges into the same table every few minutes, so
the target key lane is the textbook resident operand: build it once
(streamed in tiles), keep it in HBM, and advance it incrementally as the
log tails forward — new files' keys append (a projected Parquet read of
just the new files), removed files' rows die, and deletion-vector growth
flips per-row validity. Steady-state merges then upload only the source
keys (a few MB) and download bit masks.

Layout: one int64 key lane per (table, join-key signature) in PHYSICAL row
order per file (deletion-vector-deleted rows stay in place but are marked
invalid — they must not match, or a source row whose only "match" is a
dead row would silently skip its NOT MATCHED insert). The probe returns
physical-space bits; `commands/merge.py` maps them onto its DV-filtered
decode via each file's position column.

Composite integer keys pack into one lane (hi<<32 | lo) exactly like the
upload path; the packing is part of the signature and is only built when
the target components fit int32 (the per-merge source side is checked at
probe time).

The probe is FUSED with the join's pairing step (PR 6): the kernel also
emits each matched slab row's first-match source index, compacted on
device into an O(matched) pair download — the host no longer re-derives
the pairing from decoded target keys. Cold builds stream per-file decoded
lanes straight onto a pre-sized HBM allocation (:class:`SlabBuilder`), so
the upload overlaps the remaining Parquet decode, and file rewrites
(OPTIMIZE / UPDATE-rewrite / RESTORE) bump a per-table epoch
(:meth:`KeyCache.bump_epoch`) that drops resident entries outright — a
stale slab can never serve a post-rewrite MERGE.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from delta_tpu.parallel import link
from delta_tpu.utils import telemetry
from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64
from delta_tpu.utils.config import conf

__all__ = ["ResidentJoinKeys", "KeyCache", "PhysicalProbe", "SlabBuilder",
           "key_cache_enabled"]


def key_cache_enabled() -> bool:
    """Whether the cross-MERGE resident key cache may serve/retain entries
    (the fused device path itself is governed by
    ``delta.tpu.merge.devicePath.*``)."""
    return conf.get_bool("delta.tpu.merge.keyCache.enabled", True)

from delta_tpu.ops.state_cache import _next_pow2  # shared pad-size bucketing

# sentinel version for an entry whose tail application failed part-way:
# greater than any real snapshot version, so every staleness guard
# (`entry.version > snapshot.version`) discards the entry immediately
_POISON_VERSION = 1 << 62


class DeltaProbeOverflow(RuntimeError):
    """Internal control-flow signal: the matched source keys' candidate slab
    rows (dead versions and duplicate target keys included) pass what the
    pair kernel's scratch may hold; the caller takes the host-join
    fallback."""


@dataclass
class PhysicalProbe:
    """Probe output in physical slab space: per-source matched flags and —
    the fused-join addition — the matched PAIRS themselves (physical slab
    row → first matching source row), computed on device and downloaded
    O(matched). ``slabs`` maps file path → (offset, rows). ``t_pairs`` is
    None for an insert-only probe (only the source flags were fetched).
    ``t_bits`` (the full per-slab-row matched mask) materializes LAZILY
    from the pairs — the production merge path consumes only
    :meth:`pairs_for_file` and never pays the O(slab-rows) scatter."""

    s_matched: np.ndarray  # bool per source row
    any_multi: bool
    slabs: Dict[str, Tuple[int, int]]
    num_rows: int = 0  # live slab rows (t_bits length)
    # (physical slab rows ascending, first-match source row per pair)
    t_pairs: Optional[Tuple[np.ndarray, np.ndarray]] = None
    _bits: Optional[np.ndarray] = None

    @property
    def t_bits(self) -> Optional[np.ndarray]:
        """Bool per physical slab row; None for an insert-only probe."""
        if self._bits is None and self.t_pairs is not None:
            t = np.zeros(self.num_rows, bool)
            phys, _ = self.t_pairs
            t[phys[phys < self.num_rows]] = True
            self._bits = t
        return self._bits

    def bits_for_file(self, path: str, positions: Optional[np.ndarray],
                      num_rows: int) -> Optional[np.ndarray]:
        """Matched flags for a file's *decoded* rows. ``positions`` are the
        decoded rows' physical positions (None = decode was not DV-filtered,
        rows are physical 0..num_rows). None when the file isn't in the slab
        or shapes disagree (caller falls back)."""
        ent = self.slabs.get(path)
        if ent is None or self.t_bits is None:
            return None
        off, rows = ent
        if positions is None:
            if num_rows != rows:
                return None
            return self.t_bits[off:off + rows]
        if len(positions) and positions.max() >= rows:
            return None
        return self.t_bits[off + positions]

    def pairs_for_file(self, path: str, positions: Optional[np.ndarray],
                       num_rows: int) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """The matched pairs landing in one file, mapped onto its *decoded*
        rows: (decoded row indices, first-match source rows). ``positions``
        as in :meth:`bits_for_file`. None when the file isn't in the slab or
        the slab disagrees with the decode (a matched physical row absent
        from the DV-filtered decode) — callers fall back to the host join."""
        ent = self.slabs.get(path)
        if ent is None or self.t_pairs is None:
            return None
        off, rows = ent
        phys, srows = self.t_pairs
        lo = int(np.searchsorted(phys, off))
        hi = int(np.searchsorted(phys, off + rows))
        p_local = phys[lo:hi] - off
        s_local = srows[lo:hi]
        if positions is None:
            if num_rows != rows:
                return None
            return p_local, s_local
        if len(positions) and int(positions[-1]) >= rows:
            return None
        idx = np.searchsorted(positions, p_local)
        if (idx >= len(positions)).any():
            return None
        if len(idx) and not (positions[idx] == p_local).all():
            return None  # slab matched a row the decode dropped: fall back
        return idx, s_local


# same memoizing finalize wrapper as the upload path's handle
from delta_tpu.ops.join_kernel import PendingJoin as PendingProbe


def _tail_capacity(cap: int) -> int:
    """Rows of the slab's second sorted run, the tail, from the slab's
    capacity alone (a static shape: every program that reads the tail
    compiles once a capacity, whatever the tail holds). An append of ``a``
    rows costs the device one sort of the tail now and its share ``a / T``
    of the fold that empties it later, and every probe reads the tail
    beside the big run. On the chip (PERF.md PR 37, call 123), at
    60,817,408 rows: the whole sort 280.0 ms, the tail's 4.7 / 5.9 / 8.4 /
    14.1 / 29.9 ms at 0.5M / 1M / 2M / 4M / 8M (3.9 of it whatever the
    tail: the int64 lanes split into 32-bit planes at the capacity's
    length before the window is cut), so a refresh function's 60,000 rows
    cost 37.5 / 21.9 / 16.4 / 18.1 / 31.9 ms an append, and a probe of
    65,536 keys 1.4-1.5 ms more over a tail of 2M or 4M than over none.
    At 37,748,736: the whole sort 191.2, the tail's 7.4 / 13.0 / 28.7 at
    2M / 4M / 8M, and a probe of 1,048,576 keys 24.8 / 41.4 ms more over
    4M / 8M than over none, so an upsert's 1M-row file costs 103 (and the
    tail holds two) / 85.6 / 94.0 ms an append at 2M / 4M / 8M. A
    sixteenth of the capacity, rounded up to a power of two (the probe's
    blocks divide one), is 4,194,304 at both."""
    return _next_pow2(cap // 16, floor=64)


@functools.lru_cache(maxsize=None)
def _sort_kernel():
    """Sort one of the slab's two runs from the row-space lanes: the big
    run (``size`` the capacity: at a build, a re-ship, a fold of a full
    tail, or a validity flip too large to search for, `_flip_by_search`)
    or the tail (``size`` `_tail_capacity`: once per key append that fits
    it), NOT per probe: steady-state probes against an unchanged table
    skip the O(n log n) term entirely. ``window`` is (start, lo, n): the
    run is sorted from the ``size`` lane rows from ``start`` on, of which
    rows in [lo, n) are its own and every other is padding (the big run's
    rows, which the tail's window reaches back over where the capacity
    ends less than a tail past them, and rows not yet appended).

    One sort gives all three arrays a probe reads of a run: the int32 that
    rides with each key is its physical row with the row's validity in the
    low bit, so the permutation and the sorted-space validity are two
    dense reads of the sorted payload, never a gather through the
    permutation. The payload is the second sort key: it orders as the row
    does, so ties among equal keys stay in physical-row order, valid or
    dead, without the row-id operand a stable sort would add beside it:
    (sorted_keys, perm) rises strictly, which is how a flip finds a row's
    sorted position (`_inverse_permutation_at`). Padding rows encode as
    int64.max so they sort to the run's end and read invalid; a real key
    equal to int64.max may share their run of equal keys — harmless,
    validity excludes them."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(3,))
    def kernel(keys, valid, window, size):
        start, lo, n = window[0], window[1], window[2]
        keys = jax.lax.dynamic_slice(keys, (start,), (size,))
        valid = jax.lax.dynamic_slice(valid, (start,), (size,))
        row = start + jnp.arange(size, dtype=jnp.int32)
        inside = (row >= lo) & (row < n)
        enc = jnp.where(inside, keys, jnp.iinfo(jnp.int64).max)
        payload = (row << 1) | (valid & inside).astype(jnp.int32)
        sk, payload = jax.lax.sort((enc, payload), num_keys=2,
                                   is_stable=False)
        return sk, payload >> 1, (payload & 1) == 1

    return kernel


# entries of one node of the tree a flip's search descends: 128 is the width
# at which the slab's 1-D arrays reshape into nodes without a copy (at 256
# or 512 XLA re-tiles a capacity-sized plane: 229 MiB and 2.2 ms more at
# 60.8M rows; PERF.md PR 35, call 97)
_SEARCH_FAN = 128


def _search_steps(cap: int) -> int:
    """Nodes a row's search gathers, one a level of the tree over ``cap``
    sorted rows below its dense top."""
    steps = 0
    while cap > _SEARCH_FAN:
        cap = -(-cap // _SEARCH_FAN)
        steps += 1
    return steps


@functools.lru_cache(maxsize=None)
def _inverse_permutation_at():
    """Physical rows -> sorted positions, by search: what a validity flip
    on a live sorted view needs of the inverse permutation, and nothing of
    the capacity's length is built. `_sort_kernel` orders by (encoded key,
    row), so (sorted_keys[p], perm[p]) rises strictly with p and row r
    stands where (keys[r], r) does. The search descends a tree whose level
    i+1 is every `_SEARCH_FAN`-th entry of level i (strided reads of the
    resident arrays, nothing kept): a dense compare against the top, then
    one node gathered a level and a count of its entries at or before the
    row's. A gather costs the chip as much for one element as for a node,
    so the tree's `_search_steps` gathers a row stand where a binary
    search would pay 26 (14 ms against 125 for 65,536 rows of 60.8M,
    PERF.md PR 35). ``rows`` are rows the view was sorted with (below its
    ``n``: their encoded key is their key); a padding row (>= the
    capacity) maps to the capacity, so the scatter after it drops it."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def inverse_permutation_at(sorted_keys, perm, keys, rows):
        cap = sorted_keys.shape[0]
        fan = _SEARCH_FAN
        r = jnp.minimum(rows, cap - 1)
        k = keys[r]
        levels = []
        lk, lp = sorted_keys, perm
        while lk.shape[0] > fan:
            # a capacity is a multiple of 1,024: only an upper level pads
            pad = -lk.shape[0] % fan
            lk = jnp.pad(lk, (0, pad), constant_values=jnp.iinfo(
                lk.dtype).max).reshape(-1, fan)
            lp = jnp.pad(lp, (0, pad), constant_values=jnp.iinfo(
                lp.dtype).max).reshape(-1, fan)
            levels.append((lk, lp))
            lk, lp = lk[:, 0], lp[:, 0]

        def last_at_or_before(ek, ep, kc, rc):
            """In each row of entries, the last one at or before the
            target (there is one: a node's first entry is)."""
            before = (ek < kc[:, None]) | (
                (ek == kc[:, None]) & (ep <= rc[:, None]))
            return jnp.sum(before, axis=1, dtype=jnp.int32) - 1

        def descend(args):
            kc, rc = args
            j = last_at_or_before(lk[None, :], lp[None, :], kc, rc)
            for ek, ep in reversed(levels):
                j = j * fan + last_at_or_before(ek[j], ep[j], kc, rc)
            return j

        # a chunk of rows at a time: 48 MiB of gathered nodes a level
        chunk = min(r.shape[0], (1 << 22) // fan)
        pos = jax.lax.map(descend, (k.reshape(-1, chunk),
                                    r.reshape(-1, chunk))).reshape(-1)
        return jnp.where(rows < cap, pos, cap)

    return inverse_permutation_at


def _flip_by_search(flips: int, cap: int) -> bool:
    """Whether mirroring ``flips`` validity flips in a live sorted view of
    ``cap`` rows by search is cheaper than dropping the view, which costs
    the next probe one re-sort. A searched row costs what sorting eight
    rows does for each node it gathers. On the chip (PERF.md PR 35, calls
    93 and 97), at 60,817,408 rows and three nodes a row: the search 14.0
    ms for 65,536 rows and 60.8 for 524,288 (one run of neighbouring rows,
    as a refresh deletes them; 11.7 and 44.2 for scattered rows), 102 ns a
    row; the re-sort 280.3 ms there and 191.5 at 37,748,736, 4.6-5.1 ns a
    row: 22 sorted rows a searched one. They meet at 2.5M flips there."""
    return 8 * flips * _search_steps(cap) <= cap


def _slab_capacity(rows: int) -> int:
    """Device rows for a slab of ``rows``: `join_kernel._bucket` (pow2 to
    4M, then 2M steps), at least 1024."""
    from delta_tpu.ops.join_kernel import _bucket

    cap = max(_bucket(rows), 1024)
    # a physical row shares its int32 with the validity bit in the sort
    # (`_sort_kernel`); 4 GiB of slab is 195M rows, so no budget reaches this
    assert cap <= 1 << 30, f"key slab of {cap} rows: a row id has 30 bits"
    return cap


# what one probe may hold in HBM besides its operands: a chunk of gathered
# slab blocks, then the pair kernel's buffers (16 B a candidate row)
_PROBE_SCRATCH_BYTES = 512 << 20


def _probe_block(cap: int, m: int) -> int:
    """Rows a block of a sorted run of ``cap`` rows (a power of two that
    divides every capacity and every tail, and is no longer than the run): the
    probe gathers one block a source key and sorts one boundary a block with
    the source, so B follows the square root of the slab rows a source key.
    On a v5e, 37.7M-row capacity and 1M keys, B = 64, 128, 256, 512: 77, 58,
    59, 67 ms a probe (PR 28, chip call 29); a gathered row costs ~11 ns at
    128 or at 256 keys, the parent's block-window probe 583 + 358 ms."""
    b = 64
    while b < min(1024, cap) and b * b * m < 256 * cap:
        b *= 2
    return b


@functools.lru_cache(maxsize=None)
def _probe_sorted_kernel():
    """Source-centric membership probe of the slab's two PRE-SORTED runs
    (the big run, then the tail; `_sort_kernel`), the first of the join's
    two programs: its work follows the source (m keys), and only one dense
    read follows each run. Over a run:

      - the run is tiled into blocks of B rows (`_probe_block`); one dense
        pass takes each block's first key (its boundary) and how long, and
        how valid, the run of that key at the block's head is;
      - boundaries and source are sorted together, so a running count of
        boundaries gives every source key the block its run of equal slab
        keys starts in, and a segmented sum hands it the heads of the later
        blocks that its run crosses into;
      - each source key gathers its block (a chunk of keys at a time, under
        `_PROBE_SCRATCH_BYTES`): one broadcast compare and three counts
        give where its run starts, how long it is and how many rows of it
        are valid.

    A slab row is in one sorted run or the other, so a source key matched
    where either run matched it, the pairs are both runs' pairs and the
    counts add; equal keys may lie in both (a dead version in the big run,
    the live one in the tail). Nothing of the capacity's length is
    written. One head array carries [multi | valid pairs (4 bytes LE) |
    candidate rows (4 bytes LE) | s_bits], a single small fetch; for
    `_pair_kernel` stay on the device, the big run's first and then the
    tail's, each distinct matched source key's run of equal slab keys
    (start, length; a tail's start counts on from the big run's end) and
    its MINIMAL original source index: `_first_match_recovery`'s
    stable-tie semantics, so the pairs are row-identical to the host's."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    def one_run(sorted_keys, sorted_valid, s_keys):
        cap = sorted_keys.shape[0]
        m = s_keys.shape[0]
        blk = _probe_block(cap, m)  # static under jit
        nb = cap // blk
        keys_b = sorted_keys.reshape(nb, blk)
        valid_b = sorted_valid.reshape(nb, blk)
        bnd = keys_b[:, 0]
        # the run of the boundary key at each block's head (block 0's is
        # counted by the key's own gather below): length and valid rows
        head_eq = (keys_b == bnd[:, None]) & (jnp.arange(nb) > 0)[:, None]
        head_len = jnp.sum(head_eq, axis=1, dtype=jnp.int32)
        head_valid = jnp.sum(head_eq & valid_b, axis=1, dtype=jnp.int32)
        total = m + nb
        # one int32 rides the sort: a source row's original index, or below
        # zero a boundary's two counts (11 bits each), so that among equal
        # keys boundaries stand first and source rows in original order
        k, o = jax.lax.sort((
            jnp.concatenate([bnd, s_keys.astype(sorted_keys.dtype)]),
            jnp.concatenate([~((head_len << 11) | head_valid),
                             jnp.arange(m, dtype=jnp.int32)]),
        ), num_keys=2, is_stable=False)
        is_b = o < 0
        head_len, head_valid = (~o) >> 11, (~o) & 0x7FF
        seg_start = jnp.concatenate([jnp.ones(1, bool), k[1:] != k[:-1]])

        def seg_sum(x):
            """Sum of x over the boundaries of each row's segment of equal
            keys (they all stand before its source rows)."""
            c = jnp.cumsum(x)
            return c - jax.lax.cummax(jnp.where(seg_start, c - x, 0))

        b_before = jnp.cumsum(is_b.astype(jnp.int32)) - is_b
        # boundaries strictly below the key, less one: the block whose first
        # key is below it and whose successor's is not
        j0 = jnp.maximum(
            jax.lax.cummax(jnp.where(seg_start, b_before, 0)) - 1, 0)
        # gather each row's block, a chunk of rows at a time
        chunk = min(_next_pow2(total, floor=8), (1 << 24) // blk)
        pad = -total % chunk
        kp = jnp.pad(k, (0, pad)).reshape(-1, chunk)
        jp = jnp.pad(j0, (0, pad)).reshape(-1, chunk)

        def block_counts(args):
            kc, jc = args
            rows = keys_b[jc]
            eq = rows == kc[:, None]
            return (jnp.sum(rows < kc[:, None], axis=1, dtype=jnp.int32),
                    jnp.sum(eq, axis=1, dtype=jnp.int32),
                    jnp.sum(eq & valid_b[jc], axis=1, dtype=jnp.int32))

        below, equal, equal_valid = (
            x.reshape(-1)[:total] for x in jax.lax.map(block_counts, (kp, jp)))
        run_len = equal + seg_sum(jnp.where(is_b, head_len, 0))
        run_valid = equal_valid + seg_sum(jnp.where(is_b, head_valid, 0))
        prev_b = jnp.concatenate([jnp.ones(1, bool), is_b[:-1]])
        first = ~is_b & (seg_start | prev_b)  # of its run of equal source keys
        matched = ~is_b & (run_valid > 0)
        next_same = jnp.concatenate([~seg_start[1:], jnp.zeros(1, bool)])
        multi = jnp.any(matched & (~first | next_same))
        s_match = jnp.zeros(m, bool).at[jnp.where(is_b, m, o)].set(
            matched, mode="drop")
        run_len = jnp.where(first & (run_valid > 0), run_len, 0)
        counts = jnp.stack([jnp.sum(jnp.where(first, run_valid, 0)),
                            jnp.sum(run_len)])
        return multi, counts, s_match, j0 * blk + below, run_len, o

    @jax.jit
    def kernel(sorted_keys, sorted_valid, tail_keys, tail_valid, s_keys):
        multi, counts, s_match, *big = one_run(
            sorted_keys, sorted_valid, s_keys)
        t_multi, t_counts, t_match, t_lo, *tail = one_run(
            tail_keys, tail_valid, s_keys)
        counts = counts + t_counts
        head = jnp.concatenate([
            (multi | t_multi).astype(jnp.uint8).reshape(1),
            ((counts[:, None] >> jnp.arange(0, 32, 8)) & 0xFF).astype(
                jnp.uint8).reshape(-1),
            jnp.packbits((s_match | t_match).astype(jnp.uint8)),
        ])
        tail = (t_lo + sorted_keys.shape[0], *tail)
        return (head, *(jnp.concatenate(x) for x in zip(big, tail)))

    return kernel


def _decode_head(head: np.ndarray, cap_s: int, m: int):
    """Decode the probe head fetched from device: (multi, valid pairs,
    candidate rows, s_matched[:m]). Layout documented on
    `_probe_sorted_kernel`."""
    mc, tc = (int.from_bytes(head[i:i + 4].tobytes(), "little") for i in (1, 5))
    s = np.unpackbits(head[9:9 + cap_s // 8], count=cap_s)[:m].astype(bool)
    return bool(head[0]), mc, tc, s


@functools.lru_cache(maxsize=None)
def _pair_kernel():
    """The join's second program: the pairs, from the source side. Every
    matched run (start, length, source row) of `_probe_sorted_kernel`, the
    big run's and then the tail's, is laid out into ``cand_cap`` candidate
    slots (a scatter of the run starts and two running scans), each slot
    reads its slab row's validity and physical row through the two runs'
    permutations laid end to end (one dense copy, 0.8 ms at 65M rows,
    PERF.md PR 37, where a second pair of gathers would cost 16 ns a slot),
    and a sort by physical row brings the valid pairs to the front in the
    host's order: a dense (2, out_cap) int32 buffer of (physical row
    ascending, first-match source row). A tail's rows lie after every row
    of the big run, and one call lays both out, so ``cand_cap`` and
    ``out_cap`` are static buckets from the head's two summed counts, as
    for one run. Dead versions of a key are candidates and not pairs;
    slots past the count hold the int32 maximum (sliced off host-side)."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    @functools.partial(jax.jit, static_argnums=(7, 8))
    def kernel(run_lo, run_len, run_src, sorted_valid, perm, tail_valid,
               tail_perm, cand_cap, out_cap):
        sorted_valid = jnp.concatenate([sorted_valid, tail_valid])
        perm = jnp.concatenate([perm, tail_perm])
        total = run_len.shape[0]
        live = run_len > 0
        end = run_lo + run_len
        # runs of distinct keys lie apart and in order in a sorted run, and
        # the tail's after the big run's
        prev_end = jnp.concatenate([
            jnp.zeros(1, jnp.int32),
            jax.lax.cummax(jnp.where(live, end, 0))[:-1]])
        at = jnp.where(live, jnp.cumsum(run_len) - run_len, cand_cap)
        # a slot's slab position: one more than its left neighbour's, but at
        # a run's first slot, where it jumps to the run's start
        pos = jnp.cumsum(jnp.ones(cand_cap, jnp.int32).at[at].set(
            run_lo - prev_end + 1, mode="drop")) - 1
        run = jax.lax.cummax(jnp.zeros(cand_cap, jnp.int32).at[at].set(
            jnp.arange(total, dtype=jnp.int32), mode="drop"))
        inside = jnp.arange(cand_cap, dtype=jnp.int32) < jnp.sum(run_len)
        pos = jnp.where(inside, pos, 0)
        phys = jnp.where(inside & sorted_valid[pos], perm[pos],
                         jnp.iinfo(jnp.int32).max)
        phys, src = jax.lax.sort((phys, run_src[run]), num_keys=1,
                                 is_stable=False)
        return jnp.stack([phys[:out_cap], src[:out_cap]])

    return kernel


@functools.lru_cache(maxsize=None)
def _update_kernels():
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    return {
        "kill": jax.jit(lambda v, r: v.at[r].set(False, mode="drop")),
        "revive": jax.jit(lambda v, r: v.at[r].set(True, mode="drop")),
        "append": jax.jit(
            lambda k, v, r, nk, nv: (
                k.at[r].set(nk.astype(k.dtype), mode="drop"),
                v.at[r].set(nv, mode="drop"),
            )
        ),
        # int32-shipped slabs widen to the kernel's int64 on device
        "widen": jax.jit(lambda k: k.astype(jnp.int64)),
        # contiguous appends skip the row-index upload entirely (start is a
        # scalar); uploaded keys may arrive int32-narrowed and cast up here
        "slice_append": jax.jit(
            lambda k, v, start, nk, nv: (
                jax.lax.dynamic_update_slice(k, nk.astype(k.dtype), (start,)),
                jax.lax.dynamic_update_slice(v, nv, (start,)),
            )
        ),
        # a tail that holds no row: all padding, as `_sort_kernel` leaves it
        "empty_tail": jax.jit(
            lambda t: (jnp.full(t, jnp.iinfo(jnp.int64).max, jnp.int64),
                       jnp.zeros(t, jnp.int32), jnp.zeros(t, bool)),
            static_argnums=0),
    }


def _appended(mirror: np.ndarray, new: np.ndarray, room: int) -> np.ndarray:
    """``mirror`` with ``new`` after it, as the head of a backing array of
    ``room`` rows at least. The mirrors are as long as the slab; appending by
    ``np.concatenate`` copied all of them for every file a commit added
    (600 MB to add 60,000 keys to 60M, 0.74 s of every refresh pair on the
    chip's host, PERF.md PR 34). Now the rows go in place, and the whole is
    copied only when the backing array is outgrown: with the slab's
    capacity, and its 25% of headroom."""
    n, m = len(mirror), len(new)
    base = mirror.base
    if not (isinstance(base, np.ndarray) and base.ndim == 1
            and base.dtype == mirror.dtype and len(base) >= n + m
            and base.ctypes.data == mirror.ctypes.data):  # mirror heads base
        base = np.empty(max(room, n + m), mirror.dtype)
        base[:n] = mirror
    base[n:n + m] = new
    return base[:n + m]


# the device arrays of the slab's two sorted runs, as `_sort_kernel` returns
# a run's
_BIG_RUN = ("sorted_keys", "perm", "sorted_valid")
_TAIL_RUN = ("tail_keys", "tail_perm", "tail_valid")


class ResidentJoinKeys:
    """One table's packed join-key lane, HBM-resident with host mirrors."""

    def __init__(self, log_path: str, metadata_id: str, version: int,
                 signature: str, key_cols: List[str]):
        self.log_path = log_path
        self.metadata_id = metadata_id
        self.version = version
        self.signature = signature
        self.key_cols = key_cols
        # table rewrite generation at build time (KeyCache.bump_epoch):
        # an entry from a pre-rewrite epoch is never cached or served
        self.epoch = 0
        self.slabs: Dict[str, Tuple[int, int]] = {}  # path -> (offset, rows)
        # path -> (storageType, pathOrInlineDv, cardinality) of the deletion
        # vector whose positions are currently masked (None = no DV applied)
        self.dv_tags: Dict[str, Optional[Tuple[str, str, int]]] = {}
        self.h_keys = np.empty(0, np.int64)
        self.h_valid = np.empty(0, bool)
        # immutable per row once appended: key is non-NULL. h_valid is
        # derived: null_ok AND file alive AND not deletion-vector-deleted
        self.h_nullok = np.empty(0, bool)
        # conservative valid-key range, maintained on append (kills/DV masks
        # only shrink the valid set, so the range stays a superset): keeps
        # the per-probe sentinel/narrowing decision O(source), not O(slab)
        self.h_min = np.iinfo(np.int64).max
        self.h_max = np.iinfo(np.int64).min
        self.num_rows = 0
        self.capacity = 1024
        self._dead = 0
        self._dev = None
        self._pending = None  # batched device updates (see device_batch)
        # The resident sorted view is two sorted runs of (sorted keys,
        # perm, sorted validity): the big run over the rows [0, _sorted_n)
        # it was last sorted with, at the capacity, and the tail over
        # [_sorted_n, num_rows), at `_tail_capacity`. Which of them lags
        # the lanes: None; "tail" after a key append that fits the tail, or
        # a validity flip of its rows; "all" (both runs dropped) after any
        # other change of key rows, an append the tail has no room for
        # (the fold), or a flip of more of the big run's rows than are
        # worth finding in it (`_flip_by_search`; a smaller one is mirrored
        # in sorted space). The next probe sorts what lags, once.
        self._stale: Optional[str] = "all"
        self._sorted_n = 0
        # what made it lag (reported by the next sort's span)
        self._sort_cause = "append"
        self._lock = threading.RLock()
        self.last_used = 0.0
        # device-memory accounting (gc-backstopped so a transient
        # SlabBuilder slab or popped cache entry that dies resident still
        # returns its bytes)
        from delta_tpu.obs.hbm_ledger import Account

        self._hbm = Account("keyCache")

    # -- batched device updates ------------------------------------------
    #
    # A log-tail advance touches many files (kill + revive + append per
    # file); dispatching per file costs two host round trips each. Inside a
    # device_batch the mutators accumulate row indices and the flush issues
    # at most three kernels.

    def device_batch(self):
        import contextlib

        @contextlib.contextmanager
        def batch():
            with self._lock:
                self._pending = {"kill": [], "revive": [],
                                 "rows": [], "keys": [], "valid": []}
            try:
                yield
            finally:
                self._flush_batch()

        return batch()

    def _flush_batch(self) -> None:
        with self._lock:
            p, self._pending = self._pending, None
            if p is None or self._dev is None:
                return  # device copy dropped mid-batch: mirrors re-ship later
            # row scatter FIRST: a file appended and DV-masked in the same
            # batch carries pre-DV validity in the scatter — the kill of its
            # masked rows must land after, never be overwritten
            if p["rows"]:
                rows = np.concatenate(p["rows"]).astype(np.int32)
                keys = np.concatenate(p["keys"]).astype(np.int64)
                valid = np.concatenate(p["valid"]).astype(bool)
                self._dev_scatter_rows(rows, keys, valid)
            if p["kill"]:
                self._dev_kill(np.concatenate(p["kill"]).astype(np.int32))
            if p["revive"]:
                self._dev_revive(np.concatenate(p["revive"]).astype(np.int32))

    # -- host-side maintenance -------------------------------------------

    def _append_file(self, path: str, keys: np.ndarray, valid: np.ndarray) -> bool:
        with self._lock:
            n = len(keys)
            if path in self.slabs:
                return False
            self.slabs[path] = (self.num_rows, n)
            room = max(self.capacity, int((self.num_rows + n) * 1.25))
            self.h_keys = _appended(self.h_keys, keys, room)
            self.h_valid = _appended(self.h_valid, valid, room)
            self.h_nullok = _appended(self.h_nullok, valid, room)
            if valid.any():
                self.h_min = min(self.h_min, int(keys[valid].min()))
                self.h_max = max(self.h_max, int(keys[valid].max()))
            start = self.num_rows
            self.num_rows += n
            if self.num_rows > self.capacity:
                # regrow: drop device arrays; next probe re-ships the mirrors.
                # 25% headroom, so a steady append stream (CDC rounds)
                # doesn't cross a bucket — and recompile the probe +
                # re-upload the slab — every few commits.
                self._dev = None
                self._hbm.off()  # before capacity changes: bytes were old-cap
                self.capacity = _slab_capacity(int(self.num_rows * 1.25))
                return True
            if self._pending is not None:
                self._pending["rows"].append(
                    np.arange(start, start + n, dtype=np.int32))
                self._pending["keys"].append(keys.astype(np.int64))
                self._pending["valid"].append(valid.astype(bool))
            elif self._dev is not None:
                self._dev_scatter_rows(
                    np.arange(start, start + n, dtype=np.int32),
                    keys.astype(np.int64), valid.astype(bool))
            return True

    def _kill_file(self, path: str) -> None:
        with self._lock:
            ent = self.slabs.pop(path, None)
            self.dv_tags.pop(path, None)
            if ent is None:
                return
            off, rows = ent
            self.h_valid[off:off + rows] = False
            self._dead += rows
            if self._pending is not None:
                self._pending["kill"].append(
                    np.arange(off, off + rows, dtype=np.int32))
            elif self._dev is not None:
                self._dev_kill(np.arange(off, off + rows, dtype=np.int32))

    def _set_dv(self, path: str, positions: np.ndarray) -> bool:
        """Install a file's deletion-vector state EXACTLY: validity becomes
        null_ok AND NOT deleted. Handles growth, shrink (RESTORE), and
        replacement — the device gets only the diff rows, both directions.

        Returns False when the DV disagrees with the slab (positions beyond
        the recorded row count, or no slab at all): masking the mismatch
        would leave deleted rows valid and matchable, so the caller must
        rebuild the entry instead."""
        with self._lock:
            ent = self.slabs.get(path)
            if ent is None:
                return False
            off, rows = ent
            if len(positions) and int(positions.max()) >= rows:
                return False
            pos = positions
            new_valid = self.h_nullok[off:off + rows].copy()
            if len(pos):
                new_valid[pos] = False
            old_valid = self.h_valid[off:off + rows]
            diff = np.nonzero(new_valid != old_valid)[0]
            if len(diff) == 0:
                return True
            self.h_valid[off:off + rows] = new_valid
            if self._pending is not None:
                to_false = diff[~new_valid[diff]]
                to_true = diff[new_valid[diff]]
                if len(to_false):
                    self._pending["kill"].append((off + to_false).astype(np.int32))
                if len(to_true):
                    self._pending["revive"].append((off + to_true).astype(np.int32))
            elif self._dev is not None:
                to_false = diff[~new_valid[diff]]
                to_true = diff[new_valid[diff]]
                if len(to_false):
                    self._dev_kill((off + to_false).astype(np.int32))
                if len(to_true):
                    self._dev_revive((off + to_true).astype(np.int32))
            return True

    @property
    def garbage_fraction(self) -> float:
        return self._dead / max(self.num_rows, 1)

    # -- device residency -------------------------------------------------

    @property
    def device_bytes(self) -> int:
        # keys(8) + valid(1) + the big sorted run: sorted_keys(8) + perm(4)
        # + sorted_valid(1), a capacity row; the tail's 13 a tail row: for
        # the whole residency
        return self.capacity * 22 + _tail_capacity(self.capacity) * 13

    @property
    def is_resident(self) -> bool:
        return self._dev is not None

    def drop_device(self) -> None:
        with self._lock:
            self._dev = None
            self._hbm.off()

    def alloc_device(self) -> None:
        """Pre-size the device arrays WITHOUT uploading the host mirrors —
        the cold-build pipeline (:class:`SlabBuilder`) then scatters each
        file's lane as it decodes, so the link transfer overlaps the
        remaining Parquet decode instead of following it. No-op when a
        device copy already exists."""
        ensure_compilation_cache()
        import jax.numpy as jnp

        with self._lock:
            if self._dev is not None:
                return
            with enable_x64():
                self._dev = {
                    "keys": jnp.zeros(self.capacity, jnp.int64),
                    "valid": jnp.zeros(self.capacity, bool),
                }
            self._drop_sorted_view("append")
            self._hbm.on(self, self.device_bytes)

    def ensure_resident(self) -> None:
        """Ship the mirrors to HBM in bounded tiles (the uploads queue on
        the transfer engine and overlap, and no single transfer stalls the
        process for the whole slab)."""
        ensure_compilation_cache()
        with self._lock:
            if self._dev is not None:
                return
            # the bytes land on the span as h2dBytes (link.to_device)
            with telemetry.record_operation(
                    "delta.keyCache.upload", {"rows": self.num_rows}):
                self._dev = self._ship_mirrors()
            self._drop_sorted_view("append")
            self._hbm.on(self, self.device_bytes)

    def _ship_mirrors(self) -> Dict[str, object]:
        import jax
        import jax.numpy as jnp

        keys = np.zeros(self.capacity, np.int64)
        keys[: self.num_rows] = self.h_keys
        valid = np.zeros(self.capacity, bool)
        valid[: self.num_rows] = self.h_valid
        # halve the big transfer when every key fits int32: ship
        # narrow, cast up on device. Invalid/null rows store 0, so a raw
        # min/max scan is the exact narrowing test.
        narrow = (self.num_rows == 0 or (
            int(keys.min()) >= np.iinfo(np.int32).min
            and int(keys.max()) <= np.iinfo(np.int32).max))
        # ~32MB tiles amortize the per-transfer overhead without any
        # single transfer stalling the process for the whole slab (tile
        # counts are in ELEMENTS, derived from the byte budget per dtype)
        tile_bytes = 32 << 20
        with enable_x64():
            def ship(arr):
                step = max(tile_bytes // arr.itemsize, 1)
                if len(arr) <= step:
                    return link.to_device(arr)
                return jnp.concatenate([
                    link.to_device(arr[i:i + step])
                    for i in range(0, len(arr), step)
                ])

            if narrow:
                dk = _update_kernels()["widen"](ship(keys.astype(np.int32)))
            else:
                dk = ship(keys)
            dv = ship(valid)
            jax.block_until_ready((dk, dv))
        return {"keys": dk, "valid": dv}

    def _ensure_sorted(self) -> None:
        """Dispatch the sort of whichever sorted run lags the lanes (caller
        holds the entry lock): the tail alone from its window of the lanes,
        or the whole slab into the big run, which leaves the tail empty.
        The dispatch is async (~ms); the probe kernel that consumes the
        handles queues behind it on the device."""
        if self._dev is None or self._stale is None:
            return
        tier, n = self._stale, self.num_rows
        t = _tail_capacity(self.capacity)
        if tier == "all":
            size, lo, start = self.capacity, 0, 0
        else:
            # a tail's length back from the capacity's end where the big
            # run ends nearer to it than that: those rows read as padding
            size, lo = t, self._sorted_n
            start = min(lo, self.capacity - t)
        # `tier`: the tail alone or both runs; `cause`: what made it lag, a
        # key append, a flip too large to search for (`flips`, or a flip of
        # the tail's rows), an append the tail had no room for (`fold`)
        with telemetry.record_operation(
                "delta.keyCache.sort",
                {"rows": n - lo, "tier": tier, "cause": self._sort_cause}), \
                enable_x64():
            run = _sort_kernel()(
                self._dev["keys"], self._dev["valid"],
                link.to_device(np.array([start, lo, n], np.int32)), size)
            if tier == "all":
                self._sorted_n = n
                self._dev.update(zip(_BIG_RUN, run))
                run = _update_kernels()["empty_tail"](t)
            self._dev.update(zip(_TAIL_RUN, run))
        if tier == "tail":
            telemetry.bump_counter("merge.keyCache.tailSorts")
        elif self._sort_cause == "fold":
            telemetry.bump_counter("merge.keyCache.folds")
        self._stale = None

    def _drop_sorted_view(self, cause: str) -> None:
        """Both sorted runs lag the lanes: drop them (frees HBM) and let
        the next probe sort the whole slab into the big run."""
        self._stale, self._sorted_n, self._sort_cause = "all", 0, cause
        for view in _BIG_RUN + _TAIL_RUN:
            self._dev.pop(view, None)

    def _tail_lags(self, cause: str) -> None:
        """The tail lags the lanes (the big run, if live, stays so)."""
        if self._stale is None:
            self._stale, self._sort_cause = "tail", cause

    def _dev_flip_valid(self, rows: np.ndarray, value: bool) -> None:
        """Validity flip in ROW space plus, when the sorted view is live,
        the mirrored flip in SORTED space. Rows of the big run flip at the
        positions a search of it finds for them (`_inverse_permutation_at`);
        more of them than `_flip_by_search` allows stay in row space and
        drop the view: the next probe's sort carries validity in its
        payload anyway. Rows of the tail are not searched for: the tail
        lags, and its sort carries them. A flip on a dropped view is a
        row-space flip and nothing else."""
        d = _next_pow2(max(len(rows), 1), floor=64)
        padded = np.full(d, self.capacity, np.int32)
        padded[: len(rows)] = rows
        kern = _update_kernels()["kill" if not value else "revive"]
        rows_dev = link.to_device(padded)
        self._dev["valid"] = kern(self._dev["valid"], rows_dev)
        if self._stale == "all":
            return
        flips = int(np.count_nonzero(rows < self._sorted_n))
        if flips < len(rows):
            self._tail_lags("flips")
        if flips == 0:
            return
        if not _flip_by_search(flips, self.capacity):
            self._drop_sorted_view("flips")
            telemetry.bump_counter("merge.keyCache.flipResorts")
            return
        if flips < len(rows):
            # the search's program at the flip's own shape: the tail's rows
            # are padding to it
            rows_dev = link.to_device(np.where(
                padded < self._sorted_n, padded, np.int32(self.capacity)))
        with telemetry.record_operation(
                "delta.keyCache.locate",
                {"rows": self.num_rows, "flips": flips,
                 "steps": _search_steps(self.capacity)}), enable_x64():
            spos = _inverse_permutation_at()(
                self._dev["sorted_keys"], self._dev["perm"],
                self._dev["keys"], rows_dev)
        telemetry.bump_counter("merge.keyCache.flipSearches")
        self._dev["sorted_valid"] = kern(self._dev["sorted_valid"], spos)

    def _dev_kill(self, rows: np.ndarray) -> None:
        self._dev_flip_valid(rows, False)

    def _dev_revive(self, rows: np.ndarray) -> None:
        self._dev_flip_valid(rows, True)

    def _dev_scatter_rows(self, row_idx: np.ndarray, keys: np.ndarray,
                          valid: np.ndarray) -> None:
        k = len(keys)
        a = _next_pow2(max(k, 1), floor=64)
        i32 = np.iinfo(np.int32)
        kdtype = (np.int32 if len(keys) and keys.min() >= i32.min
                  and keys.max() <= i32.max else np.int64)
        nk = np.zeros(a, kdtype)
        nk[:k] = keys
        nv = np.zeros(a, bool)
        nv[:k] = valid
        one_run = k > 0 and bool(
            (row_idx == np.arange(row_idx[0], row_idx[0] + k,
                                  dtype=row_idx.dtype)).all())
        contiguous = one_run and row_idx[0] + a <= self.capacity
        # key rows changed. One run of rows after the live big run's stays
        # out of it: the tail's next sort takes them in, if it has room for
        # them; if not, one sort of the whole slab (the fold) empties it
        past_big = (one_run and self._stale != "all"
                    and row_idx[0] >= self._sorted_n)
        if past_big and row_idx[0] + k - self._sorted_n <= _tail_capacity(
                self.capacity):
            self._tail_lags("append")
        else:
            self._drop_sorted_view("fold" if past_big else "append")
        with enable_x64():
            if contiguous:
                self._dev["keys"], self._dev["valid"] = (
                    _update_kernels()["slice_append"](
                        self._dev["keys"], self._dev["valid"],
                        link.to_device(np.int32(row_idx[0])),
                        link.to_device(nk), link.to_device(nv),
                    )
                )
                return
            rows = np.full(a, self.capacity, np.int32)
            rows[:k] = row_idx
            self._dev["keys"], self._dev["valid"] = _update_kernels()["append"](
                self._dev["keys"], self._dev["valid"],
                link.to_device(rows), link.to_device(nk), link.to_device(nv),
            )

    # -- probing ----------------------------------------------------------

    def probe_async(self, s_keys: np.ndarray, s_ok: np.ndarray,
                    expected_version: Optional[int] = None,
                    insert_only: bool = False) -> Optional[PendingProbe]:
        """Membership probe of sentinel-encodable source keys against the
        resident slab — fused with the join's pairing: the probe kernel also
        emits each matched slab row's first-match source index, and the
        finalize downloads the compacted O(matched) pairs instead of the
        full mask. Returns None when no sentinel room exists (valid keys
        span int64) — callers fall back to the host join.

        ``insert_only``: the caller consumes only the per-source matched
        flags (the reference's left-anti fast path) — the finalize then
        fetches the head alone and skips the pair download entirely.

        ``expected_version`` guards the advance race: a tail advance holds
        the entry lock for its whole multi-step application, so under the
        lock the slab is either fully at the caller's version or fully past
        it — never half-advanced. Past it → None (caller falls back)."""
        from delta_tpu.ops.join_kernel import _bucket

        with self._lock:
            if expected_version is not None and self.version != expected_version:
                return None
            n = self.num_rows
            cap = self.capacity
            if n == 0:
                m = len(s_keys)
                slabs = dict(self.slabs)
                empty = np.empty(0, np.int64)
                return PendingProbe(lambda: PhysicalProbe(
                    np.zeros(m, bool), False, slabs, 0, (empty, empty)))
            s_key64 = np.ascontiguousarray(s_keys, np.int64)
            s_okb = np.asarray(s_ok, bool)
            # O(source) sentinel/narrowing decision: the slab's valid range
            # is maintained incrementally (h_min/h_max, a conservative
            # superset), so only the source is scanned here. Narrow the
            # uploaded side to int32 when every valid key fits — the source
            # sentinel then lives in int32 space and survives the device-
            # side cast. (The slab side needs no sentinel: the sorted-probe
            # kernel applies validity in sorted space via the permutation.)
            lo = min(self.h_min, int(np.min(s_key64, where=s_okb, initial=2**62)))
            hi = max(self.h_max, int(np.max(s_key64, where=s_okb, initial=-2**62)))
            i32, i64 = np.iinfo(np.int32), np.iinfo(np.int64)
            if lo >= i32.min + 2 and hi <= i32.max - 2:
                dtype = np.int32
                s_sent = i32.max - 1
            elif hi <= i64.max - 2:
                dtype = np.int64
                s_sent = i64.max - 1
            elif lo >= i64.min + 2:
                dtype = np.int64
                s_sent = i64.min + 1
            else:
                return None  # valid keys span int64: no sentinel room
            s_enc = np.where(s_okb, s_key64, s_sent).astype(dtype)
            self.ensure_resident()
            self._ensure_sorted()
            # pin this version's arrays: jax arrays are immutable, so a
            # concurrent tail advance replaces, never mutates, these
            dev = {view: self._dev[view] for view in _BIG_RUN + _TAIL_RUN}
            slabs = dict(self.slabs)
        m = len(s_enc)
        cap_s = _bucket(m)
        s_in = np.full(cap_s, s_sent, s_enc.dtype)
        s_in[:m] = s_enc
        state: dict = {}
        from delta_tpu.obs import hbm_ledger

        # transient probe scratch (the uploaded source lane, the source
        # sorted with a run's block boundaries, one chunk of gathered
        # blocks, for the big run and then the tail) in the HBM ledger while
        # the probe is in flight; released on the staging thread, which
        # always runs to completion
        tail = _tail_capacity(cap)
        blk, tail_blk = _probe_block(cap, cap_s), _probe_block(tail, cap_s)
        ranked, tail_ranked = cap_s + cap // blk, cap_s + tail // tail_blk
        scratch_bytes = int(s_in.nbytes) + 40 * (ranked + tail_ranked) + 9 * (
            min(ranked * blk, 1 << 24) + min(tail_ranked * tail_blk, 1 << 24))
        hbm_ledger.adjust("scratch", scratch_bytes)
        # scratch growth applies eviction pressure immediately (no cache or
        # entry lock held at this point; this probe's arrays are pinned in
        # `dev`, so even self-eviction cannot break the in-flight probe)
        hbm_ledger.maybe_relieve()
        # carry the caller's open span chain (the MERGE command span) into
        # the staging thread: the probe's device pipeline then shows up in
        # `export_chrome_trace` on its own thread lane, parented under
        # `delta.dml.merge`, instead of as an orphan root
        probe_ctx = telemetry.span_context()

        def launch():
            # the whole device pipeline runs on this staging thread, beside
            # the caller's host-side work; finalize joins it, fetches the pairs
            pair_bytes = 0
            try:
                with telemetry.adopt_span_context(probe_ctx), \
                        telemetry.record_operation(
                            "delta.merge.deviceProbe",
                            {"slabRows": int(n), "sourceRows": int(m),
                             "insertOnly": insert_only, "blockRows": blk,
                             "tailBlockRows": tail_blk,
                             "candidates": ranked * blk
                             + tail_ranked * tail_blk}):
                    with enable_x64():
                        head_dev, *runs = _probe_sorted_kernel()(
                            dev["sorted_keys"], dev["sorted_valid"],
                            dev["tail_keys"], dev["tail_valid"],
                            link.to_device(s_in))
                        # blocks until the kernel is done
                        state["head"] = _decode_head(
                            link.to_host(head_dev), cap_s, m)
                        _multi, mc, tc, _s = state["head"]
                        telemetry.add_span_data(matched=mc, candidateRows=tc)
                        if insert_only or mc == 0:
                            return
                        # room for as many dead versions as pairs before the
                        # candidates' bucket steps (and the program with it)
                        out_cap = _next_pow2(mc, floor=64)
                        cand_cap = max(2 * out_cap, _next_pow2(tc, floor=64))
                        if 16 * cand_cap > _PROBE_SCRATCH_BYTES:
                            state["overflow"] = True
                            telemetry.bump_counter(
                                "merge.resident.probe.overflow")
                            return
                        # and the two runs' validity and permutation
                        # laid end to end
                        pair_bytes = 16 * cand_cap + 5 * (cap + tail)
                        hbm_ledger.adjust("scratch", pair_bytes)
                        state["pairs_dev"] = _pair_kernel()(
                            *runs, dev["sorted_valid"], dev["perm"],
                            dev["tail_valid"], dev["tail_perm"],
                            cand_cap, out_cap)
            except BaseException as e:
                state["err"] = e
            finally:
                hbm_ledger.adjust("scratch", -scratch_bytes - pair_bytes)

        th = threading.Thread(target=launch, daemon=True,
                              name="delta-merge-device-probe")
        th.start()

        def finalize() -> PhysicalProbe:
            th.join()
            if "err" in state:
                raise state["err"]
            if "overflow" in state:
                raise DeltaProbeOverflow(
                    "probe candidate rows pass the scratch bound; host fallback")
            multi, mc, _tc, s = state["head"]
            if insert_only:
                # left-anti fast path: the head already carried everything
                return PhysicalProbe(s, multi, slabs, n, None)
            if mc == 0:
                empty = np.empty(0, np.int64)
                return PhysicalProbe(s, multi, slabs, n, (empty, empty))
            pairs = link.to_host(state["pairs_dev"])
            return PhysicalProbe(s, multi, slabs, n, (
                pairs[0, :mc].astype(np.int64), pairs[1, :mc].astype(np.int64)))

        return PendingProbe(finalize)


# -- building / advancing ----------------------------------------------------


def _file_keys(data_path: str, add, key_cols: List[str], exprs) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Evaluate the packed target key lane over a file's PHYSICAL rows
    (no DV filtering; DV positions are masked invalid separately)."""
    import os
    import urllib.parse

    import pyarrow as pa
    import pyarrow.parquet as pq

    from delta_tpu.expr.vectorized import evaluate

    path = add.path
    if "://" in path or os.path.isabs(path):
        abs_path = urllib.parse.unquote(path)
    else:
        abs_path = os.path.join(
            data_path, urllib.parse.unquote(path).replace("/", os.sep))
    try:
        pf = pq.ParquetFile(abs_path, memory_map=True)
        present = [c for c in key_cols if c in pf.schema_arrow.names]
        if len(present) != len(key_cols):
            return None
        tab = pf.read(columns=present)
    except Exception:
        return None
    return _pack_lanes(tab, exprs, evaluate)


def _pack_lanes(tab, exprs, evaluate) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    import pyarrow as pa
    import pyarrow.compute as pc

    lanes = []
    for e in exprs:
        try:
            vals = evaluate(e, tab)
        except Exception:
            return None
        arr = vals.combine_chunks() if isinstance(vals, pa.ChunkedArray) else vals
        if not pa.types.is_integer(arr.type):
            return None
        valid = ~np.asarray(pc.is_null(arr))
        keys = np.asarray(arr.fill_null(0).cast(pa.int64()))
        lanes.append((keys, valid))
    if len(lanes) == 1:
        return lanes[0]
    if len(lanes) != 2:
        return None
    i32 = np.iinfo(np.int32)
    (k0, v0), (k1, v1) = lanes
    ok = v0 & v1
    if (np.min(k0, where=ok, initial=0) < i32.min
            or np.max(k0, where=ok, initial=0) > i32.max
            or np.min(k1, where=ok, initial=0) < i32.min
            or np.max(k1, where=ok, initial=0) > i32.max):
        return None
    return (k0 << 32) | (k1 & 0xFFFFFFFF), ok


def _dv_tag(dv_dict) -> Optional[Tuple[str, str, int]]:
    if not dv_dict:
        return None
    return (dv_dict.get("storageType"), dv_dict.get("pathOrInlineDv"),
            int(dv_dict.get("cardinality", -1)))


def _dv_positions(dv_dict, data_path: str) -> Optional[np.ndarray]:
    from delta_tpu.protocol.deletion_vectors import (
        DeletionVectorDescriptor, read_deletion_vector,
    )

    try:
        return read_deletion_vector(
            DeletionVectorDescriptor.from_dict(dv_dict), data_path)
    except Exception:
        return None


class SlabBuilder:
    """Streamed cold build of a :class:`ResidentJoinKeys` slab from per-file
    decoded key tables — the upload leg of the fused device MERGE pipeline
    (`commands/merge.py`). Files arrive in decode-completion order; each
    file's packed lane scatters straight onto a pre-sized HBM allocation
    (a contiguous slice append), so the link transfer overlaps the
    remaining Parquet decode instead of following it.

    Slab layout must be exact per file even though the decode arrives
    DV-filtered: per-file PHYSICAL row counts come from AddFile stats
    (``numRecords`` is physical as this engine writes it; logical ==
    physical when no deletion vector) or the cached Parquet footer when a
    deletion vector is present or stats are absent."""

    def __init__(self, log_path: str, metadata_id: str, version: int,
                 signature: str, key_cols: List[str], exprs,
                 data_path: str, files, device: bool = True, epoch: int = 0):
        self.exprs = list(exprs)
        self.data_path = data_path
        self.failed: Optional[str] = None
        self.device = device
        # the exception a failed device allocation raised (the build then
        # continues on host mirrors); the MERGE reports it on its router
        # event, and raises it under devicePath.mode=force
        self.alloc_error: Optional[BaseException] = None
        self._phys: Dict[str, int] = {}
        total = 0
        for add in files:
            nrec = add.num_logical_records
            if add.deletion_vector is not None or nrec is None:
                n = self._footer_rows(add)
                if n is None:
                    self.failed = f"no physical row count for {add.path}"
                    break
            else:
                n = int(nrec)
            self._phys[add.path] = n
            total += n
        entry = ResidentJoinKeys(log_path, metadata_id, version, signature,
                                 list(key_cols))
        entry.epoch = epoch
        entry.capacity = _slab_capacity(max(total, 1))
        self.entry = entry

    def _footer_rows(self, add) -> Optional[int]:
        from delta_tpu.exec import rowgroups
        from delta_tpu.exec.scan import _abs_data_path

        try:
            return int(rowgroups.read_footer(
                _abs_data_path(self.data_path, add.path)).num_rows)
        except Exception:
            return None

    def add_file(self, add, table, positions: Optional[np.ndarray]) -> bool:
        """Pack one decoded file's key lane and append+upload it.
        ``positions`` are the decoded rows' physical positions (None when
        the decode was not DV-filtered). Any disagreement with the recorded
        physical row count poisons the build (the merge falls back to its
        other executors)."""
        if self.failed is not None:
            return False
        from delta_tpu.expr.vectorized import evaluate

        phys = self._phys.get(add.path)
        packed = _pack_lanes(table, self.exprs, evaluate)
        if phys is None or packed is None:
            self.failed = f"unpackable key lane for {add.path}"
            return False
        keys, valid = packed
        if positions is None:
            if len(keys) != phys:
                self.failed = f"row count mismatch for {add.path}"
                return False
            full_k = np.ascontiguousarray(keys, np.int64)
            full_v = np.asarray(valid, bool)
        else:
            if len(positions) != len(keys) or (
                    len(positions) and int(positions[-1]) >= phys):
                self.failed = f"position/physical mismatch for {add.path}"
                return False
            full_k = np.zeros(phys, np.int64)
            full_v = np.zeros(phys, bool)
            full_k[positions] = keys
            full_v[positions] = valid
        e = self.entry
        if self.device and e._dev is None and self.alloc_error is None:
            try:
                e.alloc_device()
            except Exception as err:  # noqa: BLE001 — host mirrors still work
                self.alloc_error = err
        if not e._append_file(add.path, full_k, full_v):
            self.failed = f"duplicate file {add.path}"
            return False
        e.dv_tags[add.path] = _dv_tag(add.deletion_vector)
        return True

    def finish(self, expected_files: int) -> Optional[ResidentJoinKeys]:
        if self.failed is not None or len(self.entry.slabs) != expected_files:
            return None
        return self.entry


class KeyCache:
    """Process-wide registry of resident join-key lanes, keyed by
    (log path, signature). Mirrors `DeviceStateCache`'s locking: registry
    lock for lookups, per-entry build locks for the slow work."""

    _instance: Optional["KeyCache"] = None
    _instance_lock = threading.Lock()

    def __init__(self):
        self._entries: Dict[Tuple[str, str], ResidentJoinKeys] = {}
        self._build_locks: Dict[Tuple[str, str], threading.Lock] = {}
        self._lock = threading.RLock()
        self._tick = 0
        # per-table rewrite generation (bump_epoch): entries built under an
        # older epoch are never served or cached
        self._epochs: Dict[str, int] = {}
        # tables whose residency gauge was last published non-zero, so a
        # full drop publishes an explicit 0 (see _publish_residency), and
        # the last value published per table (unchanged values skip the
        # telemetry lock)
        self._last_resident: set = set()
        self._published_bytes: Dict[str, int] = {}

    @classmethod
    def instance(cls) -> "KeyCache":
        with cls._instance_lock:
            if cls._instance is None:
                cls._instance = KeyCache()
            return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._instance_lock:
            cls._instance = None

    def invalidate(self, log_path: str) -> None:
        with self._lock:
            for k in [k for k in self._entries if k[0] == log_path]:
                e = self._entries.pop(k, None)
                self._build_locks.pop(k, None)
                if e is not None:
                    e.drop_device()  # return its bytes to the HBM ledger
        self._publish_residency()

    def epoch(self, log_path: str) -> int:
        with self._lock:
            return self._epochs.get(log_path, 0)

    def bump_epoch(self, log_path: str) -> None:
        """File-rewrite invalidation (OPTIMIZE / UPDATE-rewrite / RESTORE):
        drop the table's resident entries outright — a stale slab must never
        serve a post-rewrite MERGE, and after a rewrite most of the slab is
        garbage anyway (an advance would kill + re-append nearly every
        row). In-flight holders of a dropped entry fail their version guard:
        the version is poisoned before release."""
        from delta_tpu.utils.telemetry import bump_counter

        with self._lock:
            self._epochs[log_path] = self._epochs.get(log_path, 0) + 1
            stale = [k for k in self._entries if k[0] == log_path]
            for k in stale:
                e = self._entries.pop(k)
                e.version = _POISON_VERSION
                self._build_locks.pop(k, None)
                e.drop_device()  # return its bytes to the HBM ledger
        if stale:
            bump_counter("merge.keyCache.invalidations", len(stale))
            self._publish_residency()

    def register(self, entry: ResidentJoinKeys) -> bool:
        """Adopt an externally built slab (the merge cold pipeline's
        :class:`SlabBuilder` output) so later MERGEs against the table
        cache-hit. Refused when the table's epoch moved during the build (a
        rewrite raced it) or a newer entry already holds the key — the
        caller's probe of the transient entry stays valid either way."""
        from delta_tpu.utils.telemetry import bump_counter

        if not key_cache_enabled():
            return False
        key = (entry.log_path, entry.signature)
        with self._lock:
            if entry.epoch != self._epochs.get(entry.log_path, 0):
                return False
            cur = self._entries.get(key)
            if cur is not None and cur.version >= entry.version:
                return False
            self._tick += 1
            entry.last_used = self._tick
            self._entries[key] = entry
            self._build_locks.setdefault(key, threading.Lock())
        bump_counter("merge.keyCache.builds")  # inline cold build adopted
        self._evict(keep=key)
        return True

    def peek(self, log_path: str, signature: str) -> Optional[ResidentJoinKeys]:
        with self._lock:
            return self._entries.get((log_path, signature))

    def get(self, snapshot, signature: str, key_cols: List[str],
            exprs, build_if_missing: bool = True) -> Optional[ResidentJoinKeys]:
        """Entry current at the snapshot's version, advancing incrementally
        through the log tail (appending new files' keys, killing removed
        files, masking DV growth). ``build_if_missing=False`` only serves /
        advances an existing entry — the cold build policy stays with the
        caller (merge builds in the background after an eligible merge)."""
        from delta_tpu.utils.telemetry import bump_counter

        if not key_cache_enabled():
            return None
        log_path = snapshot.delta_log.log_path
        key = (log_path, signature)
        with self._lock:
            self._tick += 1
            tick = self._tick
            cur_epoch = self._epochs.get(log_path, 0)
            build_lock = self._build_locks.setdefault(key, threading.Lock())
            e = self._entries.get(key)
        if e is not None and (e.metadata_id != snapshot.metadata.id
                              or e.version > snapshot.version
                              or e.epoch != cur_epoch):
            e = None
        if e is not None and e.version == snapshot.version:
            e.last_used = tick
            return e
        if e is None and not build_if_missing:
            return None
        with build_lock:
            with self._lock:
                cur_epoch = self._epochs.get(log_path, 0)
                e = self._entries.get(key)
            if e is not None and (e.metadata_id != snapshot.metadata.id
                                  or e.version > snapshot.version
                                  or e.epoch != cur_epoch):
                e = None
            if e is not None and e.version == snapshot.version:
                e.last_used = tick
                return e
            if e is not None:
                with telemetry.record_operation(
                        "delta.keyCache.advance",
                        {"fromVersion": e.version,
                         "toVersion": snapshot.version}):
                    advanced = self._advance(e, snapshot, key_cols, exprs)
                if advanced:
                    bump_counter("merge.keyCache.advances")
                else:
                    # a failed advance may have half-applied its tail: the
                    # entry must not stay visible at its (stale) version
                    with self._lock:
                        if self._entries.get(key) is e:
                            self._entries.pop(key, None)
                    e = None
            if e is None:
                if not build_if_missing:
                    return None
                e = self._build(snapshot, signature, key_cols, exprs,
                                epoch=cur_epoch)
                if e is None:
                    return None
                bump_counter("merge.keyCache.builds")
                with self._lock:
                    # a rewrite may have raced the build: the entry stays
                    # exact for the caller's snapshot (file contents are
                    # immutable), so serve it — but only CACHE it when the
                    # epoch still matches
                    if self._epochs.get(log_path, 0) == cur_epoch:
                        self._entries[key] = e
            e.last_used = tick
            self._evict(keep=key)
            return e

    def _build(self, snapshot, signature, key_cols, exprs,
               epoch: int = 0) -> Optional[ResidentJoinKeys]:
        e = ResidentJoinKeys(
            snapshot.delta_log.log_path, snapshot.metadata.id,
            snapshot.version, signature, list(key_cols),
        )
        e.epoch = epoch
        data_path = snapshot.delta_log.data_path
        for add in snapshot.all_files:
            kv = _file_keys(data_path, add, key_cols, exprs)
            if kv is None:
                return None
            keys, valid = kv
            e._append_file(add.path, keys, valid)
            if add.deletion_vector is not None:
                pos = _dv_positions(add.deletion_vector, data_path)
                if pos is None:
                    return None
                if not e._set_dv(add.path, pos):
                    return None
                e.dv_tags[add.path] = _dv_tag(add.deletion_vector)
        return e

    def _advance(self, e: ResidentJoinKeys, snapshot, key_cols, exprs) -> bool:
        """Apply the log tail (e.version, snapshot.version]."""
        from delta_tpu.log.columnar import decode_segment
        from delta_tpu.protocol import filenames
        from delta_tpu.protocol.actions import AddFile, Metadata, RemoveFile

        if e.garbage_fraction > 0.5 and e.num_rows > 1 << 20:
            return False  # too much garbage: rebuild compacts
        log = snapshot.delta_log
        paths = [
            f"{log.log_path}/{filenames.delta_file(v)}"
            for v in range(e.version + 1, snapshot.version + 1)
        ]
        try:
            cols = decode_segment(log.store, [], paths)
        except Exception:
            return False
        if any(isinstance(a, Metadata) for a in cols.other_actions):
            return False
        w = cols.winner_mask()
        actions = cols.materialize(w)
        data_path = log.data_path
        # hold the ENTRY lock across the whole multi-step application (and
        # the version bump): a concurrent probe then sees the slab either
        # fully at its version or fully past it, never in between
        with e._lock, e.device_batch():
            # poison a half-applied tail BEFORE releasing the entry lock —
            # on clean failure AND on exceptions (a raise would otherwise
            # bypass get()'s pop and leave the entry serving probes at its
            # old version with some files killed and others not appended)
            ok = False
            try:
                for a in actions:
                    if isinstance(a, RemoveFile):
                        e._kill_file(a.path)
                    elif isinstance(a, AddFile):
                        if a.path not in e.slabs:
                            kv = _file_keys(data_path, a, key_cols, exprs)
                            if kv is None:
                                return False
                            if not e._append_file(a.path, *kv):
                                return False
                        # re-adds keep their keys (physical rows are
                        # immutable); only the DV validity may change
                        new_tag = _dv_tag(a.deletion_vector)
                        if e.dv_tags.get(a.path) != new_tag:
                            if a.deletion_vector is not None:
                                pos = _dv_positions(a.deletion_vector, data_path)
                                if pos is None:
                                    return False
                            else:
                                pos = np.empty(0, np.int64)
                            if not e._set_dv(a.path, pos):
                                return False
                            e.dv_tags[a.path] = new_tag
                ok = True
                return True
            finally:
                # poison ABOVE any real version: get()'s `e.version >
                # snapshot.version` staleness guard then discards the entry
                # in O(1) instead of attempting a from-zero tail decode
                e.version = snapshot.version if ok else _POISON_VERSION

    def _publish_residency(self) -> None:
        """Per-table ``keyCache.residentBytes`` gauges for the fleet plane
        (label: hashed table path). Runs only on mutation paths (build /
        advance / evict / invalidate / epoch bump — pure cache hits return
        before ``_evict``); unchanged values skip the telemetry lock, and
        tables whose last entry just dropped publish an explicit 0 so
        scraped series show the release."""
        from delta_tpu.obs.fleet import table_label
        from delta_tpu.utils.telemetry import set_gauge

        with self._lock:
            by_table: Dict[str, int] = {t: 0 for t in self._last_resident}
            for (log_path, _sig), e in self._entries.items():
                if e.is_resident:
                    table = log_path[:-len("/_delta_log")] \
                        if log_path.endswith("/_delta_log") else log_path
                    by_table[table] = by_table.get(table, 0) + e.device_bytes
            self._last_resident = {t for t, b in by_table.items() if b}
            changed = {t: b for t, b in by_table.items()
                       if self._published_bytes.get(t) != b}
            self._published_bytes.update(changed)
            # published under the lock: two racing mutators (a drop and a
            # register) must not land their gauge writes out of order and
            # leave a stale value standing
            for table, total in changed.items():
                set_gauge("keyCache.residentBytes", total,
                          table=table_label(table))

    def _evict(self, keep) -> None:
        budget = int(conf.get("delta.tpu.keyCache.maxBytes", 1 << 30))
        # the process-wide device-memory soft budget (obs/hbm_ledger): the
        # key cache yields to state-cache lanes and in-flight scratch, so
        # growth anywhere becomes LRU pressure here instead of OOM
        from delta_tpu.obs import hbm_ledger

        allowance = hbm_ledger.key_cache_allowance()
        if allowance is not None:
            budget = min(budget, allowance)
        with self._lock:
            resident = [(k, e) for k, e in self._entries.items() if e.is_resident]
            total = sum(e.device_bytes for _, e in resident)
            for k, e in sorted(resident, key=lambda kv: kv[1].last_used):
                if total <= budget:
                    break
                if k == keep:
                    continue
                total -= e.device_bytes
                e.drop_device()
            max_entries = int(conf.get("delta.tpu.keyCache.maxEntries", 8))
            if len(self._entries) > max_entries:
                for k, e in sorted(self._entries.items(),
                                   key=lambda kv: kv[1].last_used):
                    if k == keep:
                        continue
                    self._entries.pop(k, None)
                    self._build_locks.pop(k, None)
                    e.drop_device()  # return its bytes to the HBM ledger
                    if len(self._entries) <= max_entries:
                        break
        self._publish_residency()
