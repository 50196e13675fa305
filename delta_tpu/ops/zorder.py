"""Z-order (Morton) interleaving on device.

The reference carries Z-order cluster tags in the file format
(`actions/actions.scala:270-291`) but ships no OPTIMIZE command; the baseline
harness measures Z-ORDER + point-query skipping, so we implement it: each
clustering column is rank-normalized to 16 bits, ranks are bit-interleaved
into one Morton key on device (16 static rounds of shifts/masks — pure VPU
work, fused by XLA), and rows sort by that key. Sorting by Morton keys makes
per-file min/max boxes compact in every clustered dimension, which is what
the skipping predicate (`ops/pruning.py`) exploits.
"""
from __future__ import annotations

from typing import Sequence

import numpy as np
from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64

__all__ = ["morton_order", "rank_u16"]

_BITS = 16


def rank_u16(values: np.ndarray) -> np.ndarray:
    """Dense-rank a column and scale into [0, 2^16): order-preserving,
    type-agnostic (works for strings via argsort on host)."""
    order = np.argsort(values, kind="stable")
    ranks = np.empty(len(values), np.int64)
    ranks[order] = np.arange(len(values))
    n = max(len(values) - 1, 1)
    return ((ranks * ((1 << _BITS) - 1)) // n).astype(np.uint32)


def morton_order(columns: Sequence[np.ndarray]) -> np.ndarray:
    """Row permutation sorting by the interleaved (Morton) key of the given
    rank columns; the bit-interleave runs on the device."""
    k = len(columns)
    if k == 0:
        raise ValueError("morton_order needs at least one column")
    ranks = [rank_u16(c) for c in columns]
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    @jax.jit
    def interleave(rs):
        key = jnp.zeros(rs[0].shape, jnp.uint64)
        for b in range(_BITS):
            for c in range(k):
                bit = (rs[c] >> b) & 1
                key = key | (bit.astype(jnp.uint64) << (b * k + c))
        return key

    with enable_x64():
        key = np.asarray(interleave([jnp.asarray(r) for r in ranks]))
    return np.argsort(key, kind="stable")
