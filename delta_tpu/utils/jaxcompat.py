"""The narrow slice of jax API the engine's kernels share.

The wrappers resolve jax LAZILY, at call time: several modules
(``ops/pruning``, ``ops/zorder``, ``ops/key_cache``, ``ops/join_kernel``)
deliberately keep every jax import function-local so the plain host scan
path never pays the multi-second ``import jax`` — importing this module
must not break that.
"""
from __future__ import annotations

__all__ = ["enable_x64", "shard_map"]


def enable_x64():
    """Context manager enabling 64-bit dtypes (``jax.enable_x64()``)."""
    import jax

    return jax.enable_x64()


def shard_map(*args, **kwargs):
    """``jax.shard_map``."""
    import jax

    return jax.shard_map(*args, **kwargs)
