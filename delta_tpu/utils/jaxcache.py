"""Persistent XLA compilation cache — where it lives.

Device kernels here compile against a handful of bucketed shapes
(`join_kernel._bucket`, `state_cache._next_pow2`); a cold compile of the
larger ones costs seconds to tens of seconds on a TPU. JAX's persistent
compilation cache amortizes that across processes: first contact per
machine compiles, everything after loads from disk.

Placement, in order:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at import; this
  module touches no ``jax.config`` option, so the operator's directory is
  the only one used.
* otherwise ``<checkout>/.jax_cache``, derived from this package's own
  location — a fixed path (the directory is part of JAX's cache key, so a
  home-, temp-, pid- or time-derived path would never hit).

Every module that jits passes :func:`ensure_compilation_cache` before its
first compile. A directory that cannot be created is an error naming the
path, never a silent in-memory cache.
"""
from __future__ import annotations

import os
import threading

__all__ = ["ensure_compilation_cache", "cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_done = False
_lock = threading.Lock()


def cache_dir() -> str:
    """The directory compiled executables persist to (see module doc)."""
    env = os.environ.get(_ENV)
    if env:
        return env
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(checkout, ".jax_cache")


def ensure_compilation_cache() -> None:
    global _done
    if _done:
        return
    with _lock:
        if _done:
            return
        if not os.environ.get(_ENV):
            path = cache_dir()
            try:
                os.makedirs(path, exist_ok=True)
            except OSError as e:
                raise RuntimeError(
                    f"cannot create the XLA compilation cache directory "
                    f"{path!r} ({e}); set {_ENV} to a writable directory"
                ) from e
            import jax

            jax.config.update("jax_compilation_cache_dir", path)
        _done = True
