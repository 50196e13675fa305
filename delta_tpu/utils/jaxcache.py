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

The same first pass registers one ``jax.monitoring`` listener, so that a
compile is counted where it happens: ``device.compiles`` / ``device.compileUs``
for every XLA compile, ``scan.device.compiles`` / ``merge.device.compiles`` by
the request span open on the compiling thread, ``compiles`` / ``compileMs`` in
the innermost open span's data. An executable fetched from the persistent
cache is no compile: it counts under ``device.cacheFetches``.
"""
from __future__ import annotations

import os
import threading

__all__ = ["ensure_compilation_cache", "cache_dir"]

_ENV = "JAX_COMPILATION_CACHE_DIR"
_done = False
_lock = threading.Lock()

# JAX times `compile_or_get_cached` as a whole under the first name, and a
# hit in the persistent cache under the second, inside it and before it ends
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_FETCH_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_thread = threading.local()


def _on_duration(event: str, secs: float, **_kw) -> None:
    from delta_tpu.utils import telemetry

    if event == _FETCH_EVENT:
        _thread.fetched = True
        telemetry.bump_counter("device.cacheFetches")
        return
    if event != _COMPILE_EVENT:
        return
    if getattr(_thread, "fetched", False):
        _thread.fetched = False  # the fetch's own enclosing event
        return
    telemetry.bump_counter("device.compiles")
    telemetry.bump_counter("device.compileUs", int(secs * 1e6))
    for span in telemetry.open_spans():  # outermost first: the request
        if span.op_type == "delta.scan":
            telemetry.bump_counter("scan.device.compiles")
            break
        if span.op_type == "delta.dml.merge":
            telemetry.bump_counter("merge.device.compiles")
            break
    telemetry.add_span_counts(compiles=1, compileMs=round(secs * 1e3, 3))


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
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _done = True
