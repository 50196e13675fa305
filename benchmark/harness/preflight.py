"""Refuse to run without the chips the cell asks for, and say what was found."""
from __future__ import annotations

import json
import os
import sys
from typing import Any, Dict

PEAKS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "peaks.json")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell needs."""


def log(*parts: Any) -> None:
    """Everything but the result goes to standard error."""
    print(*parts, file=sys.stderr, flush=True)


def cache_entries(path: str) -> int:
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0


def peak_table() -> Dict[str, Dict[str, Any]]:
    with open(PEAKS, encoding="utf-8") as f:
        return json.load(f)["device_kinds"]


def preflight(chips: int, need_tpu: bool = True) -> Dict[str, Any]:
    """The device as JAX reports it, the compile cache's directory and its
    entries. ``need_tpu=False`` is for the tests, which drive the rest of a
    run on whatever platform they have."""
    import jax

    from delta_tpu.utils import jaxcache

    devices = jax.devices()
    platform = devices[0].platform
    if need_tpu and (platform != "tpu" or len(devices) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX found "
                     f"{len(devices)} device(s) of platform {platform!r}")
    kind = devices[0].device_kind
    if need_tpu and kind not in peak_table():
        raise NoChip(f"device kind {kind!r} is not in {PEAKS}: add its "
                     f"published peaks with their source")
    jaxcache.ensure_compilation_cache()
    # keep every program, also one that compiled in under JAX's default of
    # a second: the residual mask is one small program for each query, and
    # without this each run of each check would compile them all again
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cache_dir = jax.config.jax_compilation_cache_dir
    facts = {
        "device": {"platform": platform, "kind": kind, "count": len(devices)},
        "versions": {"jax": jax.__version__},
        "cache": {"dir": cache_dir, "entries": cache_entries(cache_dir)},
    }
    log("preflight:", json.dumps(facts))
    return facts


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices())


def host_memory() -> str:
    """This process's resident and peak resident memory, for the log."""
    out = {}
    try:
        with open("/proc/self/status", encoding="ascii") as f:
            for row in f:
                if row.startswith(("VmRSS", "VmHWM")):
                    k, v = row.split(":")
                    out[k] = round(int(v.split()[0]) / 1048576, 2)
    except OSError:
        pass
    return f"host memory GiB {out}"


def release_memory() -> None:
    """Hand freed memory back to the system: the generator and the
    comparison work in arrays of tens of megabytes, which the allocator would
    keep, and the machine has 40 GiB for the table, its reference and the
    program."""
    import ctypes

    import pyarrow as pa

    pa.default_memory_pool().release_unused()
    try:
        ctypes.CDLL("libc.so.6").malloc_trim(0)
    except OSError:
        pass
