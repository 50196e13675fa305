"""Ungrouped aggregates answered where the data lives: a fused
filter-and-sum kernel over the scan column cache's resident lanes.

``SELECT sum(a * b) FROM t WHERE lo <= c AND c < hi`` through the scan path
downloads a byte a row of mask and decodes the survivors' columns from
Parquet to add them up on the host. When every referenced column has a lane
(`ops/column_cache`: integers, dates, ``decimal(p <= 18)`` as unscaled
int64), the whole query is one pass over HBM and its answer a few bytes:
:func:`device_aggregate` plans the files as a scan does, loads the lanes it
misses, and runs :func:`_aggregate_kernel`'s program once a file, carrying
the partial sums on the device; one fetch brings them back.

Exact by construction: the conjunction of range predicates is compared in
integers (literals scaled by `jaxeval.compile_residual`, the bounds handed
to the program as **operands**, so one program a lane shape serves every
literal), validity and deletion vectors are honoured, and ``sum`` /
``count`` / ``min`` / ``max`` accumulate in int64 after a bound from the
lanes' own extremes times the row counts has proved that nothing can
overflow. The result is the Arrow table the host route
(`sql/parser._run_aggregate`) returns, type included: each type is read off
the host's own kernels.

The route is taken from what can be observed, by no conf of its own
(``delta.tpu.read.deviceResidual.mode=off`` turns the column cache, and so
this, off): the shape of the select list and of the predicate, the columns'
types, the lanes' bytes against the cache's budget, the overflow bound. A
decline says why in the span's ``route`` (``host:<reason>``) and the host
route runs.
"""
from __future__ import annotations

import functools
from decimal import Decimal
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from delta_tpu.expr import ir, jaxeval
from delta_tpu.expr.jaxeval import NotDeviceCompilable
from delta_tpu.ops import column_cache
from delta_tpu.parallel import link
from delta_tpu.utils import telemetry
from delta_tpu.utils.config import conf
from delta_tpu.utils.jaxcache import ensure_compilation_cache
from delta_tpu.utils.jaxcompat import enable_x64

__all__ = ["device_aggregate", "AggregateSpec"]

_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min
#: slots of the carry a spec owns: sum, count, min, max
_SLOTS = 4
#: a float64 holds every integer below this, so a mean of integers computed
#: from an exact sum is the host's, whatever order it adds in
_EXACT_F64 = 2 ** 53


class AggregateSpec(NamedTuple):
    func: str               # count | sum | avg | min | max
    cols: Tuple[str, ...]   # () is COUNT(*); (a,) a column; (a, b) a * b


class _Decline(Exception):
    """The host route answers; ``args[0]`` is the reason."""


class _FileLanes(NamedTuple):
    """One file's lanes as the kernel takes them. The arrays are held here,
    so an eviction between the load and the launch frees nothing in use."""

    add: Any
    env: Dict[str, Tuple[Any, Any]]   # column -> (values, valid)
    rows: int                         # physical rows; the lanes are padded
    extremes: Dict[str, int]          # column -> largest magnitude it holds


# -- the kernel ----------------------------------------------------------------


@functools.lru_cache(maxsize=64)
def _aggregate_kernel(preds: Tuple[str, ...], specs: Tuple[AggregateSpec, ...]):
    """One program for a select list and the columns its predicate ranges
    over; XLA keys it further on the lane shape and on whether a deletion
    vector's ``keep`` comes with the file. Never on a literal."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    def filter_aggregate(lanes, bounds, n, keep, carry):
        # lanes: {column: (values, valid)} padded to one pow2 length;
        # bounds: int64[len(preds), 2], inclusive; n: the file's rows
        cap = next(iter(lanes.values()))[0].shape[0]
        live = jnp.arange(cap, dtype=jnp.int32) < n
        if keep is not None:
            live = live & keep
        for i, c in enumerate(preds):
            v, ok = lanes[c]
            v = v.astype(jnp.int64)
            live = live & ok & (v >= bounds[i, 0]) & (v <= bounds[i, 1])
        out = []
        for k, spec in enumerate(specs):
            m, x = live, None
            for c in spec.cols:
                v, ok = lanes[c]
                m = m & ok
                x = v.astype(jnp.int64) if x is None else x * v.astype(jnp.int64)
            base = _SLOTS * k
            # a file holds fewer than 2^31 rows: count in the native width
            count = jnp.sum(m.astype(jnp.int32)).astype(jnp.int64)
            total = carry[base]
            low, high = carry[base + 2], carry[base + 3]
            if spec.func in ("sum", "avg"):
                total = total + jnp.sum(jnp.where(m, x, 0))
            elif spec.func == "min":
                low = jnp.minimum(low, jnp.min(jnp.where(m, x, _I64_MAX)))
            elif spec.func == "max":
                high = jnp.maximum(high, jnp.max(jnp.where(m, x, _I64_MIN)))
            out += [total, carry[base + 1] + count, low, high]
        return jnp.stack(out)

    return jax.jit(filter_aggregate)


@functools.lru_cache(maxsize=1024)
def _rows_on_device(n: int):
    """A file's row count as a device scalar, kept: handed to the program
    as a host number it is one blocking upload a launch (0.29 ms of a
    0.53 ms launch on a v5e's host, 15 launches a query; PERF.md, PR 27)."""
    return link.to_device(np.int32(n))


def _empty_carry(specs: Sequence[AggregateSpec]) -> np.ndarray:
    return np.array([0, 0, _I64_MAX, _I64_MIN] * len(specs), np.int64)


# -- what the select list and the predicate have to look like -------------------


def _specs(parsed_items) -> Tuple[AggregateSpec, ...]:
    out = []
    for kind, payload, _alias in parsed_items:
        if kind != "agg":
            raise _Decline("shape")
        func, inner = payload
        while isinstance(inner, ir.Alias):
            inner = inner.child
        if inner is None:
            cols: Tuple[str, ...] = ()
        elif isinstance(inner, ir.Column):
            cols = (inner.name.lower(),)
        elif isinstance(inner, ir.Mul) and isinstance(inner.left, ir.Column) \
                and isinstance(inner.right, ir.Column):
            cols = (inner.left.name.lower(), inner.right.name.lower())
        else:
            raise _Decline("shape")
        out.append(AggregateSpec(func, cols))
    return tuple(out)


def _arrow_types(specs, fields, partition_columns) -> Dict[str, Any]:
    """Arrow type of every aggregated column, as the scan would decode it;
    declines on a column without an integer lane or a partition column
    (no file stores it). ``fields``: `synthesis.schema_types`."""
    import pyarrow as pa

    from delta_tpu.expr.vectorized import arrow_type_for

    parts = {c.lower() for c in partition_columns}
    out = {}
    for spec in specs:
        for c in spec.cols:
            if c not in fields or c in parts:
                raise _Decline("type")
            t = arrow_type_for(fields[c])
            summable = pa.types.is_integer(t) or (
                pa.types.is_decimal128(t)
                and t.precision <= jaxeval.DECIMAL_LANE_PRECISION)
            if not (summable or (pa.types.is_date32(t) and len(spec.cols) == 1
                                 and spec.func in ("count", "min", "max"))):
                raise _Decline("type")
            out[c] = t
        if len(spec.cols) == 2 and pa.types.is_integer(out[spec.cols[0]]) \
                != pa.types.is_integer(out[spec.cols[1]]):
            raise _Decline("type")  # integer times decimal: the host's to type
    return out


def _ranges(predicate: Optional[ir.Expression], fields, partition_columns):
    """The residual as inclusive int64 ranges over lanes: ``(columns,
    bounds)``. Every conjunct has to be a compare of one lane with a
    literal, in the units `jaxeval.compile_residual` lowers it to."""
    if predicate is None:
        return (), np.zeros((0, 2), np.int64)
    try:
        plan = jaxeval.compile_residual(predicate, fields, partition_columns)
    except NotDeviceCompilable:
        raise _Decline("predicate") from None
    if plan.part_refs or plan.str_binds:
        raise _Decline("predicate")
    box: Dict[str, List[int]] = {}
    for conj in ir.split_conjuncts(plan.expr):
        col, lit = getattr(conj, "left", None), getattr(conj, "right", None)
        if type(conj) not in (ir.Eq, ir.Lt, ir.Le, ir.Gt, ir.Ge) \
                or not isinstance(col, ir.Column) \
                or not isinstance(lit, ir.Literal) \
                or isinstance(lit.value, bool) or not isinstance(lit.value, int):
            raise _Decline("predicate")
        bound = box.setdefault(col.name, [_I64_MIN, _I64_MAX])
        if type(conj) in (ir.Eq, ir.Ge, ir.Gt):
            bound[0] = max(bound[0], lit.value + (type(conj) is ir.Gt))
        if type(conj) in (ir.Eq, ir.Le, ir.Lt):
            bound[1] = min(bound[1], lit.value - (type(conj) is ir.Lt))
    cols = tuple(sorted(box))
    # a bound beyond int64 holds for no row: the empty range
    bounds = [box[c] if box[c][0] <= _I64_MAX and box[c][1] >= _I64_MIN
              else [1, 0] for c in cols]
    return cols, np.array(bounds, np.int64).reshape(-1, 2)


# -- the bounds that make int64 exact --------------------------------------------


def _magnitude(extremes: Dict[str, int], cols: Sequence[str]) -> int:
    out = 1
    for c in cols:
        out *= extremes[c]
    return out


@functools.lru_cache(maxsize=256)
def _host_types(func: str, column_types: Tuple[Any, ...]):
    """``(type of the aggregated expression, type of the aggregate)`` as the
    host route types them, read off its own kernels over empty arrays."""
    import pyarrow as pa
    import pyarrow.compute as pc

    expr = pa.array([], column_types[0])
    for t in column_types[1:]:
        expr = pc.multiply(expr, pa.array([], t))
    kern = {"sum": pc.sum, "avg": pc.mean, "min": pc.min, "max": pc.max,
            "count": pc.count}
    return expr.type, kern[func](expr).type


def _check_overflow(specs, types, per_file) -> None:
    """Decline unless every sum stays inside int64 whatever rows survive,
    and every product inside the type the host multiplies in (the host's
    int32 product wraps where int64 does not: the routes have to agree)."""
    import pyarrow as pa

    for spec in specs:
        if not spec.cols or not per_file:
            continue
        expr_type, _out = _host_types(spec.func,
                                      tuple(types[c] for c in spec.cols))
        width = expr_type.bit_width if pa.types.is_integer(expr_type) else 64
        if max(_magnitude(f.extremes, spec.cols)
               for f in per_file) >= 2 ** (width - 1):
            raise _Decline("overflow")
        if spec.func in ("sum", "avg"):
            total = sum(_magnitude(f.extremes, spec.cols) * f.rows
                        for f in per_file)
            limit = _EXACT_F64 if spec.func == "avg" \
                and pa.types.is_integer(expr_type) else _I64_MAX
            if total > limit:
                raise _Decline("overflow")


# -- the answer, typed as the host types it ---------------------------------------


def _scalar(spec: AggregateSpec, slots, types):
    """One select item as a one-row Arrow array of the host route's type."""
    import pyarrow as pa

    total, count, low, high = (int(x) for x in slots)
    if spec.func == "count":
        return pa.array([count], pa.int64())
    _expr, out_type = _host_types(spec.func, tuple(types[c] for c in spec.cols))
    if count == 0:
        return pa.array([None], out_type)
    if spec.func == "avg" and pa.types.is_floating(out_type):
        return pa.array([total / count], out_type)
    if spec.func == "avg":  # a decimal mean rounds half away from zero
        q, r = divmod(abs(total), count)
        units = (q + (2 * r >= count)) * (1 if total >= 0 else -1)
    else:
        units = {"sum": total, "min": low, "max": high}[spec.func]
    if pa.types.is_decimal(out_type):
        return pa.array([Decimal(units).scaleb(-out_type.scale)], out_type)
    if pa.types.is_date(out_type):
        return pa.array([units], pa.int32()).cast(out_type)
    return pa.array([units], pa.int64()).cast(out_type)


# -- the route -------------------------------------------------------------------


def _lane_bytes(files, columns, fields) -> int:
    """What the lanes of ``files`` take on the device, padded as
    `column_cache.ResidentColumn` pads them; rows from the log's statistics
    (a file without them is reckoned by its size, 64 bytes a row as the
    residual router does)."""
    import pyarrow as pa

    from delta_tpu.expr.vectorized import arrow_type_for

    # a date lane is int32, every other int64; a byte of validity each
    width = sum(5 if pa.types.is_date(arrow_type_for(fields[c])) else 9
                for c in columns)
    total = 0
    for f in files:
        rows = f.num_logical_records
        if rows is None:
            rows = max((f.size or 0) // 64, 1024)
        if f.deletion_vector is not None:
            rows += int(f.deletion_vector.get("cardinality", 0))
        total += column_cache._next_pow2(max(rows, 1), floor=64) * width
    return total


def _keep_mask(add, data_path: str, cap: int):
    """The file's deletion vector as a row mask on the device."""
    from delta_tpu.protocol.deletion_vectors import (DeletionVectorDescriptor,
                                                     read_deletion_vector)

    keep = np.ones(cap, bool)
    keep[read_deletion_vector(
        DeletionVectorDescriptor.from_dict(add.deletion_vector), data_path)] = False
    return link.to_device(keep)


def device_aggregate(snapshot, filters: Sequence[ir.Expression], parsed_items):
    """The select list ``parsed_items`` (`sql/parser._select`) over the rows
    of ``snapshot`` that ``filters`` hold for, as a one-row Arrow table
    computed on the device; or None, with the reason on the span, when the
    host route has to answer."""
    import pyarrow as pa

    with telemetry.record_operation("delta.scan.deviceAggregate") as ev:
        try:
            columns = _device_aggregate(snapshot, filters, parsed_items, ev)
        except _Decline as d:
            ev.data["route"] = f"host:{d.args[0]}"
        except Exception as e:  # noqa: BLE001
            # as the residual mask: the device route never fails a query the
            # host can answer, unless the mode pins the device
            if str(conf.get("delta.tpu.read.deviceResidual.mode",
                            "auto")).lower() == "force":
                raise
            ev.data.update(route="host:error",
                           deviceError=telemetry.exc_text(e))
        else:
            ev.data["route"] = "device"
            telemetry.bump_counter("scan.aggregate.device")
            return pa.Table.from_arrays(
                columns, names=[alias for _kind, _payload, alias in parsed_items])
        telemetry.bump_counter("scan.aggregate.declined")
        return None


def _device_aggregate(snapshot, filters, parsed_items, ev):
    from delta_tpu.ops import pruning

    if not column_cache.column_cache_enabled():
        raise _Decline("off")
    from delta_tpu.expr.synthesis import schema_types

    metadata = snapshot.metadata
    fields, parts = schema_types(metadata), metadata.partition_columns
    specs = _specs(parsed_items)
    types = _arrow_types(specs, fields, parts)
    scan = pruning.files_for_scan(snapshot, list(filters))
    preds, bounds = _ranges(
        ir.and_all(scan.data_filters) if scan.data_filters else None,
        fields, parts)
    need = sorted(set(preds) | {c for s in specs for c in s.cols})
    if not need:
        raise _Decline("shape")  # COUNT(*) of a whole table: the log's to answer
    log_path = snapshot.delta_log.log_path
    data_path = snapshot.delta_log.data_path
    cache = column_cache.ColumnCache.instance()
    # resident, or what is missing fits the budget beside what is
    held, cold = 0, []
    for add in scan.files:
        lanes = [cache.get(log_path, add.path, c) for c in need]
        if all(e is not None for e in lanes):
            held += sum(e.nbytes for e in lanes)
        else:
            cold.append(add)
    if cold and held + _lane_bytes(cold, need, fields) > column_cache.lane_budget():
        raise _Decline("budget")
    counters = {"hits": 0, "misses": 0, "coldBytes": 0}
    per_file: List[_FileLanes] = []
    for add in scan.files:
        lanes = column_cache._ensure_lanes(cache, log_path, data_path, add, need,
                                           cache.epoch(log_path), counters)
        if lanes is None or any(e.dict_codes is not None for e in lanes.values()):
            raise _Decline("type")
        per_file.append(_FileLanes(
            add, {c: (e.values, e.valid) for c, e in lanes.items()},
            max(e.n for e in lanes.values()),
            {c: max(abs(e.lo), abs(e.hi)) for c, e in lanes.items()}))
    for name in ("hits", "misses"):
        if counters[name]:
            telemetry.bump_counter(f"columnCache.{name}", counters[name])
    _check_overflow(specs, types, per_file)
    rows = sum(f.rows for f in per_file)
    ev.data.update(files=len(per_file), rows=rows, hits=counters["hits"],
                   misses=counters["misses"])
    carry = _empty_carry(specs)
    if per_file:
        with telemetry.record_operation("delta.columnCache.aggregate",
                                        {"rows": rows}), enable_x64():
            kernel = _aggregate_kernel(preds, specs)
            dev_bounds, carry = link.to_device(bounds), link.to_device(carry)
            for f in per_file:
                cap = next(iter(f.env.values()))[0].shape[0]
                keep = None if f.add.deletion_vector is None \
                    else _keep_mask(f.add, data_path, cap)
                carry = kernel(f.env, dev_bounds, _rows_on_device(f.rows), keep,
                               carry)
            carry = link.to_host(carry)
    return [_scalar(spec, carry[_SLOTS * k:_SLOTS * (k + 1)], types)
            for k, spec in enumerate(specs)]
