"""Columnar expression evaluation over Arrow tables (host data plane).

The reference evaluates predicates/projections row-at-a-time inside Spark
executors (e.g. ``MergeIntoCommand.scala:702-752``, codegen'd invariant checks
``constraints/CheckDeltaInvariant.scala``). Here the host data plane is Arrow:
expressions compile to ``pyarrow.compute`` kernel calls (Arrow's C++ vectorized
kernels — the native-performance role the JVM plays in the reference), with a
row-at-a-time fallback through :meth:`Expression.eval` for the long tail of
semantics (permissive casts, functions Arrow lacks).

NULL semantics match Spark SQL: comparisons with NULL are NULL, AND/OR are
Kleene, a predicate filter keeps only rows that are exactly TRUE.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

import pyarrow as pa
import pyarrow.compute as pc

from delta_tpu.expr import ir
from delta_tpu.schema.types import (
    ArrayType,
    BinaryType,
    BooleanType,
    ByteType,
    DataType,
    DateType,
    DecimalType,
    DoubleType,
    FloatType,
    IntegerType,
    MapType,
    LongType,
    ShortType,
    StringType,
    StructType,
    TimestampType,
)
from delta_tpu.utils.errors import DeltaAnalysisError
from delta_tpu.utils import errors

__all__ = ["evaluate", "filter_table", "boolean_mask", "project", "arrow_type_for"]


def arrow_type_for(dt: DataType) -> pa.DataType:
    """Map our schema types to Arrow types (Parquet physical layout)."""
    if isinstance(dt, BooleanType):
        return pa.bool_()
    if isinstance(dt, ByteType):
        return pa.int8()
    if isinstance(dt, ShortType):
        return pa.int16()
    if isinstance(dt, IntegerType):
        return pa.int32()
    if isinstance(dt, LongType):
        return pa.int64()
    if isinstance(dt, FloatType):
        return pa.float32()
    if isinstance(dt, DoubleType):
        return pa.float64()
    if isinstance(dt, StringType):
        return pa.string()
    if isinstance(dt, DateType):
        return pa.date32()
    if isinstance(dt, TimestampType):
        return pa.timestamp("us")
    if isinstance(dt, DecimalType):
        return pa.decimal128(dt.precision, dt.scale)
    if isinstance(dt, StructType):
        return pa.struct([pa.field(f.name, arrow_type_for(f.data_type), f.nullable) for f in dt.fields])
    if isinstance(dt, BinaryType):
        return pa.binary()
    if isinstance(dt, ArrayType):
        return pa.list_(arrow_type_for(dt.element_type))
    if isinstance(dt, MapType):
        return pa.map_(arrow_type_for(dt.key_type), arrow_type_for(dt.value_type))
    raise errors.arrow_mapping_missing(dt.simple_string())


def _resolve_column(table: pa.Table, name: str) -> pa.ChunkedArray:
    if name in table.column_names:
        return table.column(name)
    lowered = name.lower()
    for c in table.column_names:
        if c.lower() == lowered:
            return table.column(c)
    raise errors.column_not_found_in_table(name, table.column_names)


def _as_array(v: Any, n: int) -> pa.ChunkedArray:
    if isinstance(v, pa.ChunkedArray):
        return v
    if isinstance(v, pa.Array):
        return pa.chunked_array([v])
    if isinstance(v, pa.Scalar):
        if not v.is_valid:
            return pa.chunked_array([pa.nulls(n)])
        return pa.chunked_array([pa.array([v.as_py()] * n, type=v.type)])
    return pa.chunked_array([pa.array([v] * n)])


def _row_fallback(expr: ir.Expression, table: pa.Table, rows=None) -> pa.ChunkedArray:
    """Exact-semantics fallback: row-at-a-time eval over python dicts."""
    if rows is None:
        rows = table.to_pylist()
    return pa.chunked_array([pa.array([expr.eval(r) for r in rows])]) if rows else pa.chunked_array(
        [pa.nulls(0)]
    )


def _numeric_coerce(l: Any, r: Any):
    """Arrow's kernels refuse string-vs-number and string-vs-temporal;
    mimic Spark's implicit cast of the string side."""
    lt = getattr(l, "type", None)
    rt = getattr(r, "type", None)
    if lt is not None and rt is not None:
        if pa.types.is_string(lt) and (pa.types.is_integer(rt) or pa.types.is_floating(rt)):
            return pc.cast(l, pa.float64(), safe=False), pc.cast(r, pa.float64(), safe=False)
        if pa.types.is_string(rt) and (pa.types.is_integer(lt) or pa.types.is_floating(lt)):
            return pc.cast(l, pa.float64(), safe=False), pc.cast(r, pa.float64(), safe=False)
        # ISO string literals against date/timestamp columns
        if pa.types.is_string(lt) and (pa.types.is_date(rt) or pa.types.is_timestamp(rt)):
            return pc.cast(l, rt), r
        if pa.types.is_string(rt) and (pa.types.is_date(lt) or pa.types.is_timestamp(lt)):
            return l, pc.cast(r, lt)
    return l, r


def _is_decimal(x: Any) -> bool:
    t = getattr(x, "type", None)
    return t is not None and pa.types.is_decimal(t)


class _Vectorizer:
    def __init__(self, table: pa.Table):
        self.table = table
        self.n = table.num_rows
        self._rows = None  # lazy to_pylist() cache for the fallback path

    def _fallback(self, e: ir.Expression):
        if self._rows is None:
            self._rows = self.table.to_pylist()
        return _row_fallback(e, self.table, self._rows)

    def visit(self, e: ir.Expression):
        m = getattr(self, "_v_" + type(e).__name__, None)
        if m is None:
            return self._fallback(e)
        try:
            return m(e)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError, pa.ArrowTypeError,
                UnicodeEncodeError):
            return self._fallback(e)

    # -- leaves -----------------------------------------------------------
    def _v_Column(self, e: ir.Column):
        return _resolve_column(self.table, e.name)

    def _v_Literal(self, e: ir.Literal):
        return pa.scalar(e.value)

    def _v_Alias(self, e: ir.Alias):
        return self.visit(e.child)

    # -- boolean ----------------------------------------------------------
    def _v_And(self, e: ir.And):
        return pc.and_kleene(*self._bool_pair(e))

    def _v_Or(self, e: ir.Or):
        return pc.or_kleene(*self._bool_pair(e))

    def _bool_pair(self, e):
        l = self.visit(e.left)
        r = self.visit(e.right)
        # and_kleene needs at least one array argument
        if isinstance(l, pa.Scalar) and isinstance(r, pa.Scalar):
            l = _as_array(l, self.n)
        return l, r

    def _v_Not(self, e: ir.Not):
        return pc.invert(self.visit(e.child))

    # -- comparisons ------------------------------------------------------
    def _cmp(self, e, fn):
        l, r = _numeric_coerce(self.visit(e.left), self.visit(e.right))
        return fn(l, r)

    def _compare(self, e, fn):
        """A decimal column against a literal written in decimal notation
        compares decimal with decimal (Arrow rescales exactly); the float
        the literal also carries would be ``0.06 + 0.01 < 0.07``."""
        l, r = self.visit(e.left), self.visit(e.right)
        if _is_decimal(l) and getattr(e.right, "exact", None) is not None:
            r = pa.scalar(e.right.exact)
        elif _is_decimal(r) and getattr(e.left, "exact", None) is not None:
            l = pa.scalar(e.left.exact)
        return fn(*_numeric_coerce(l, r))

    def _v_Eq(self, e):
        return self._compare(e, pc.equal)

    def _v_Ne(self, e):
        return self._compare(e, pc.not_equal)

    def _v_Lt(self, e):
        return self._compare(e, pc.less)

    def _v_Le(self, e):
        return self._compare(e, pc.less_equal)

    def _v_Gt(self, e):
        return self._compare(e, pc.greater)

    def _v_Ge(self, e):
        return self._compare(e, pc.greater_equal)

    def _v_NullSafeEq(self, e):
        l = _as_array(self.visit(e.left), self.n)
        r = _as_array(self.visit(e.right), self.n)
        eq = pc.equal(l, r)
        both_null = pc.and_(pc.is_null(l), pc.is_null(r))
        return pc.if_else(pc.is_null(eq), both_null, eq)

    def _v_In(self, e: ir.In):
        v = _as_array(self.visit(e.value), self.n)
        if not all(isinstance(o, ir.Literal) for o in e.options):
            return self._fallback(e)
        if _is_decimal(v) and any(o.exact is not None for o in e.options):
            return self._fallback(e)  # the row evaluator compares exactly
        opts = [o.value for o in e.options]
        has_null_opt = any(o is None for o in opts)
        vals = [o for o in opts if o is not None]
        found = pc.is_in(v, value_set=pa.array(vals, type=v.type) if vals else pa.nulls(0, v.type))
        if has_null_opt:
            # SQL IN: not-found with a NULL option is NULL, not FALSE
            found = pc.if_else(found, pa.scalar(True), pa.scalar(None, pa.bool_()))
        return pc.if_else(pc.is_null(v), pa.scalar(None, pa.bool_()), found)

    def _v_IsNull(self, e: ir.IsNull):
        return pc.is_null(_as_array(self.visit(e.child), self.n))

    def _v_IsNotNull(self, e: ir.IsNotNull):
        return pc.is_valid(_as_array(self.visit(e.child), self.n))

    # -- arithmetic ------------------------------------------------------
    def _v_Add(self, e):
        return self._cmp(e, pc.add)

    def _v_Sub(self, e):
        return self._cmp(e, pc.subtract)

    def _v_Mul(self, e):
        return self._cmp(e, pc.multiply)

    def _v_Div(self, e):
        l = self.visit(e.left)
        r = _as_array(self.visit(e.right), self.n)
        # Spark (ansi off): x / 0 is NULL; arrow raises / returns inf
        r = pc.if_else(pc.equal(r, pa.scalar(0).cast(r.type)), pa.scalar(None, r.type), r)
        lt = l.type
        if pa.types.is_integer(lt) and pa.types.is_integer(r.type):
            return pc.divide(pc.cast(l, pa.float64()), pc.cast(r, pa.float64()))
        return pc.divide(l, r)

    def _v_Neg(self, e: ir.Neg):
        return pc.negate(self.visit(e.child))

    def _v_Cast(self, e: ir.Cast):
        child = self.visit(e.child)
        target = arrow_type_for(e.data_type)
        try:
            return pc.cast(child, target, safe=False)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError, pa.ArrowTypeError):
            return self._fallback(e)

    # -- strings ----------------------------------------------------------
    def _v_Like(self, e: ir.Like):
        if not isinstance(e.right, ir.Literal):
            return self._fallback(e)
        return pc.match_like(self.visit(e.left), e.right.value)

    def _v_StartsWith(self, e: ir.StartsWith):
        if not isinstance(e.right, ir.Literal):
            return self._fallback(e)
        return pc.starts_with(self.visit(e.left), pattern=e.right.value)

    def _v_Coalesce(self, e: ir.Coalesce):
        return pc.coalesce(*[_as_array(self.visit(c), self.n) for c in e.children])

    def _v_CaseWhen(self, e: ir.CaseWhen):
        result = _as_array(self.visit(e.children[-1]), self.n)
        for i in reversed(range(e.n_branches)):
            cond = _as_array(self.visit(e.children[2 * i]), self.n)
            val = _as_array(self.visit(e.children[2 * i + 1]), self.n)
            # CASE matches only when the condition is exactly TRUE
            cond = pc.fill_null(cond, False)
            result = pc.if_else(cond, val, result)
        return result

    _ARROW_FUNCS = {
        "abs": pc.abs,
        "length": pc.utf8_length,
        "lower": pc.utf8_lower,
        "upper": pc.utf8_upper,
        "trim": pc.utf8_trim_whitespace,
        "floor": pc.floor,
        "ceil": pc.ceil,
        "year": pc.year,
        "month": pc.month,
        "day": pc.day,
        "exp": pc.exp,
        "log": lambda x: _ln_null(x),
        "sqrt": lambda x: _sqrt_null(x),
        "pow": lambda x, y: _pow_f64(x, y),
        "power": lambda x, y: _pow_f64(x, y),
    }

    def _v_Func(self, e: ir.Func):
        # concat / round / substring need special argument handling; the
        # rest map 1:1 onto an Arrow kernel. Anything else (or non-literal
        # substring/round arguments) keeps the exact row-eval semantics.
        if e.name == "concat":
            args = [self.visit(a) for a in e.children]
            types = [getattr(a, "type", None) for a in args]
            # stringified-operand semantics match Arrow's cast only for
            # strings and integers (floats/bools render differently than
            # str()) — anything else keeps the exact row semantics
            if all(t is not None and (pa.types.is_string(t) or pa.types.is_integer(t))
                   for t in types):
                args = [
                    a if pa.types.is_string(a.type) else pc.cast(a, pa.string())
                    for a in args
                ]
                # any NULL argument → NULL (binary_join's default emit_null)
                return pc.binary_join_element_wise(*args, "")
            return self._fallback(e)
        if e.name == "hour":
            arg = self.visit(e.children[0])
            t = getattr(arg, "type", None)
            if t is not None and pa.types.is_timestamp(t):
                return pc.hour(arg)
            return self._fallback(e)  # int-µs inputs keep row semantics
        def _int_literals(args):
            return all(
                isinstance(a, ir.Literal) and isinstance(a.value, int)
                and not isinstance(a.value, bool)
                for a in args
            )

        if e.name == "round" and (
            len(e.children) == 1
            or (_int_literals(e.children[1:])
                and e.children[1].value == 0)
        ):
            # only ndigits=0 vectorizes: integer boundaries are binary-exact
            # so Arrow's half_to_even agrees with Python's round(); for
            # ndigits>0 Arrow rounds the binary-scaled value (round(2.675,2)
            # → 2.68) while Python is correctly rounded (→ 2.67) — keep the
            # exact row semantics there
            return pc.round(
                self.visit(e.children[0]), ndigits=0,
                round_mode="half_to_even",
            )
        if (e.name in ("substring", "substr") and _int_literals(e.children[1:])
                and int(e.children[1].value) >= 0):
            # positive positions only: negative-position window semantics
            # (prefix consumed before the string) keep the exact row path
            s = self.visit(e.children[0])
            pos = int(e.children[1].value)
            start = max(pos - 1, 0)
            if len(e.children) > 2:
                stop = start + max(int(e.children[2].value), 0)
                return pc.utf8_slice_codeunits(s, start=start, stop=stop)
            return pc.utf8_slice_codeunits(s, start=start)
        if e.name in ("minute", "second"):
            arg = self.visit(e.children[0])
            t = getattr(arg, "type", None)
            if t is not None and pa.types.is_timestamp(t):
                return (pc.minute if e.name == "minute" else pc.second)(arg)
            return self._fallback(e)  # int-µs inputs keep row semantics
        if e.name == "to_date":
            arg = self.visit(e.children[0])
            t = getattr(arg, "type", None)
            if t is None or not pa.types.is_string(t):
                return self._fallback(e)
            try:
                if len(e.children) == 1:
                    # row semantics parse the first 10 chars as ISO; Arrow's
                    # date32 cast accepts exactly that for ISO strings, but
                    # errors (not NULLs) bad input — fall back then
                    return pc.cast(
                        pc.utf8_slice_codeunits(arg, start=0, stop=10),
                        pa.date32(),
                    )
                if isinstance(e.children[1], ir.Literal):
                    fmt = ir.java_fmt_to_strftime(e.children[1].value)
                    ts = pc.strptime(arg, format=fmt, unit="s", error_is_null=True)
                    return pc.cast(ts, pa.date32())
            except Exception:
                return self._fallback(e)
            return self._fallback(e)
        if e.name in ("date_add", "date_sub"):
            d = self.visit(e.children[0])
            n = self.visit(e.children[1])
            t = getattr(d, "type", None)
            if t is None or not pa.types.is_date(t):
                return self._fallback(e)
            days = pc.cast(pc.cast(d, pa.date32()), pa.int32())
            n32 = pc.cast(_as_array(n, self.n), pa.int32())
            out = (pc.add if e.name == "date_add" else pc.subtract)(days, n32)
            return pc.cast(out, pa.date32())
        if e.name == "datediff":
            a = self.visit(e.children[0])
            b = self.visit(e.children[1])
            ta, tb = getattr(a, "type", None), getattr(b, "type", None)
            if (ta is None or tb is None or not pa.types.is_date(ta)
                    or not pa.types.is_date(tb)):
                return self._fallback(e)
            return pc.subtract(pc.cast(pc.cast(a, pa.date32()), pa.int32()),
                               pc.cast(pc.cast(b, pa.date32()), pa.int32()))
        if e.name in ("lpad", "rpad"):
            tail = e.children[1:]
            if not (isinstance(tail[0], ir.Literal)
                    and isinstance(tail[0].value, int)):
                return self._fallback(e)
            pad = " "
            if len(tail) > 1:
                if not (isinstance(tail[1], ir.Literal)
                        and isinstance(tail[1].value, str) and tail[1].value):
                    return self._fallback(e)
                pad = tail[1].value
            n = int(tail[0].value)
            if n <= 0 or len(pad) != 1:
                return self._fallback(e)  # multi-char pad: row semantics
            s = self.visit(e.children[0])
            t = getattr(s, "type", None)
            if t is None or not pa.types.is_string(t):
                return self._fallback(e)
            padded = (pc.utf8_lpad if e.name == "lpad" else pc.utf8_rpad)(
                s, width=n, padding=pad
            )
            # Spark truncates to the target width when the input is longer
            return pc.utf8_slice_codeunits(padded, start=0, stop=n)
        if e.name == "log" and len(e.children) == 2:
            base = pc.cast(_as_array(self.visit(e.children[0]), self.n), pa.float64())
            x = pc.cast(_as_array(self.visit(e.children[1]), self.n), pa.float64())
            ok = pc.and_(pc.and_(pc.greater(x, 0.0), pc.greater(base, 0.0)),
                         pc.not_equal(base, 1.0))
            return pc.if_else(pc.fill_null(ok, False), pc.logb(x, base),
                              pa.scalar(None, pa.float64()))
        fn = self._ARROW_FUNCS.get(e.name)
        if fn is None:
            return self._fallback(e)
        args = [self.visit(a) for a in e.children]
        return fn(*args)


# domain-guarded math: the row evaluator yields NULL outside the domain
# (Spark semantics); raw Arrow kernels would yield NaN/-inf — mask them
def _ln_null(x):
    xf = pc.cast(x, pa.float64())
    return pc.if_else(pc.fill_null(pc.greater(xf, 0.0), False),
                      pc.ln(xf), pa.scalar(None, pa.float64()))


def _sqrt_null(x):
    xf = pc.cast(x, pa.float64())
    return pc.if_else(pc.fill_null(pc.greater_equal(xf, 0.0), False),
                      pc.sqrt(xf), pa.scalar(None, pa.float64()))


def _pow_f64(x, y):
    return pc.power(pc.cast(x, pa.float64()), pc.cast(y, pa.float64()))


def evaluate(expr: ir.Expression, table: pa.Table) -> pa.ChunkedArray:
    """Evaluate ``expr`` over every row of ``table``; result aligned by row."""
    v = _Vectorizer(table)
    return _as_array(v.visit(expr), table.num_rows)


def filter_table(table: pa.Table, expr: Optional[ir.Expression]) -> pa.Table:
    """Keep rows where ``expr`` is exactly TRUE (NULL drops, like SQL WHERE)."""
    if expr is None or table.num_rows == 0:
        return table
    return table.filter(boolean_mask(expr, table))


def boolean_mask(expr: ir.Expression, table: pa.Table):
    """Evaluate a predicate to a null-free boolean array (NULL → False)."""
    return pc.fill_null(pc.cast(evaluate(expr, table), pa.bool_()), False)


def project(table: pa.Table, exprs: Dict[str, ir.Expression]) -> pa.Table:
    """SELECT exprs: build a new table with one column per (name, expression)."""
    cols: List[pa.ChunkedArray] = []
    names: List[str] = []
    for name, e in exprs.items():
        arr = evaluate(e, table)
        cols.append(arr)
        names.append(name)
    return pa.table(cols, names=names)
