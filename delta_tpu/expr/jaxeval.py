"""Compile expressions to ``jnp`` ops over device-resident columns.

TPU columns are SoA pairs ``(values, valid)``: a numeric/bool lane array plus a
boolean validity mask (NULL = invalid lane). Strings never reach the device as
bytes — the host dictionary-encodes them (``ops/state_export.py``) and the
device compares int32 codes; that keeps everything MXU/VPU-friendly and
static-shaped.

Three-valued logic is carried explicitly through the mask, matching
:mod:`delta_tpu.expr.ir` row semantics (Kleene AND/OR, NULL-propagating
comparisons). Replaces the role Catalyst codegen plays in the reference
(``constraints/CheckDeltaInvariant.scala``, ``MergeIntoCommand.scala:702-752``)
with XLA-fused vector code.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from delta_tpu.expr import ir
from delta_tpu.utils.errors import DeltaAnalysisError
from delta_tpu.utils.jaxcache import ensure_compilation_cache

__all__ = ["DeviceColumn", "compile_expr", "NotDeviceCompilable",
           "ResidualPlan", "compile_residual", "STR_CODE_ABSENT",
           "f64_order_key", "DECIMAL_LANE_PRECISION", "decimal_literal_units"]


class NotDeviceCompilable(DeltaAnalysisError):
    """Raised when an expression cannot be lowered to device ops
    (caller falls back to the host vectorized/row evaluators)."""


class DeviceColumn(NamedTuple):
    """One SoA column: lane values + validity mask (True = non-NULL)."""

    values: Any  # jnp array
    valid: Any  # jnp bool array

    @staticmethod
    def of(values, valid=None) -> "DeviceColumn":
        values = jnp.asarray(values)
        if valid is None:
            valid = jnp.ones(values.shape, dtype=bool)
        return DeviceColumn(values, jnp.asarray(valid, dtype=bool))


Env = Dict[str, DeviceColumn]
_Compiled = Callable[[Env], DeviceColumn]


def _lit(e: ir.Literal) -> _Compiled:
    v = e.value
    if v is None:
        return lambda env: DeviceColumn(jnp.zeros((), jnp.float32), jnp.zeros((), bool))
    # Keep literals as numpy until trace time: wide dtypes (int64/float64)
    # only take effect inside the kernel's jax.enable_x64() scope.
    if isinstance(v, bool):
        arr = np.asarray(v)
    elif isinstance(v, int):
        if not (-(2**63) <= v < 2**63):
            raise NotDeviceCompilable(f"integer literal {v} exceeds int64")
        arr = np.asarray(v, np.int64 if not (-(2**31) <= v < 2**31) else np.int32)
    elif isinstance(v, float):
        arr = np.asarray(v, np.float64)
    else:
        raise NotDeviceCompilable(f"literal {v!r} has no device representation")
    return lambda env: DeviceColumn(jnp.asarray(arr), jnp.ones((), bool))


def _col(e: ir.Column) -> _Compiled:
    name = e.name

    def run(env: Env) -> DeviceColumn:
        c = env.get(name) or env.get(name.lower())
        if c is None:
            raise NotDeviceCompilable(f"column {name!r} not bound in device env")
        return c

    return run


def _binop(e, fn) -> _Compiled:
    lf, rf = compile_expr(e.left), compile_expr(e.right)

    def run(env: Env) -> DeviceColumn:
        l, r = lf(env), rf(env)
        return DeviceColumn(fn(l.values, r.values), l.valid & r.valid)

    return run


def _kleene_and(e: ir.And) -> _Compiled:
    lf, rf = compile_expr(e.left), compile_expr(e.right)

    def run(env: Env) -> DeviceColumn:
        l, r = lf(env), rf(env)
        lt = l.values.astype(bool) & l.valid  # definitely TRUE
        rt = r.values.astype(bool) & r.valid
        lF = ~l.values.astype(bool) & l.valid  # definitely FALSE
        rF = ~r.values.astype(bool) & r.valid
        value = lt & rt
        valid = value | lF | rF
        return DeviceColumn(value, valid)

    return run


def _kleene_or(e: ir.Or) -> _Compiled:
    lf, rf = compile_expr(e.left), compile_expr(e.right)

    def run(env: Env) -> DeviceColumn:
        l, r = lf(env), rf(env)
        lv = l.values.astype(bool) & l.valid
        rv = r.values.astype(bool) & r.valid
        value = lv | rv
        valid = (l.valid & r.valid) | lv | rv
        return DeviceColumn(value, valid)

    return run


def _div(e: ir.Div) -> _Compiled:
    lf, rf = compile_expr(e.left), compile_expr(e.right)

    def run(env: Env) -> DeviceColumn:
        l, r = lf(env), rf(env)
        rnz = r.values != 0
        lv = l.values.astype(jnp.float64)
        rv = jnp.where(rnz, r.values, 1).astype(jnp.float64)
        return DeviceColumn(lv / rv, l.valid & r.valid & rnz)

    return run


_CMP = {
    ir.Eq: lambda a, b: a == b,
    ir.Ne: lambda a, b: a != b,
    ir.Lt: lambda a, b: a < b,
    ir.Le: lambda a, b: a <= b,
    ir.Gt: lambda a, b: a > b,
    ir.Ge: lambda a, b: a >= b,
    ir.Add: lambda a, b: a + b,
    ir.Sub: lambda a, b: a - b,
    ir.Mul: lambda a, b: a * b,
}


def compile_expr(e: ir.Expression) -> _Compiled:
    """Lower an expression tree to a function over a device-column env.

    Raises :class:`NotDeviceCompilable` for string ops / casts / functions
    that belong on the host.
    """
    # every caller jits what this returns: place the compile cache first
    ensure_compilation_cache()
    t = type(e)
    if t is ir.Literal:
        return _lit(e)
    if t is ir.Column:
        return _col(e)
    if t is ir.Alias:
        return compile_expr(e.child)
    if t in _CMP:
        return _binop(e, _CMP[t])
    if t is ir.And:
        return _kleene_and(e)
    if t is ir.Or:
        return _kleene_or(e)
    if t is ir.Div:
        return _div(e)
    if t is ir.Not:
        cf = compile_expr(e.child)
        return lambda env: (lambda c: DeviceColumn(~c.values.astype(bool), c.valid))(cf(env))
    if t is ir.Neg:
        cf = compile_expr(e.child)
        return lambda env: (lambda c: DeviceColumn(-c.values, c.valid))(cf(env))
    if t is ir.IsNull:
        cf = compile_expr(e.child)
        return lambda env: (lambda c: DeviceColumn(~c.valid, jnp.ones_like(c.valid)))(cf(env))
    if t is ir.IsNotNull:
        cf = compile_expr(e.child)
        return lambda env: (lambda c: DeviceColumn(c.valid, jnp.ones_like(c.valid)))(cf(env))
    if t is ir.NullSafeEq:
        lf, rf = compile_expr(e.left), compile_expr(e.right)

        def run_nse(env: Env) -> DeviceColumn:
            l, r = lf(env), rf(env)
            eq = (l.values == r.values) & l.valid & r.valid
            both_null = ~l.valid & ~r.valid
            return DeviceColumn(eq | both_null, jnp.ones_like(eq))

        return run_nse
    if t is ir.In:
        vf = compile_expr(e.value)
        opts = [compile_expr(o) for o in e.options]

        def run_in(env: Env) -> DeviceColumn:
            v = vf(env)
            hit = jnp.zeros(jnp.shape(v.values), bool)
            any_null_opt = jnp.zeros((), bool)
            for of in opts:
                o = of(env)
                hit = hit | ((v.values == o.values) & o.valid)
                any_null_opt = any_null_opt | ~jnp.all(o.valid)
            valid = v.valid & (hit | ~any_null_opt)
            return DeviceColumn(hit, valid)

        return run_in
    if t is ir.Coalesce:
        fns = [compile_expr(c) for c in e.children]

        def run_coalesce(env: Env) -> DeviceColumn:
            cols = [f(env) for f in fns]
            out = cols[-1]
            for c in reversed(cols[:-1]):
                out = DeviceColumn(
                    jnp.where(c.valid, c.values, out.values), c.valid | out.valid
                )
            return out

        return run_coalesce
    if t is ir.CaseWhen:
        conds = [compile_expr(e.children[2 * i]) for i in range(e.n_branches)]
        vals = [compile_expr(e.children[2 * i + 1]) for i in range(e.n_branches)]
        default = compile_expr(e.children[-1])

        def run_case(env: Env) -> DeviceColumn:
            out = default(env)
            for cf, vf2 in zip(reversed(conds), reversed(vals)):
                c, v = cf(env), vf2(env)
                fire = c.values.astype(bool) & c.valid
                out = DeviceColumn(
                    jnp.where(fire, v.values, out.values),
                    jnp.where(fire, v.valid, out.valid),
                )
            return out

        return run_case
    if t is ir.Cast:
        cf = compile_expr(e.child)
        name = e.data_type.name if not hasattr(e.data_type, "precision") else "decimal"
        if name in ("byte", "short", "integer"):
            dtype: Any = jnp.int32
        elif name == "long":
            dtype = jnp.int64
        elif name in ("float", "double", "decimal"):
            # host row-eval casts produce python doubles; match that width
            dtype = jnp.float64
        elif name == "boolean":
            dtype = bool
        else:
            raise NotDeviceCompilable(f"cast to {name} not device-representable")
        return lambda env: (lambda c: DeviceColumn(c.values.astype(dtype), c.valid))(cf(env))
    if t is ir.Func and e.name in ("abs", "floor", "ceil", "exp", "sqrt"):
        cf = compile_expr(e.children[0])
        if e.name == "sqrt":
            # Spark: NULL outside the domain (the row evaluator's contract)
            return lambda env: (lambda c: DeviceColumn(
                jnp.sqrt(jnp.maximum(c.values.astype(jnp.float64), 0.0)),
                c.valid & (c.values >= 0)))(cf(env))
        fn = {"abs": jnp.abs, "floor": jnp.floor, "ceil": jnp.ceil,
              "exp": lambda v: jnp.exp(v.astype(jnp.float64))}[e.name]
        return lambda env: (lambda c: DeviceColumn(fn(c.values), c.valid))(cf(env))
    if t is ir.Func and e.name == "log" and len(e.children) == 1:
        cf = compile_expr(e.children[0])
        return lambda env: (lambda c: DeviceColumn(
            jnp.log(jnp.maximum(c.values.astype(jnp.float64), 1e-300)),
            c.valid & (c.values > 0)))(cf(env))
    if t is ir.Func and e.name in ("pow", "power") and len(e.children) == 2:
        cx = compile_expr(e.children[0])
        cy = compile_expr(e.children[1])
        return lambda env: (lambda a, b: DeviceColumn(
            jnp.power(a.values.astype(jnp.float64), b.values.astype(jnp.float64)),
            a.valid & b.valid))(cx(env), cy(env))
    if t is ir.Func and e.name in ("date_add", "date_sub") and len(e.children) == 2:
        # date lanes are epoch days on device
        cd = compile_expr(e.children[0])
        cn = compile_expr(e.children[1])
        sign = 1 if e.name == "date_add" else -1
        return lambda env: (lambda d, n: DeviceColumn(
            d.values + sign * n.values.astype(d.values.dtype),
            d.valid & n.valid))(cd(env), cn(env))
    if t is ir.Func and e.name == "datediff" and len(e.children) == 2:
        ca = compile_expr(e.children[0])
        cb = compile_expr(e.children[1])
        return lambda env: (lambda a, b: DeviceColumn(
            a.values - b.values, a.valid & b.valid))(ca(env), cb(env))
    if t is ir.Func and e.name in ("minute", "second") and len(e.children) == 1:
        ct = compile_expr(e.children[0])
        div = 60_000_000 if e.name == "minute" else 1_000_000
        return lambda env: (lambda c: DeviceColumn(
            (c.values // div) % 60, c.valid))(ct(env))
    if t is ir.Func and e.name == "hour" and len(e.children) == 1:
        # timestamp lanes are epoch microseconds (naive UTC)
        ct = compile_expr(e.children[0])
        return lambda env: (lambda c: DeviceColumn(
            (c.values // 3_600_000_000) % 24, c.valid))(ct(env))
    if t is ir.Func and e.name == "__ts_days" and len(e.children) == 1:
        # compile_residual's unit bridge: epoch-µs timestamp lane → epoch
        # days, so the calendar kernels below serve both temporal lanes
        ct = compile_expr(e.children[0])
        return lambda env: (lambda c: DeviceColumn(
            jnp.floor_divide(c.values, 86_400_000_000), c.valid))(ct(env))
    if t is ir.Func and e.name in ("__year_days", "__month_days",
                                   "__day_days") and len(e.children) == 1:
        ct = compile_expr(e.children[0])
        idx = ("__year_days", "__month_days", "__day_days").index(e.name)

        def run_civil(env: Env, _ct=ct, _idx=idx) -> DeviceColumn:
            # civil-from-days (Hinnant): exact for every date32 value; all
            # intermediate operands are non-negative after the era shift,
            # so jnp floor division matches the reference arithmetic
            c = _ct(env)
            z = c.values.astype(jnp.int64) + 719468
            era = jnp.floor_divide(z, 146097)
            doe = z - era * 146097
            yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
            doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
            mp = (5 * doy + 2) // 153
            day = doy - (153 * mp + 2) // 5 + 1
            month = jnp.where(mp < 10, mp + 3, mp - 9)
            year = yoe + era * 400 + (month <= 2)
            return DeviceColumn((year, month, day)[_idx], c.valid)

        return run_civil
    raise NotDeviceCompilable(f"{type(e).__name__} has no device lowering: {e.sql()}")


def columns_from_numpy(data: Dict[str, np.ndarray], masks: Optional[Dict[str, np.ndarray]] = None) -> Env:
    """Build a device env from host numpy columns (tests / small paths)."""
    masks = masks or {}
    return {k: DeviceColumn.of(v, masks.get(k)) for k, v in data.items()}


# -- residual-predicate lowering (the device scan path) ----------------------

#: dictionary code bound to a string literal ABSENT from a file's
#: dictionary — real codes are >= 0, so equality never fires against it
#: and inequality fires for every non-NULL row, exactly the host verdicts.
STR_CODE_ABSENT = -2

_STRLIT_PREFIX = "__strlit"
_CMP_TYPES = (ir.Eq, ir.Ne, ir.Lt, ir.Le, ir.Gt, ir.Ge)
_CMP_FLIP = {ir.Lt: ir.Gt, ir.Le: ir.Ge, ir.Gt: ir.Lt, ir.Ge: ir.Le}
_FLOAT_FUNCS = frozenset({"exp", "log", "sqrt", "pow", "power"})


def f64_order_key(values):
    """IEEE float64 value(s) → int64 key(s) whose signed order IS the float
    order (−0.0 folds into +0.0; NaN keys lie beyond the keys of ±inf,
    which `compile_residual` guards so compares stay IEEE's).

    The device encoding of every float lane (`ops/column_cache` data lanes,
    `ops/state_export` min/max stats lanes). A TPU holds float64 as a
    float32 pair — about 48 mantissa bits, float32's exponent range — so a
    float compare there can drop a row within 2^-48 of the literal, or
    beyond 3.4e38. int64 is emulated exactly, so the same compare over keys
    is exact on every device."""
    a = np.asarray(values, np.float64) + 0.0
    bits = a.view(np.int64)
    return bits ^ ((bits >> 63) & np.int64(0x7FFFFFFFFFFFFFFF))


#: a ``decimal(p, s)`` column with ``p`` up to this is an int64 lane of its
#: unscaled values (10^18 < 2^63); a wider one has no lane
DECIMAL_LANE_PRECISION = 18


def decimal_literal_units(lit: ir.Literal, scale: int):
    """``(floor(L * 10^scale), L * 10^scale is whole)`` for an exact numeric
    literal ``L`` (an integer, or one written in decimal notation), in
    integer arithmetic; None for any other literal. What a compare of a
    ``decimal(p, scale)`` lane against ``L`` lowers through: a literal with
    more digits than the scale tightens the bound and is never rounded."""
    v = lit.value
    if isinstance(v, bool):
        return None
    if lit.exact is not None:
        sign, digits, exp = lit.exact.as_tuple()
        if not isinstance(exp, int):  # NaN, Infinity
            return None
        n = int("".join(map(str, digits)) or "0") * (-1 if sign else 1)
    elif isinstance(v, int):
        n, exp = v, 0
    else:
        return None
    exp += scale
    if exp >= 0:
        return n * 10 ** exp, True
    q, r = divmod(n, 10 ** -exp)  # floors, also below zero
    return q, r == 0


_KEY_NEG_INF = int(f64_order_key(-np.inf))
_KEY_POS_INF = int(f64_order_key(np.inf))


class ResidualPlan(NamedTuple):
    """A residual predicate lowered for the device scan path
    (``ops/column_cache``): the rewritten expression (string literals have
    become placeholder columns over dictionary codes, temporal literals
    epoch ints — hashable, so it doubles as the jit-cache key), the data
    columns the device env must bind as lanes, the partition columns bound
    as per-file scalars, and the string-literal bindings the caller resolves
    per file against that file's dictionary (absent value →
    :data:`STR_CODE_ABSENT`)."""

    expr: ir.Expression
    refs: frozenset        # data columns needed as lanes (lower-cased)
    part_refs: frozenset   # partition columns bound as per-file scalars
    str_binds: tuple       # ((placeholder, column_lower, literal_value), ...)


def compile_residual(e: ir.Expression, types: Dict[str, Any],
                     partition_names=()) -> ResidualPlan:
    """Rewrite + gate a residual predicate so :func:`compile_expr` can run
    it over decoded file lanes:

    * string equality / ``IN`` against literals lowers to int32
      dictionary-code compares via per-file placeholder columns — string
      ORDER comparisons do not lower (codes are unordered);
    * date/timestamp literals (ISO strings or datetime objects) become the
      lane encodings (epoch days / epoch microseconds), and
      ``year``/``month``/``day``/``to_date``/``hour`` over temporal columns
      lower to the device calendar kernels;
    * a ``decimal(p, s)`` column with ``p <= 18`` is an int64 lane of
      unscaled values: compared against an exact literal (an integer or
      one written in decimal notation, scaled to ``s`` by
      :func:`decimal_literal_units`; more digits than ``s`` tighten the
      bound), against ``IN`` literals, against a decimal column of the same
      scale, or null-tested, it lowers to an exact int64 compare; every
      other use of a decimal column (arithmetic, a float literal, ``p >
      18``, a partition column) raises :class:`NotDeviceCompilable`;
    * string partition references and mixed date-vs-timestamp compares
      raise :class:`NotDeviceCompilable` — the caller falls back to the
      Arrow path;
    * float lanes hold :func:`f64_order_key` keys (a TPU's float64 is not
      IEEE): a float column compared against a numeric literal (or ``IN``
      literals), or null-tested, lowers to an exact int64 compare over
      them, and every other use of a float column, literal, division, cast
      or function raises :class:`NotDeviceCompilable`.

    ``types`` maps lower-cased column names to declared
    :class:`~delta_tpu.schema.types.DataType`; ``partition_names`` marks the
    columns bound as per-file scalars instead of lanes.
    """
    import datetime as _dt

    from delta_tpu.schema.types import (DateType, DecimalType, DoubleType,
                                        FloatType, StringType, TimestampType)

    parts = frozenset(c.lower() for c in partition_names)
    binds: list = []
    refs: set = set()
    part_refs: set = set()

    def _ctype(x):
        while isinstance(x, ir.Alias):
            x = x.child
        if isinstance(x, ir.Column):
            return types.get(x.name.lower())
        if isinstance(x, ir.Func) and x.name == "to_date" and len(x.children) == 1:
            ct = _ctype(x.children[0])
            return DateType() if isinstance(ct, (DateType, TimestampType)) else None
        return None

    def _inexact(what: str) -> NotDeviceCompilable:
        return NotDeviceCompilable(
            f"{what}: float64 is not exact on the device")

    def _is_float(n: str) -> bool:
        return isinstance(types.get(n), (FloatType, DoubleType))

    def _note(c: ir.Column, as_key: bool = False) -> ir.Column:
        """``as_key``: the caller reads the lane in its own encoding (a
        float's order keys, a decimal's unscaled integers)."""
        n = c.name.lower()
        if not as_key and isinstance(types.get(n), DecimalType):
            raise NotDeviceCompilable(
                f"decimal column {c.name!r} outside an exact compare stays "
                f"on host (exact arithmetic)")
        if not as_key and _is_float(n):
            raise _inexact(f"float column {c.name!r} outside a literal compare")
        if n in parts:
            if isinstance(types.get(n), StringType):
                raise NotDeviceCompilable(
                    f"string partition column {c.name!r} has no device codes")
            part_refs.add(n)
        else:
            refs.add(n)
        return ir.Column(n)

    def _temporal_lit(lit: ir.Literal, dt) -> ir.Literal:
        v = lit.value
        if v is None:
            return lit
        if isinstance(v, str):
            from delta_tpu.utils.timeparse import iso_to_date, iso_to_naive_utc

            try:
                v = (iso_to_date(v) if isinstance(dt, DateType)
                     else iso_to_naive_utc(v))
            except ValueError:
                raise NotDeviceCompilable(
                    f"unparseable temporal literal {lit.value!r}") from None
        if isinstance(dt, TimestampType) and isinstance(v, _dt.date) \
                and not isinstance(v, _dt.datetime):
            v = _dt.datetime.combine(v, _dt.time())  # midnight, like Spark
        if isinstance(v, _dt.datetime):
            if not isinstance(dt, TimestampType):
                raise NotDeviceCompilable("timestamp literal vs date lane")
            if v.tzinfo is None:
                v = v.replace(tzinfo=_dt.timezone.utc)  # naive IS UTC here
            return ir.Literal(int(v.timestamp() * 1_000_000))
        if isinstance(v, _dt.date):
            return ir.Literal((v - _dt.date(1970, 1, 1)).days)
        raise NotDeviceCompilable(
            f"literal {v!r} does not coerce to a temporal lane")

    def _strip(x):
        while isinstance(x, ir.Alias):
            x = x.child
        return x

    def _ifunc(name: str, child: ir.Expression) -> ir.Func:
        # internal lowering-only node (__ts_days / __{year,month,day}_days):
        # built via the clone idiom because ir.Func validates public names,
        # and these never reach host eval — compile_expr consumes them
        f = object.__new__(ir.Func)
        f.name = name
        f.children = (child,)
        return f

    def _key_lane(x) -> Optional[ir.Column]:
        """The float DATA column ``x`` is (its lane holds order keys)."""
        x = _strip(x)
        if (isinstance(x, ir.Column) and x.name.lower() not in parts
                and _is_float(x.name.lower())):
            return x
        return None

    def _dec_lane(x) -> Optional[ir.Column]:
        """The decimal DATA column ``x`` is, when it has an int64 lane."""
        x = _strip(x)
        if isinstance(x, ir.Column) and x.name.lower() not in parts:
            dt = types.get(x.name.lower())
            if isinstance(dt, DecimalType) \
                    and dt.precision <= DECIMAL_LANE_PRECISION:
                return x
        return None

    def _dec_compare(t, c: ir.Column, lit: ir.Literal) -> ir.Expression:
        """``c <t> lit`` over the lane's unscaled integers."""
        col = _note(c, as_key=True)
        if lit.value is None:
            return t(col, lit)
        units = decimal_literal_units(lit, types[col.name].scale)
        if units is None:
            raise _inexact(f"decimal column {c.name!r} against the inexact "
                           f"literal {lit.sql()}")
        floor, whole = units
        if not whole:
            # between two lane values: no row equals it (Ne(c, c) is FALSE
            # for a value and NULL for a NULL, as the compare would be), and
            # an ordering compare moves to the whole number below
            if t is ir.NullSafeEq:
                raise NotDeviceCompilable(
                    f"<=> against {lit.sql()}, which no {c.name!r} holds")
            t = {ir.Eq: ir.Ne, ir.Ne: ir.Eq, ir.Ge: ir.Gt,
                 ir.Lt: ir.Le}.get(t, t)
            if t in (ir.Eq, ir.Ne):
                return t(col, col)
        return t(col, ir.Literal(floor))

    def _key_lit(x) -> Optional[ir.Literal]:
        x = _strip(x)
        if isinstance(x, ir.Literal) and not isinstance(x.value, bool):
            if x.value is None:
                return x
            if isinstance(x.value, float) and x.value == x.value \
                    or isinstance(x.value, int) and abs(x.value) <= 2**53:
                return ir.Literal(int(f64_order_key(float(x.value))))
        # NaN literals (no key compare is IEEE's) and integers float64
        # cannot hold exactly have no key
        return None

    def rw(x: ir.Expression) -> ir.Expression:
        t = type(x)
        if t is ir.Alias:
            return rw(x.child)
        if t is ir.Column:
            return _note(x)
        if t is ir.Literal:
            v = x.value
            if isinstance(v, float):
                raise _inexact(f"float literal {v!r} outside a float-column compare")
            if isinstance(v, str):
                # a string literal outside a code compare has no device form
                raise NotDeviceCompilable(
                    f"string literal {v!r} outside a dictionary-code compare")
            if isinstance(v, _dt.datetime):
                if v.tzinfo is None:
                    v = v.replace(tzinfo=_dt.timezone.utc)
                return ir.Literal(int(v.timestamp() * 1_000_000))
            if isinstance(v, _dt.date):
                return ir.Literal((v - _dt.date(1970, 1, 1)).days)
            return x
        if t in _CMP_TYPES or t is ir.NullSafeEq:
            l, r = x.left, x.right
            if isinstance(l, ir.Literal) and not isinstance(r, ir.Literal):
                l, r = r, l
                t = _CMP_FLIP.get(t, t)
            dcol = _dec_lane(l)
            if dcol is not None and isinstance(_strip(r), ir.Literal):
                return _dec_compare(t, dcol, _strip(r))
            if dcol is not None and _dec_lane(r) is not None \
                    and _ctype(l).scale == _ctype(r).scale:
                return t(_note(dcol, as_key=True),
                         _note(_dec_lane(r), as_key=True))
            kcol, klit = _key_lane(l), _key_lit(r)
            if kcol is not None and klit is not None:
                col = _note(kcol, as_key=True)
                cmp = t(col, klit)
                # NaN keys lie beyond ±inf: an ordering compare must not
                # take a NaN row for a large (or small) value
                if klit.value is not None and t in (ir.Lt, ir.Le):
                    return ir.And(cmp, ir.Ge(col, ir.Literal(_KEY_NEG_INF)))
                if klit.value is not None and t in (ir.Gt, ir.Ge):
                    return ir.And(cmp, ir.Le(col, ir.Literal(_KEY_POS_INF)))
                return cmp
            lt_, rt_ = _ctype(l), _ctype(r)
            if isinstance(lt_, (DateType, TimestampType)) \
                    and isinstance(rt_, (DateType, TimestampType)):
                if type(lt_) is not type(rt_):
                    raise NotDeviceCompilable(
                        "mixed date/timestamp compare (lane units differ)")
                return t(rw(l), rw(r))
            if isinstance(lt_, (DateType, TimestampType)) and isinstance(r, ir.Literal):
                return t(rw(l), _temporal_lit(r, lt_))
            stringy = (isinstance(lt_, StringType) or isinstance(rt_, StringType)
                       or isinstance(getattr(_strip(l), "value", None), str)
                       or isinstance(getattr(_strip(r), "value", None), str))
            if stringy:
                col, lit = _strip(l), _strip(r)
                if t in (ir.Eq, ir.Ne, ir.NullSafeEq) \
                        and isinstance(lt_, StringType) \
                        and isinstance(col, ir.Column) \
                        and isinstance(lit, ir.Literal) \
                        and (lit.value is None or isinstance(lit.value, str)):
                    if lit.value is None:
                        return t(_note(col), ir.Literal(None))
                    ph = f"{_STRLIT_PREFIX}{len(binds)}"
                    binds.append((ph, col.name.lower(), lit.value))
                    return t(_note(col), ir.Column(ph))
                raise NotDeviceCompilable(
                    f"string comparison stays on host: {x.sql()}")
            return t(rw(l), rw(r))
        if t is ir.In:
            v = _strip(x.value)
            vt = _ctype(v)
            opts = list(x.options)
            dcol = _dec_lane(v)
            if dcol is not None and all(
                    isinstance(_strip(o), ir.Literal) for o in opts):
                col, new_opts = _note(dcol, as_key=True), []
                for o in map(_strip, opts):
                    units = None if o.value is None else \
                        decimal_literal_units(o, types[col.name].scale)
                    if o.value is not None and units is None:
                        raise _inexact(f"decimal IN option {o.sql()}")
                    if units is None:
                        new_opts.append(o)  # NULL option: Kleene semantics
                    elif units[1]:
                        new_opts.append(ir.Literal(units[0]))
                    # an option between two lane values matches no row
                return ir.In(col, new_opts) if new_opts else ir.Ne(col, col)
            kcol, kopts = _key_lane(v), [_key_lit(o) for o in opts]
            if kcol is not None and all(o is not None for o in kopts):
                return ir.In(_note(kcol, as_key=True), kopts)
            if isinstance(vt, StringType):
                if not isinstance(v, ir.Column):
                    raise NotDeviceCompilable("string IN over a non-column")
                new_opts = []
                for o in opts:
                    o = _strip(o)
                    if not isinstance(o, ir.Literal):
                        raise NotDeviceCompilable(
                            "string IN option is not a literal")
                    if o.value is None:
                        new_opts.append(o)  # NULL option: Kleene semantics
                        continue
                    if not isinstance(o.value, str):
                        raise NotDeviceCompilable(
                            f"non-string option {o.value!r} in string IN")
                    ph = f"{_STRLIT_PREFIX}{len(binds)}"
                    binds.append((ph, v.name.lower(), o.value))
                    new_opts.append(ir.Column(ph))
                return ir.In(_note(v), new_opts)
            if isinstance(vt, (DateType, TimestampType)):
                new_opts = [o if (isinstance(_strip(o), ir.Literal)
                                  and _strip(o).value is None)
                            else _temporal_lit(_strip(o), vt)
                            if isinstance(_strip(o), ir.Literal) else rw(o)
                            for o in opts]
                return ir.In(rw(x.value), new_opts)
            return ir.In(rw(x.value), [rw(o) for o in opts])
        if t is ir.Func and x.name in ("year", "month", "day") \
                and len(x.children) == 1:
            ct = _ctype(x.children[0])
            child = rw(x.children[0])
            if isinstance(ct, TimestampType):
                child = _ifunc("__ts_days", child)
            elif not isinstance(ct, DateType):
                raise NotDeviceCompilable(
                    f"{x.name}() over a non-temporal lane")
            return _ifunc(f"__{x.name}_days", child)
        if t is ir.Func and x.name == "to_date" and len(x.children) == 1:
            ct = _ctype(x.children[0])
            if isinstance(ct, TimestampType):
                return _ifunc("__ts_days", rw(x.children[0]))
            if isinstance(ct, DateType):
                return rw(x.children[0])
            raise NotDeviceCompilable("to_date over a non-temporal lane")
        if t is ir.Func and x.name == "hour" and len(x.children) == 1:
            if not isinstance(_ctype(x.children[0]), TimestampType):
                raise NotDeviceCompilable("hour() needs a timestamp lane")
            return ir.Func("hour", [rw(x.children[0])])
        if t in (ir.IsNull, ir.IsNotNull):
            kcol = _key_lane(x.child) or _dec_lane(x.child)
            if kcol is not None:
                return t(_note(kcol, as_key=True))  # reads validity only
        if (t is ir.Div
                or (t is ir.Func and x.name in _FLOAT_FUNCS)
                or (t is ir.Cast and (
                    hasattr(x.data_type, "precision")
                    or x.data_type.name in ("float", "double")))):
            raise _inexact(x.sql())
        # generic rebuild (And/Or/Not/arith/null tests/Coalesce/CaseWhen/
        # Cast/other Funcs) — unsupported shapes surface from compile_expr
        new_children = tuple(rw(c) for c in x.children)
        if new_children == x.children:
            return x
        clone = object.__new__(t)
        clone.__dict__.update(x.__dict__)
        clone.children = new_children
        return clone

    out = rw(e)
    compile_expr(out)  # validate the lowering NOW — routers price after this
    return ResidualPlan(out, frozenset(refs), frozenset(part_refs),
                        tuple(binds))
