"""Aggregates answered where the data lives: fused filter-and-sum kernels
over the scan column cache's resident lanes, ungrouped or grouped by a few
values.

``SELECT sum(a * b) FROM t WHERE lo <= c AND c < hi`` through the scan path
downloads a byte a row of mask and decodes the survivors' columns from
Parquet to add them up on the host. When every referenced column has a lane
(`ops/column_cache`: integers, dates, ``decimal(p <= 18)`` as unscaled
int64, strings as dictionary codes), the whole query is one pass over HBM
and its answer a few bytes: :func:`device_aggregate` plans the files as a
scan does, loads the lanes it misses, and runs one program once a file,
the partials staying on the device; one fetch brings them back.

Ungrouped (:func:`_aggregate_kernel`, the XLA module
``jit_filter_aggregate``): one carry of four slots a select item, summed
across the files on the device.

Grouped (:func:`_group_kernel`, ``jit_filter_group_aggregate``): ``GROUP BY``
over columns that have lanes. A string lane's codes number a dictionary
**per file**, so a code means nothing across files: the program works in
each file's own numbering (the group of a row is a mixed-radix number of its
key lanes' codes, the radices handed in as operands), writes that file's
partials into its row of the carry, and the host merges the files' partials
**by value** through each file's ``dict_codes`` read backwards, a NULL key a
group of its own. The sums a group come from the matrix unit: a one-hot of
the group against the aggregated values cut into 8-bit limbs, int8 by int8
into int32, exact. There are two schedules of that one algorithm
(:func:`_group_kernel`): ``tiled``, a hand-written kernel that reads a file's
lanes once, a tile of rows at a time, forms limbs and one-hot in on-chip
memory in 32-bit integers and contracts them once a tile (:func:`_tile_call`,
the repo's one kernel in `jax.experimental.pallas`); and ``wide``, XLA's own
schedule, a contraction a term over byte rows in HBM, the only one that
carries an int64 product (:func:`_wide_sums`). The lanes' own extremes over
the planned files choose (:func:`_fits_tiles`), by no conf, and the stage's
span says which ran (``program``).

Exact by construction: the conjunction of range predicates is compared in
integers (literals scaled by `jaxeval.compile_residual`, the bounds handed
to the program as **operands**, so one program a lane shape serves every
literal), validity and deletion vectors are honoured, an aggregated
expression is a product of up to three factors, each a lane or an exact
literal plus or minus a lane in the lane's own units (``1 - l_discount``
over hundredths is ``100 - units``), and ``sum`` / ``count`` / ``min`` /
``max`` accumulate in integers after a bound from the lanes' own extremes
times the row counts has proved that nothing can overflow. No float is
anywhere on the way. The result is the Arrow table the host route
(`sql/parser._run_aggregate`) returns, type included: each type is read off
the host's own kernels.

The route is taken from what can be observed, by no conf of its own
(``delta.tpu.read.deviceResidual.mode=off`` turns the column cache, and so
this, off): the shape of the select list and of the predicate, the columns'
types, the lanes' bytes against the cache's budget, the overflow bound, the
groups a file can hold. A decline says why in the span's ``route``
(``host:<reason>``: ``shape``, ``type``, ``predicate``, ``budget``,
``overflow``, ``groups``, ``off``) and the host route runs.
"""
from __future__ import annotations

import contextlib
import functools
import time
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

__all__ = ["device_aggregate", "AggregateSpec", "Factor", "GROUP_SLOTS"]

_I64_MAX = np.iinfo(np.int64).max
_I64_MIN = np.iinfo(np.int64).min
#: slots of the carry a spec owns: sum, count, min, max
_SLOTS = 4
#: a float64 holds every integer below this, so a mean of integers computed
#: from an exact sum is the host's, whatever order it adds in
_EXACT_F64 = 2 ** 53
#: Groups one launch carries: the product over the key lanes of the values
#: the file holds (one more each where a NULL can be) may not pass it
#: (``host:groups``). 32 is the height of one int8 tile on the chip (32
#: sublanes x 128 lanes): in the wide program the one-hot of the group is one
#: tile high for any count up to it and a group's number fits the int8 the
#: one-hot compares in; in the tiled program four slots share a 32-bit word,
#: so the one-hot is 8 int8 tiles at most (what a launch of TPC-H Q1 takes
#: under either, by slots: PERF.md, PR 33). TPC-H Q1 needs 3 x 2.
GROUP_SLOTS = 32
#: rows one int8 contraction may add into int32: 2^22 x 128 < 2^31
_DOT_ROWS = 1 << 22
#: registers of 8 x 128 rows a lane that one step of the tile kernel takes
#: (32,768 rows)
_TILE_VREGS = 32
_I32_MAX = np.iinfo(np.int32).max
_I32_MIN = np.iinfo(np.int32).min


class Factor(NamedTuple):
    """One factor of an aggregated product, in the lane's own units:
    ``offset + sign * lane``. A bare lane is ``(column, 1, 0)``;
    ``1 - l_discount`` over hundredths is ``(l_discount, -1, 100)``."""

    column: str
    sign: int = 1
    offset: int = 0


class AggregateSpec(NamedTuple):
    func: str                      # count | sum | avg | min | max
    factors: Tuple[Factor, ...]    # () is COUNT(*)

    @property
    def cols(self) -> Tuple[str, ...]:
        return tuple(f.column for f in self.factors)


class _Term(NamedTuple):
    """One product the grouped program forms, shared by the select items
    over it: which of sum / min / max are wanted (its count always is), the
    index of the first factor at which the running product passes int32
    (``len(factors)`` when it never does), and the bytes its sum is cut into.
    The wide program multiplies in int64 from ``wide_at`` on and sends
    ``nbytes`` rows; the tiled one has no int64: it takes a term whose
    ``wide_at`` is the last factor at the earliest, splits that last step in
    16-bit halves (`_tile_call`) and always sends a value's four bytes. The
    two widths come from the lanes' extremes, never from a literal of the
    predicate."""

    factors: Tuple[Factor, ...]
    want: Tuple[str, ...]
    wide_at: int
    nbytes: int

    @property
    def passes_int32(self) -> bool:
        return self.wide_at < len(self.factors)


class _Decline(Exception):
    """The host route answers; ``args[0]`` is the reason."""


class _FileLanes(NamedTuple):
    """One file's lanes as the kernel takes them. The arrays are held here,
    so an eviction between the load and the launch frees nothing in use."""

    add: Any
    env: Dict[str, Tuple[Any, Any]]       # column -> (values, valid)
    rows: int                             # physical rows; the lanes are padded
    ranges: Dict[str, Tuple[int, int]]    # column -> (least, largest) it holds
    codes: Dict[str, Optional[Dict[str, int]]]  # string column -> value -> code


def _lane_rows(f: _FileLanes) -> int:
    """The padded length the file's lanes share."""
    return next(iter(f.env.values()))[0].shape[0]


# -- the kernels -----------------------------------------------------------------


def _live_rows(jnp, lanes, preds, bounds, n, keep):
    """Rows of the file that exist, are not deleted and pass every range."""
    cap = next(iter(lanes.values()))[0].shape[0]
    live = jnp.arange(cap, dtype=jnp.int32) < n
    if keep is not None:
        live = live & keep
    for i, c in enumerate(preds):
        v, ok = lanes[c]
        v = v.astype(jnp.int64)
        live = live & ok & (v >= bounds[i, 0]) & (v <= bounds[i, 1])
    return live


def _product(jnp, lanes, factors, live, wide_at=0):
    """``(mask, value)`` of a product of factors over the live rows: the mask
    drops a row in which a factor is NULL. Factors before ``wide_at``
    multiply in int32, the rest in int64."""
    m, x = live, None
    for j, f in enumerate(factors):
        v, ok = lanes[f.column]
        m = m & ok
        dtype = jnp.int32 if j < wide_at else jnp.int64
        v = v.astype(dtype)
        if f.sign != 1 or f.offset:
            v = dtype(f.offset) + (v if f.sign == 1 else -v)
        x = v if x is None else x.astype(dtype) * v
    return m, x


@functools.lru_cache(maxsize=64)
def _aggregate_kernel(preds: Tuple[str, ...], specs: Tuple[AggregateSpec, ...]):
    """One program for a select list and the columns its predicate ranges
    over; XLA keys it further on the lane shape and on whether a deletion
    vector's ``keep`` comes with the file. Never on a literal of the
    predicate."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    def filter_aggregate(lanes, bounds, n, keep, carry):
        # lanes: {column: (values, valid)} padded to one pow2 length;
        # bounds: int64[len(preds), 2], inclusive; n: the file's rows
        live = _live_rows(jnp, lanes, preds, bounds, n, keep)
        out = []
        for k, spec in enumerate(specs):
            m, x = _product(jnp, lanes, spec.factors, live)
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


def _term_columns(terms: Sequence[_Term]) -> List[Dict[str, int]]:
    """Where each term's count and wanted aggregates sit in a group's row of
    the partials."""
    out, at = [], 1  # column 0: the group's live rows
    for t in terms:
        names = ("count",) + t.want
        out.append({name: at + i for i, name in enumerate(names)})
        at += len(names)
    return out


def _off_chip() -> bool:
    """Off the chip the tile kernel's body runs in the kernel language's
    interpreter: the same code, so the tests execute what the chip runs."""
    import jax

    return jax.default_backend() != "tpu"


def _group_ids(jnp, lanes, keys, layout):
    """Each row's group in the file's own numbering,
    ``sum((key_i - lo_i) * stride_i)`` with a NULL key counted as ``size_i``:
    below ``slots`` by the caller's check."""
    gid = None
    for i, c in enumerate(keys):
        v, ok = lanes[c]
        part = jnp.where(ok, (v - layout[i, 0].astype(v.dtype)).astype(jnp.int32),
                         layout[i, 1].astype(jnp.int32))
        part = part * layout[i, 2].astype(jnp.int32)
        gid = part if gid is None else gid + part
    return gid


def _from_limbs(limbs, in_group):
    """A group's sum from the sums of its value's bytes, least first: a low
    byte was sent as ``byte - 128``, so the group's rows times 128 come back;
    the top byte is signed as it stands. int64 over ``[slots]`` values."""
    total = limbs[-1] << (8 * (len(limbs) - 1))
    for b, low in enumerate(limbs[:-1]):
        total = total + ((low + 128 * in_group) << (8 * b))
    return total


def _wide_sums(jnp, lanes, terms, live, gid, slots):
    """``(rows a group, [(count, sum or None) a term])`` as XLA schedules
    it: one contraction a term over the whole file, the one-hot of the group
    (int8, ``slots`` x rows, never stored: XLA forms it inside each
    contraction) against int8 rows the program writes to HBM, one the
    count's mask and one a byte of the summed value. The only formulation
    that carries a product in int64 (from ``_Term.wide_at`` on). At most
    ``_DOT_ROWS`` rows go into one int32 (2^22 x 128 < 2^31); the blocks add
    in int64."""
    cap = live.shape[0]
    # in int8: a live row's group is below 32, a dead row's is -1 (a launch
    # is 1.75 ms so, 2.16 with the compare in int32: PR 32, call 3)
    small = jnp.where(live, gid, -1).astype(jnp.int8)
    hot = (small[None, :] == jnp.arange(slots, dtype=jnp.int8)[:, None]
           ).astype(jnp.int8)
    block = min(cap, _DOT_ROWS)
    hot = hot.reshape(slots, cap // block, block)

    def per_group(mat):
        """int8[rows, cap] against the one-hot: int64[slots, rows]."""
        acc = jnp.einsum("gbk,rbk->bgr", hot,
                         mat.reshape(mat.shape[0], cap // block, block),
                         preferred_element_type=jnp.int32)
        return jnp.sum(acc.astype(jnp.int64), axis=0)

    in_group = per_group(jnp.ones((1, cap), jnp.int8))[:, 0]
    sums = []
    for t in terms:
        if not t.factors:  # COUNT(*)
            sums.append((in_group, None))
            continue
        m, x = _product(jnp, lanes, t.factors, live, t.wide_at)
        # row 0 the count's mask, then a row a byte of the value, all of
        # one expression, (source >> shift & mask) - bias, so that XLA
        # writes the term's rows in one pass
        nb = t.nbytes if "sum" in t.want else 0
        shift = np.array([0] + [8 * b for b in range(nb)])
        mask = np.array([1] + [255] * (nb - 1) + [-1] * bool(nb))
        bias = np.array([0] + [128] * (nb - 1) + [0] * bool(nb))
        source = jnp.where(jnp.asarray(np.arange(nb + 1) == 0)[:, None],
                           m.astype(x.dtype)[None, :],
                           jnp.where(m, x, 0)[None, :])
        mat = ((source >> jnp.asarray(shift, x.dtype)[:, None])
               & jnp.asarray(mask, x.dtype)[:, None]) \
            - jnp.asarray(bias, x.dtype)[:, None]
        acc = per_group(mat.astype(jnp.int8))
        total = _from_limbs([acc[:, 1 + b] for b in range(nb)], in_group) \
            if nb else None
        sums.append((acc[:, 0], total))
    return in_group, sums


def _tile_call(preds, terms, keys, slots, columns, blocks, interpret):
    """The tile kernel: ``(the call, where each mask and each value sits in
    its result)``. A grid step takes ``tile`` x 1,024 rows of every lane
    (`_TILE_VREGS` registers of 8 x 128 32-bit values a lane; the row a
    value belongs to is no matter to a sum, only that every lane is cut the
    same way) and, in on-chip memory, 32-bit integers only:

    * the live mask from the word of bits the caller packed (bit 0: the row
      exists and no deletion vector drops it; bit 1 + k: column k is not
      NULL) and the inclusive bounds, the group number from the key lanes
      and their radices (scalars, so a fresh literal or another dictionary
      compiles nothing), each term's product. A product whose last step
      passes int32 is split, not widened: ``p = ph * 2^16 + pl`` and two
      sums, ``ph * f`` and ``pl * f``, each below 2^31 while ``|f| < 2^15``
      (`_fits_tiles` holds a query to that), joined by the caller over
      ``[slots]`` values;
    * a 32-bit word is four int8 rows at no cost (`pltpu.bitcast`: byte b of
      sublane i is row 4 i + b). A value's word, ``x ^ 0x00808080``, is its
      three low bytes each less 128 and its top byte signed as it stands:
      the limbs `_wide_sums` cuts one at a time; the count masks go four to
      a word, the first of them the constant 1 that counts a group's rows;
      the one-hot of the group is ``1 << 8 (g % 4)`` in word ``g // 4``;
    * one contraction a step on the matrix unit, the one-hot's rows against
      all the limbs' at once, added into an int32 accumulator that stays on
      the chip across the grid. Each of the 8 sublanes is a stream of rows of
      its own, so the accumulator holds a product for every pair of streams
      and the caller reads the 8 in which both are the same.

    Exact: a limb is at most 128 in size and one stream holds an eighth of
    the file, at most `_DOT_ROWS` rows (`_fits_tiles`): below 2^31."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    i32 = jnp.int32
    tile = min(blocks, _TILE_VREGS)
    bit = {c: 1 << (1 + k) for k, c in enumerate(columns)}
    masks: List[Any] = ["rows"]     # what each mask byte counts, in order
    values: List[Tuple[int, int]] = []  # (term, half) of each value's word
    for k, t in enumerate(terms):
        if t.factors:
            masks.append(k)
            if "sum" in t.want:
                values += [(k, h) for h in range(1 + t.passes_int32)]
    mask_words = -(-len(masks) // 4)
    words = len(values) + mask_words

    def kernel(scal, bits_ref, *refs):
        acc = refs[len(columns)]

        def load(ref):
            # registers side by side: [tile, 8, 128] as [8, tile x 128]
            x = jnp.concatenate([ref[a] for a in range(tile)], axis=1)
            return x if x.dtype == i32 else jax.lax.bitcast_convert_type(x, i32)

        bits = load(bits_ref)
        lane = {c: load(ref) for c, ref in zip(columns, refs)}

        def has(need):
            return (bits & i32(need)) == i32(need)

        need = 1
        for c in preds:
            need |= bit[c]
        live = has(need)
        for i, c in enumerate(preds):
            live = live & (lane[c] >= scal[2 * i]) & (lane[c] <= scal[2 * i + 1])
        gid, base = None, 2 * len(preds)
        for i, c in enumerate(keys):
            lo, size, stride = (scal[base + 3 * i + j] for j in range(3))
            part = jnp.where(has(bit[c]), lane[c] - lo, size) * stride
            gid = part if gid is None else gid + part
        gid = jnp.where(live, gid, i32(-4))   # a dead row: word -1, no slot's
        word_of = gid >> i32(2)
        byte = jnp.left_shift(i32(1), (gid & i32(3)) << i32(3))
        hot = [jnp.where(word_of == i32(j), byte, i32(0))
               for j in range(slots // 4)]

        def factor(f):
            v = lane[f.column]
            return v if f.sign == 1 and not f.offset else \
                i32(f.offset) + (v if f.sign == 1 else -v)

        count, sent = {}, {}
        for k, t in enumerate(terms):
            if not t.factors:
                continue
            need = 0
            for f in t.factors:
                need |= bit[f.column]
            m = count[k] = live & has(need)
            if "sum" not in t.want:
                continue
            x = None
            for f in t.factors[:-1] if t.passes_int32 else t.factors:
                x = factor(f) if x is None else x * factor(f)
            halves = [x]
            if t.passes_int32:  # the last step, in 16-bit halves
                last = factor(t.factors[-1])
                halves = [(x & i32(0xFFFF)) * last, (x >> i32(16)) * last]
            for h, x in enumerate(halves):
                sent[k, h] = jnp.where(m, x, i32(0)) ^ i32(0x00808080)
        rows = [sent[v] for v in values]
        for w in range(mask_words):
            word = None
            for b, name in enumerate(masks[4 * w:4 * w + 4]):
                one = i32(1 << (8 * b)) if name == "rows" else \
                    jnp.where(count[name], i32(1 << (8 * b)), i32(0))
                word = one if word is None else word | one
            rows.append(jnp.broadcast_to(word, bits.shape))
        mat = jnp.concatenate([pltpu.bitcast(r, jnp.int8) for r in rows], axis=0)
        one_hot = jnp.concatenate([pltpu.bitcast(r, jnp.int8) for r in hot],
                                  axis=0)
        part = jax.lax.dot_general(one_hot, mat, (((1,), (1,)), ((), ())),
                                   preferred_element_type=i32)

        @pl.when(pl.program_id(0) == 0)
        def _():
            acc[...] = jnp.zeros(acc.shape, i32)

        acc[...] += part

    # (a bare 0 in an index map is 64-bit under x64, which the chip refuses)
    lanes_in = pl.BlockSpec((tile, 8, 128), lambda i, scal: (i, i32(0), i32(0)))
    shape = (slots * 8, words * 32)
    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shape, i32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(blocks // tile,),
            in_specs=[lanes_in] * (1 + len(columns)),
            out_specs=pl.BlockSpec(shape, lambda i, scal: (i32(0), i32(0)))),
        # the word of bits may be computed in the kernel's own pipeline, from
        # the bool lanes: no 16 MB of bits is written and read back, and all
        # four low words then fit beside the lanes in on-chip memory (a
        # launch of Q1 alone 0.38 -> 0.29 ms: PERF.md, PR 33)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            allow_input_fusion=[False, True] + [False] * len(columns)),
        name="group_tiles", interpret=interpret)
    at = {("mask", name): len(values) * 4 + i for i, name in enumerate(masks)}
    at.update({("value", v): 4 * i for i, v in enumerate(values)})
    return call, at


def _tiled_sums(jnp, lanes, preds, terms, keys, slots, bounds, n, keep, layout,
                interpret):
    """What `_wide_sums` returns, from the tile kernel (`_tile_call`). Around
    it, in the same XLA module: a lane the cache holds as int64 is cut to its
    low word (`_fits_tiles`: the lane's own extremes fit), the validity
    lanes, ``keep`` and ``row < n`` are packed into one word of bits a row in
    one elementwise pass (the kernel language takes no ``bool`` array), the
    bounds and the keys' radices become int32 scalars, and the accumulator's
    limbs are put together in int64 over ``[slots]`` values."""
    columns = tuple(sorted(lanes))
    cap = max(next(iter(lanes.values()))[0].shape[0], 1024)
    if cap > 8 * _DOT_ROWS:  # `_fits_tiles` sends such a file to `wide`
        raise ValueError(f"{cap} rows a launch: a stream of the tile kernel "
                         f"adds at most {_DOT_ROWS} limbs into an int32")
    blocks = cap // 1024

    def rows(x):
        if x.shape[0] < cap:
            x = jnp.pad(x, (0, cap - x.shape[0]))
        return x.reshape(blocks, 8, 128)

    bits = jnp.arange(cap, dtype=jnp.int32) < n
    if keep is not None:
        bits = bits & rows(keep).reshape(cap)
    bits = bits.astype(jnp.int32).reshape(blocks, 8, 128)
    for k, c in enumerate(columns):
        bits = bits | (rows(lanes[c][1]).astype(jnp.int32) << (1 + k))
    # the low word of an int64 is XLA's split alone as uint32 (as int32 it
    # is one more pass over the lane); the kernel reads the same bits
    values = [rows(v if v.dtype == jnp.int32 else v.astype(jnp.uint32))
              for v in (lanes[c][0] for c in columns)]
    # a bound beyond int32 holds for every row of a lane inside it, or none
    lo, hi = bounds[:, 0], bounds[:, 1]
    none = (lo > _I32_MAX) | (hi < _I32_MIN)
    scal = jnp.concatenate([
        jnp.stack([jnp.where(none, 1, jnp.clip(lo, _I32_MIN, _I32_MAX)),
                   jnp.where(none, 0, jnp.clip(hi, _I32_MIN, _I32_MAX))],
                  axis=1).reshape(-1), layout.reshape(-1)]).astype(jnp.int32)
    call, at = _tile_call(preds, terms, keys, slots, columns, blocks, interpret)
    acc = call(scal, bits, *values)
    # [one-hot word, stream, byte] x [word, stream, byte]: the same stream
    words = acc.shape[1] // 32
    acc = acc.reshape(slots // 4, 8, 4, words, 8, 4).astype(jnp.int64)
    same = jnp.eye(8, dtype=jnp.int64)[None, :, None, None, :, None]
    limbs = jnp.sum(acc * same, axis=(1, 4)).reshape(slots, words * 4)
    in_group = limbs[:, at["mask", "rows"]]

    def value(k, half):
        b = at["value", (k, half)]
        return _from_limbs([limbs[:, b + j] for j in range(4)], in_group)

    sums = []
    for k, t in enumerate(terms):
        if not t.factors:  # COUNT(*)
            sums.append((in_group, None))
            continue
        total = None
        if "sum" in t.want:
            total = value(k, 0)
            if t.passes_int32:
                total = total + (value(k, 1) << 16)
        sums.append((limbs[:, at["mask", k]], total))
    return in_group, sums


@functools.lru_cache(maxsize=64)
def _group_kernel(preds: Tuple[str, ...], terms: Tuple[_Term, ...],
                  keys: Tuple[str, ...], slots: int, program: str = "wide"):
    """The grouped program for a select list's terms, the columns its
    predicate ranges over and its key columns; XLA keys it further on the
    lane shapes, on the carry's (files, slots, columns) and on whether a
    deletion vector comes with the file. Never on a literal of the
    predicate, never on a dictionary's content: the keys' radices are the
    operand ``layout``.

    One algorithm, the one-hot of a row's group against the aggregated
    values cut into 8-bit limbs, contracted on the matrix unit into int32
    and put together in int64 over ``[slots]`` values (a value's low bytes
    are unsigned: each is sent as ``byte - 128`` and the group's row count
    times 128 is added back; its top byte is signed as it stands; nothing
    rounds), in two schedules. ``program`` says which (`_fits_tiles`
    chooses, from the lanes' extremes): ``tiled`` is one pass over the file
    in a hand-written kernel, nothing a row leaving the chip (`_tile_call`);
    ``wide`` is XLA's, a contraction a term over byte rows in HBM, and
    carries what ``tiled`` does not, a product in int64 (`_wide_sums`).
    Either way the module is ``jit_filter_group_aggregate`` and holds all a
    launch does on the device. Grouped ``min`` / ``max`` have no
    contraction: one masked pass a slot in XLA, under both."""
    ensure_compilation_cache()
    import jax
    import jax.numpy as jnp

    interpret = _off_chip()

    def filter_group_aggregate(lanes, bounds, n, keep, layout, at, carry):
        # layout: int64[len(keys), 3]: least value, values, stride of a key
        tiled = program == "tiled"
        if not tiled or any(set(t.want) - {"sum"} for t in terms):
            live = _live_rows(jnp, lanes, preds, bounds, n, keep)
            gid = _group_ids(jnp, lanes, keys, layout)
        in_group, sums = _tiled_sums(
            jnp, lanes, preds, terms, keys, slots, bounds, n, keep, layout,
            interpret) if tiled else _wide_sums(jnp, lanes, terms, live, gid, slots)
        out = [in_group]
        for t, (count, total) in zip(terms, sums):
            out.append(count)
            for name in t.want:
                if name == "sum":
                    out.append(total)
                    continue
                # min / max have no contraction: one masked pass a slot
                m, x = _product(jnp, lanes, t.factors, live, t.wide_at)
                fill, pick = (_I64_MAX, jnp.min) if name == "min" \
                    else (_I64_MIN, jnp.max)
                x64 = x.astype(jnp.int64)
                out.append(jnp.stack([
                    pick(jnp.where(m & (gid == g), x64, fill))
                    for g in range(slots)]))
        part = jnp.stack(out, axis=1)                      # [slots, columns]
        zero = jnp.zeros((), at.dtype)
        return jax.lax.dynamic_update_slice(carry, part[None], (at, zero, zero))

    return jax.jit(filter_group_aggregate)


@functools.lru_cache(maxsize=1024)
def _rows_on_device(n: int):
    """A file's row count as a device scalar, kept: handed to the program
    as a host number it is one blocking upload a launch (0.29 ms of a
    0.53 ms launch on a v5e's host, 15 launches a query; PERF.md, PR 27)."""
    return link.to_device(np.int32(n))


@functools.lru_cache(maxsize=1024)
def _layout_on_device(layout: Tuple[Tuple[int, int, int], ...]):
    """A file's key radices as a device array, kept for the same reason."""
    return link.to_device(np.array(layout, np.int64).reshape(-1, 3))


@functools.lru_cache(maxsize=64)
def _zeros_on_device(shape: Tuple[int, ...]):
    """The grouped carry before its first launch, kept: device arrays do
    not change, so every query starts from the same one."""
    return link.to_device(np.zeros(shape, np.int64))


def _empty_carry(specs: Sequence[AggregateSpec]) -> np.ndarray:
    return np.array([0, 0, _I64_MAX, _I64_MIN] * len(specs), np.int64)


# -- what the select list and the predicate have to look like -------------------


def _strip(e):
    while isinstance(e, ir.Alias):
        e = e.child
    return e


def _flatten(e) -> List[Any]:
    e = _strip(e)
    if isinstance(e, ir.Mul):
        return _flatten(e.left) + _flatten(e.right)
    return [e]


def _is_text(t) -> bool:
    import pyarrow as pa

    return pa.types.is_string(t) or pa.types.is_large_string(t)


def _lane_type(column: str, fields, parts):
    """Arrow type of a column as the scan would decode it; declines on a
    partition column (no file stores it) or an unknown one."""
    from delta_tpu.expr.vectorized import arrow_type_for

    if column not in fields or column in parts:
        raise _Decline("type")
    return arrow_type_for(fields[column])


def _factor(e, types) -> Factor:
    """A lane, or an exact literal plus or minus a lane, in the lane's
    units."""
    import pyarrow as pa

    if isinstance(e, ir.Column):
        return Factor(e.name.lower())
    if type(e) not in (ir.Add, ir.Sub):
        raise _Decline("shape")
    left, right = _strip(e.left), _strip(e.right)
    if isinstance(left, ir.Literal) and isinstance(right, ir.Column):
        lit, col, sign = left, right, (1 if type(e) is ir.Add else -1)
    elif isinstance(left, ir.Column) and isinstance(right, ir.Literal):
        lit, col, sign = right, left, 1
    else:
        raise _Decline("shape")
    t = types[col.name.lower()]
    scaled = jaxeval.decimal_literal_units(
        lit, t.scale if pa.types.is_decimal(t) else 0)
    if scaled is None:
        raise _Decline("shape")  # not an exact number
    units, whole = scaled
    if not whole:
        raise _Decline("type")  # finer than the lane's scale: the host's to type
    if lit is right and type(e) is ir.Sub:
        units = -units
    if abs(units) > _I64_MAX:
        raise _Decline("overflow")
    return Factor(col.name.lower(), sign, units)


def _specs(parsed_items, keys, fields, parts):
    """``(specs, types, inners, layout)`` of a select list: every item an
    aggregate of a product of up to three factors, or one of the group
    ``keys``. ``types`` maps each aggregated column to its Arrow type,
    ``inners`` holds each spec's expression as parsed (the host types it),
    ``layout`` says for each item which key or spec it shows. Declines
    ``shape`` or ``type``."""
    import pyarrow as pa

    specs, inners, layout, types = [], [], [], {}
    for kind, payload, _alias in parsed_items:
        if kind == "col" and payload.lower() in keys:
            layout.append(("key", payload.lower()))
            continue
        if kind != "agg":
            raise _Decline("shape")
        func, inner = payload
        nodes = [] if inner is None else _flatten(inner)
        if len(nodes) > 3:
            raise _Decline("shape")
        for node in nodes:
            for name in ir.references(node):
                types[name.lower()] = _lane_type(name.lower(), fields, parts)
        factors = tuple(_factor(node, types) for node in nodes)
        for f in factors:
            t = types[f.column]
            summable = pa.types.is_integer(t) or (
                pa.types.is_decimal128(t)
                and t.precision <= jaxeval.DECIMAL_LANE_PRECISION)
            if not (summable or (pa.types.is_date32(t) and factors == (f,)
                                 and f == Factor(f.column)
                                 and func in ("count", "min", "max"))):
                raise _Decline("type")
        layout.append(("agg", len(specs)))
        specs.append(AggregateSpec(func, factors))
        inners.append(inner)
    return tuple(specs), types, inners, layout


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


# -- the types the host would give ---------------------------------------------------


_ARITH = {ir.Add: "add", ir.Sub: "subtract", ir.Mul: "multiply"}


def _over_no_rows(e, types, outermost: bool = False):
    """``e`` over no rows, through the kernels `expr/vectorized` calls: the
    type of the result is the host's. Where Arrow refuses an operation (a
    product of decimals past 38 digits) the host evaluates that node row by
    row in ``decimal.Decimal`` and Arrow infers ``decimal128(p, s)`` with
    ``p`` from the values: None for the ``outermost`` node, a decline for
    one inside, whose parent's type would follow the data."""
    import pyarrow as pa
    import pyarrow.compute as pc

    from delta_tpu.expr.vectorized import _numeric_coerce

    e = _strip(e)
    if isinstance(e, ir.Column):
        return pa.array([], types[e.name.lower()])
    if isinstance(e, ir.Literal):
        return pa.scalar(e.value)
    sides = _numeric_coerce(_over_no_rows(e.left, types),
                            _over_no_rows(e.right, types))
    try:
        out = getattr(pc, _ARITH[type(e)])(*sides)
    except (pa.ArrowInvalid, pa.ArrowNotImplementedError, pa.ArrowTypeError):
        if outermost:
            return None
        raise _Decline("type") from None
    if not outermost and pa.types.is_integer(out.type) \
            and out.type.bit_width < 64:
        raise _Decline("type")  # an inner product the host may wrap
    return out


@functools.lru_cache(maxsize=256)
def _host_types(func: str, inner, column_types: Tuple[Tuple[str, Any], ...]):
    """``(type of the aggregated expression, type of the aggregate)`` as the
    host route types them, read off its own kernels over empty arrays;
    ``(None, None)`` where the host types the expression by rows
    (``a * (1 - b) * (1 + c)`` over ``decimal(15,2)``: see
    :func:`_over_no_rows`), and :func:`_typed` answers only what does not
    depend on the values."""
    import pyarrow as pa
    import pyarrow.compute as pc

    expr = _over_no_rows(inner, dict(column_types), outermost=True)
    if expr is None:
        return None, None
    if not (pa.types.is_integer(expr.type) or pa.types.is_decimal(expr.type)
            or pa.types.is_date(expr.type)):
        raise _Decline("type")  # a literal written 1.0 makes the host's a float
    kern = {"sum": pc.sum, "avg": pc.mean, "min": pc.min, "max": pc.max,
            "count": pc.count}
    return expr.type, kern[func](expr).type


def _typed(specs, types, inners):
    """``[(expression type or None, aggregate type)]`` a spec. An expression
    the host types by rows (see :func:`_host_types`) has no type here: its
    ``count`` is int64 and its ``sum`` ``decimal128(38, s)``, whatever the
    values; a mean or an extreme of it would be typed by the data, and is
    declined. Declines too when the host's scale is not the integers' (it
    never should be)."""
    import pyarrow as pa

    out = []
    for spec, inner in zip(specs, inners):
        if not spec.factors:
            out.append((None, pa.int64()))
            continue
        expr_type, out_type = _host_types(
            spec.func, inner, tuple(sorted({c: types[c] for c in spec.cols}.items())))
        scale = sum(types[c].scale for c in spec.cols
                    if pa.types.is_decimal(types[c]))
        if out_type is None:
            if spec.func not in ("count", "sum") or not scale:
                raise _Decline("type")
            out_type = pa.int64() if spec.func == "count" \
                else pa.decimal128(38, scale)
        shown = out_type if expr_type is None else expr_type
        if spec.func != "count" and scale != (
                shown.scale if pa.types.is_decimal(shown) else 0):
            raise _Decline("type")
        out.append((expr_type, out_type))
    return out


# -- the bounds that make the integers exact ------------------------------------------


def _magnitude(ranges: Dict[str, Tuple[int, int]],
               factors: Sequence[Factor]) -> List[int]:
    """The running product's largest magnitude after each factor."""
    out, m = [], 1
    for f in factors:
        lo, hi = ranges[f.column]
        m *= max(abs(f.offset + f.sign * lo), abs(f.offset + f.sign * hi),
                 abs(f.offset))
        out.append(m)
    return out


def _check_overflow(specs, typed, per_file, grouped: bool) -> None:
    """Decline unless every sum stays inside what carries it, whatever rows
    survive, and every product inside the type the host multiplies in (the
    host's int32 product wraps where int64 does not: the routes have to
    agree). The device carries int64: ungrouped, the sum over all files;
    grouped, one file's partial, the files then added in Python integers
    and held to the result's own type."""
    import pyarrow as pa

    for spec, (expr_type, out_type) in zip(specs, typed):
        if not spec.factors or not per_file:
            continue
        width = expr_type.bit_width if expr_type is not None \
            and pa.types.is_integer(expr_type) else 64
        sizes = [_magnitude(f.ranges, spec.factors)[-1] for f in per_file]
        if max(sizes) >= 2 ** (width - 1):
            raise _Decline("overflow")
        if spec.func in ("sum", "avg"):
            partial = [m * f.rows for m, f in zip(sizes, per_file)]
            limit = _EXACT_F64 if pa.types.is_floating(out_type) else \
                10 ** 38 - 1 if grouped and pa.types.is_decimal(out_type) \
                else _I64_MAX
            if sum(partial) > limit or max(partial) > _I64_MAX:
                raise _Decline("overflow")


# -- the answer, typed as the host types it ---------------------------------------


def _column(func: str, types_of, rows):
    """One select item as an Arrow array of the host route's type, a value
    a group; ``rows`` holds each group's ``(sum, count, min, max)``."""
    import pyarrow as pa

    expr_type, out_type = types_of
    if func == "count":
        return pa.array([int(r[1]) for r in rows], pa.int64())
    if expr_type is None and not any(r[1] for r in rows):
        # typed by rows on the host (`_host_types`): with no value at all
        # Arrow infers the null type there, whose sum is an int64
        return pa.array([None] * len(rows), pa.int64())
    units: List[Optional[Any]] = []
    for total, count, low, high in rows:
        total, count = int(total), int(count)
        if count == 0:
            units.append(None)
        elif func == "avg" and pa.types.is_floating(out_type):
            units.append(total / count)
        elif func == "avg":  # a decimal mean rounds half away from zero
            q, r = divmod(abs(total), count)
            units.append((q + (2 * r >= count)) * (1 if total >= 0 else -1))
        else:
            units.append(int({"sum": total, "min": low, "max": high}[func]))
    if pa.types.is_floating(out_type):
        return pa.array(units, out_type)
    if pa.types.is_decimal(out_type):
        return pa.array([None if u is None else
                         Decimal(u).scaleb(-out_type.scale) for u in units],
                        out_type)
    if pa.types.is_date(out_type):
        return pa.array(units, pa.int32()).cast(out_type)
    return pa.array(units, pa.int64()).cast(out_type)


def _key_column(t, values):
    import pyarrow as pa

    if pa.types.is_date(t):
        return pa.array(values, pa.int32()).cast(t)
    if pa.types.is_integer(t):
        return pa.array(values, pa.int64()).cast(t)
    return pa.array(values, t)


# -- groups ------------------------------------------------------------------------


def _terms(specs, per_file) -> Tuple[Tuple[_Term, ...], List[int]]:
    """The distinct products of the select list, each with what is wanted of
    it and the widths its values need over these files; and each spec's
    term."""
    wants: Dict[Tuple[Factor, ...], List[str]] = {}
    for spec in specs:
        have = wants.setdefault(spec.factors, [])
        name = "sum" if spec.func == "avg" else spec.func
        if name != "count" and name not in have:
            have.append(name)
    terms = []
    for factors, want in wants.items():
        sizes = [max(ms) for ms in zip(*(
            _magnitude(f.ranges, factors) for f in per_file))] \
            if per_file and factors else []
        wide_at = next((j for j, m in enumerate(sizes) if m >= 2 ** 31),
                       len(sizes))
        # the top byte is signed: a value below 2^(8 nbytes - 1) in size
        nbytes = max(-(-(max(sizes, default=0).bit_length() + 1) // 8), 1)
        terms.append(_Term(factors, tuple(sorted(want)), wide_at, nbytes))
    order = list(wants)
    return tuple(terms), [order.index(spec.factors) for spec in specs]


def _key_layout(f: _FileLanes, keys):
    """``(least value, values, stride)`` of each key lane in this file, and
    the slots the file's groups take: a lane of dictionary codes holds its
    dictionary's values, an integer or date lane (or a string column the
    file predates: all NULL) the range it spans, which bounds the distinct
    ones. A NULL key is the digit ``values``: one more slot a key, but for
    a dictionary whose codes show that the lane holds none (a NULL's code
    is -1, the lane's least)."""
    layout, stride = [], 1
    for c in keys:
        lo, hi = f.ranges[c]
        if f.codes[c] is not None:
            size = len(f.codes[c])
            lo, null = 0, not size or lo < 0
        else:
            size, null = hi - lo + 1, True
        layout.append((lo, size, stride))
        stride *= size + null
    return tuple(layout), stride


def _fits_tiles(terms, per_file, columns) -> bool:
    """Whether the tile kernel (`_tile_call`) can answer, from the lanes' own
    extremes over these files and from nothing else: every lane the query
    reads fits int32 (the kernel has no wider integer), so does every factor
    and every running product but at most the last step's, whose factor is
    then below 2^15 (the product is split in 16-bit halves there); a stream
    of the kernel, an eighth of a padded file, adds at most `_DOT_ROWS` rows
    into an int32; and the validity bits of the lanes fit one word."""
    if not per_file or len(columns) > 30:
        return False
    if max(_lane_rows(f) for f in per_file) > 8 * _DOT_ROWS:
        return False
    # over all the files at once: an extreme of the union is some file's
    union = {c: (min(f.ranges[c][0] for f in per_file),
                 max(f.ranges[c][1] for f in per_file)) for c in columns}
    if any(not _I32_MIN <= v <= _I32_MAX for r in union.values() for v in r):
        return False
    for t in terms:
        own = [_magnitude(union, (factor,))[0] for factor in t.factors]
        if any(m > _I32_MAX for m in own) or t.wide_at < len(own) - 1 \
                or (t.passes_int32 and own[-1] >= 2 ** 15):
            return False
    return True


def _merge_groups(partials, per_file, layouts, keys, terms, spec_term, specs):
    """The files' partials merged **by value**: each file's slot is read back
    to its key values through the file's own dictionary, a NULL key a value
    of its own, and equal keys add in Python integers. Returns the keys in
    order and, a spec, each group's ``(sum, count, min, max)``."""
    columns = _term_columns(terms)
    merged: Dict[Tuple, List[List[int]]] = {}
    for part, f, layout in zip(partials, per_file, layouts):
        names = {c: None if f.codes[c] is None else
                 {code: value for value, code in f.codes[c].items()}
                 for c in keys}
        radix = [nxt[2] // this[2] for this, nxt in zip(layout, layout[1:])] \
            + [part.shape[0]]
        for g in np.flatnonzero(part[:, 0]):
            key = []
            for c, (lo, size, stride), base in zip(keys, layout, radix):
                digit = (int(g) // stride) % base
                key.append(None if digit == size else
                           lo + digit if names[c] is None else names[c][digit])
            row = part[g]
            into = merged.setdefault(tuple(key), [
                [0, 0, _I64_MAX, _I64_MIN] for _ in specs])
            for k, spec in enumerate(specs):
                at = columns[spec_term[k]]
                acc = into[k]
                acc[1] += int(row[at["count"]])
                if "sum" in at:
                    acc[0] += int(row[at["sum"]])
                if "min" in at:
                    acc[2] = min(acc[2], int(row[at["min"]]))
                if "max" in at:
                    acc[3] = max(acc[3], int(row[at["max"]]))
    order = sorted(merged, key=lambda key: tuple((v is None, v) for v in key))
    return order, [[merged[key][k] for key in order] for k in range(len(specs))]


# -- the route -------------------------------------------------------------------


def _lane_bytes(files, columns, fields) -> int:
    """What the lanes of ``files`` take on the device, padded as
    `column_cache.ResidentColumn` pads them; rows from the log's statistics
    (a file without them is reckoned by its size, 64 bytes a row as the
    residual router does)."""
    import pyarrow as pa

    from delta_tpu.expr.vectorized import arrow_type_for

    # a date lane and a string lane's codes are int32, every other int64; a
    # byte of validity each
    types = [arrow_type_for(fields[c]) for c in columns]
    width = sum(5 if pa.types.is_date(t) or _is_text(t) else 9 for t in types)
    total = 0
    for f in files:
        rows = f.num_logical_records
        if rows is None:
            rows = max((f.size or 0) // 64, 1024)
        if f.deletion_vector is not None:
            rows += int(f.deletion_vector.get("cardinality", 0))
        # a byte a row more where a vector comes as a keep mask
        total += column_cache._next_pow2(max(rows, 1), floor=64) \
            * (width + (f.deletion_vector is not None))
    return total


def _keep_mask(f: _FileLanes, table):
    """The file's deletion vector as a row mask on the device, a resident
    lane of the column cache (`column_cache.ensure_keep`: built and
    uploaded once a vector, not once a launch); None for a file that has
    none. ``table`` is ``(cache, log path, data path)``."""
    if f.add.deletion_vector is None:
        return None
    with telemetry.record_operation("delta.columnCache.keepMask",
                                    {"rows": f.rows}) as ev:
        mask, deleted, cached = column_cache.ensure_keep(
            *table, f.add, _lane_rows(f))
        ev.data.update(deleted=deleted, cached=cached)
    return mask


def device_aggregate(snapshot, filters: Sequence[ir.Expression], parsed_items,
                     group_by: Sequence[str] = (), order_keys: Sequence[str] = ()):
    """The select list ``parsed_items`` (`sql/parser._select`) over the rows
    of ``snapshot`` that ``filters`` hold for, computed on the device: one
    row, or with ``group_by`` one row a group, in the order of the keys'
    values (a NULL last). A group key that the select list leaves out and
    ``order_keys`` names comes as one more column after the select list's,
    for the caller to sort by and drop. None, with the reason on the span,
    when the host route has to answer."""
    import pyarrow as pa

    with telemetry.record_operation("delta.scan.deviceAggregate") as ev:
        try:
            names, columns = _device_aggregate(snapshot, filters, parsed_items,
                                               group_by, order_keys, ev)
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
            if group_by:
                telemetry.bump_counter("scan.aggregate.grouped")
            if ev.data.get("program") == "tiled":
                telemetry.bump_counter("scan.aggregate.grouped.tiled")
            return pa.Table.from_arrays(columns, names=names)
        telemetry.bump_counter("scan.aggregate.declined")
        return None


def _group_keys(snapshot, group_by, fields, parts) -> Tuple[Dict[str, str], Dict]:
    """``({key: its name in the schema}, {key: Arrow type})`` in the order
    asked; a key has to be a stored column of a type whose lane numbers
    values: a string, an integer, a date."""
    import pyarrow as pa

    names: Dict[str, str] = {}
    types: Dict[str, Any] = {}
    if not group_by:
        return names, types
    real = {f.name.lower(): f.name for f in snapshot.metadata.schema.fields}
    for g in group_by:
        c = g.strip("`").lower()
        if c not in real or c in names:
            raise _Decline("shape")  # unknown or twice: the host's to say
        t = _lane_type(c, fields, parts)
        if not (_is_text(t) or pa.types.is_integer(t) or pa.types.is_date32(t)):
            raise _Decline("type")
        names[c], types[c] = real[c], t
    return names, types


def _launches(lev, kernel, calls, carry):
    """``kernel(*arguments, carry)`` once for each of ``calls``, the carry
    threaded through. ``calls`` is lazy, so a launch's arguments are made
    between two launches; the stage's event ``lev`` gets ``launches`` and
    ``dispatchUs``, the time inside the calls of ``kernel`` alone: the
    stage's length less that is what the arguments took (`_rows_on_device`,
    `_keep_mask`, `_layout_on_device`). A count, not a span a launch: a span
    costs what a tenth of a launch does."""
    n = inside = 0
    for args in calls:
        t0 = time.perf_counter_ns()
        carry = kernel(*args, carry)
        inside += time.perf_counter_ns() - t0
        n += 1
    lev.data.update(launches=n, dispatchUs=inside // 1000)
    return carry


def _launch_ungrouped(preds, bounds, specs, per_file, table, rows):
    """The ungrouped program once a file, the carry summed on the device;
    one fetch: four slots a spec. Two stages tile the span: ``.launch`` (to
    the last launch enqueued) and ``.fetch`` (the wait for the device and
    the download)."""
    carry = _empty_carry(specs)
    if per_file:
        with telemetry.record_operation("delta.columnCache.aggregate",
                                        {"rows": rows}), enable_x64(), \
                telemetry.span_stages() as stage:
            lev = stage("delta.columnCache.aggregate.launch")
            kernel = _aggregate_kernel(preds, specs)
            dev_bounds, carry = link.to_device(bounds), link.to_device(carry)
            carry = _launches(lev, kernel, (
                (f.env, dev_bounds, _rows_on_device(f.rows),
                 _keep_mask(f, table)) for f in per_file), carry)
            stage("delta.columnCache.aggregate.fetch")
            carry = link.to_host(carry)
    return carry


def _launch_grouped(preds, bounds, terms, keys, per_file, table, rows):
    """The grouped program once a file, each into its own row of the carry;
    one fetch: ``(partials[file, slot, column], each file's key layout, the
    program that ran: `_fits_tiles`)``. Declines ``groups`` when a file's
    keys could number more than ``GROUP_SLOTS``. The span's two stages are
    `_launch_ungrouped`'s."""
    layouts, slots = [], 1
    for f in per_file:
        file_layout, file_slots = _key_layout(f, keys)
        if file_slots > GROUP_SLOTS:
            raise _Decline("groups")
        layouts.append(file_layout)
        slots = max(slots, file_slots)
    slots = -(-slots // 8) * 8  # the program is keyed on it: few distinct counts
    if not per_file:
        return np.zeros((0, slots, 1), np.int64), layouts, None
    program = "tiled" if _fits_tiles(terms, per_file,
                                     sorted(per_file[0].env)) else "wide"
    with telemetry.record_operation("delta.columnCache.aggregate",
                                    {"rows": rows, "program": program}), \
            enable_x64(), telemetry.span_stages() as stage:
        lev = stage("delta.columnCache.aggregate.launch")
        kernel = _group_kernel(preds, terms, keys, slots, program)
        width = 1 + sum(len(c) for c in _term_columns(terms))
        dev_bounds = link.to_device(bounds)
        carry = _launches(lev, kernel, (
            (f.env, dev_bounds, _rows_on_device(f.rows),
             _keep_mask(f, table), _layout_on_device(file_layout),
             _rows_on_device(i))
            for i, (f, file_layout) in enumerate(zip(per_file, layouts))),
            # the program is keyed on the carry's shape: a row a file, in
            # power-of-two counts, so a table that gains a file a commit
            # compiles once a doubling and not once a commit
            _zeros_on_device((column_cache._next_pow2(len(per_file), floor=8),
                              slots, width)))
        stage("delta.columnCache.aggregate.fetch")
        return link.to_host(carry)[:len(per_file)], layouts, program


@contextlib.contextmanager
def _stage(op_type: str):
    """A child span of the query's that a decline passes through unmarked:
    a decline is the route's answer (`device_aggregate` writes it on the
    query's span), not an error of the stage it was reached in."""
    declined = None
    with telemetry.record_operation(op_type) as ev:
        try:
            yield ev
        except _Decline as d:
            declined = d
    if declined is not None:
        raise declined


def _device_aggregate(snapshot, filters, parsed_items, group_by, order_keys, ev):
    from delta_tpu.ops import pruning

    if not column_cache.column_cache_enabled():
        raise _Decline("off")
    from delta_tpu.expr.synthesis import schema_types

    # two stages name what the query's span holds beside planning, the
    # launches and the merge of the groups
    with _stage("delta.scan.deviceAggregate.resolve"):
        metadata = snapshot.metadata
        fields = schema_types(metadata)
        parts = {c.lower() for c in metadata.partition_columns}
        key_names, key_types = _group_keys(snapshot, group_by, fields, parts)
        keys = tuple(key_names)
        specs, types, inners, layout = _specs(parsed_items, key_names, fields,
                                              parts)
        typed = _typed(specs, types, inners)
    scan = pruning.files_for_scan(snapshot, list(filters))
    with _stage("delta.scan.deviceAggregate.lanes") as lev:
        preds, bounds = _ranges(
            ir.and_all(scan.data_filters) if scan.data_filters else None,
            fields, metadata.partition_columns)
        need = sorted(set(preds) | set(keys) | {c for s in specs for c in s.cols})
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
                mask = add.deletion_vector is not None and cache.get(
                    log_path, add.path, column_cache.KEEP)
                held += mask.nbytes if mask else 0
            else:
                cold.append(add)
        if cold and held + _lane_bytes(cold, need, fields) > column_cache.lane_budget():
            raise _Decline("budget")
        counters = {"hits": 0, "misses": 0, "coldBytes": 0}
        per_file: List[_FileLanes] = []
        for add in scan.files:
            lanes = column_cache._ensure_lanes(cache, log_path, data_path, add,
                                               need, cache.epoch(log_path),
                                               counters)
            if lanes is None or any(e.dict_codes is not None
                                    for c, e in lanes.items() if c not in keys):
                raise _Decline("type")
            per_file.append(_FileLanes(
                add, {c: (e.values, e.valid) for c, e in lanes.items()},
                max(e.n for e in lanes.values()),
                {c: (e.lo, e.hi) for c, e in lanes.items()},
                {c: lanes[c].dict_codes for c in keys}))
        for name in ("hits", "misses"):
            if counters[name]:
                telemetry.bump_counter(f"columnCache.{name}", counters[name])
        _check_overflow(specs, typed, per_file, bool(keys))
        rows = sum(f.rows for f in per_file)
        ev.data.update(files=len(per_file), rows=rows, hits=counters["hits"],
                       misses=counters["misses"],
                       vectors=sum(f.add.deletion_vector is not None
                                   for f in per_file),
                       laneShapes=sorted({_lane_rows(f) for f in per_file}))
        lev.data.update(files=len(per_file), lanes=len(per_file) * len(need))
        names = [alias if kind == "agg" else alias or payload
                 for kind, payload, alias in parsed_items]
        if keys:
            terms, spec_term = _terms(specs, per_file)
    if not keys:
        carry = _launch_ungrouped(preds, bounds, specs, per_file,
                                  (cache, log_path, data_path), rows)
        return names, [_column(spec.func, types_of, [carry[_SLOTS * k:_SLOTS * (k + 1)]])
                       for k, (spec, types_of) in enumerate(zip(specs, typed))]
    partials, layouts, program = _launch_grouped(
        preds, bounds, terms, keys, per_file, (cache, log_path, data_path), rows)
    with telemetry.record_operation("delta.scan.deviceAggregate.groups") as gev:
        order, slots_of = _merge_groups(partials, per_file, layouts, keys,
                                        terms, spec_term, specs)
        key_arrays = {c: _key_column(key_types[c], [key[i] for key in order])
                      for i, c in enumerate(keys)}
        columns = [key_arrays[ref] if kind == "key" else
                   _column(specs[ref].func, typed[ref], slots_of[ref])
                   for kind, ref in layout]
        shown = {n.lower() for n in names}
        for c in keys:  # a key only ORDER BY names rides along, the last
            if c not in shown and c in order_keys:
                names.append(key_names[c])
                columns.append(key_arrays[c])
        gev.data.update(groups=len(order), files=len(per_file))
    ev.data.update(groups=len(order), groupColumns=list(keys), program=program)
    return names, columns
