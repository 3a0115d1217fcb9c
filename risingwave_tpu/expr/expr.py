"""Vectorized expression engine.

Counterpart of the reference's ``Expression::{eval, eval_row}`` engine
(reference: src/expr/src/expr/mod.rs:85-126 and the ~40 scalar-function
modules under src/expr/src/vector_op/). Here an expression is a small static
tree whose ``eval(chunk) -> Column`` is pure jnp over column arrays — the
whole tree inlines into the enclosing jitted operator step, so XLA fuses the
expression with the operator (no interpreter at runtime, unlike the
reference's boxed-trait-object evaluation).

Null semantics are SQL three-valued logic: masks propagate through strict
functions; AND/OR use Kleene logic; CASE/COALESCE/IS NULL handle masks
explicitly.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp

from ..common.chunk import Column, StreamChunk
from ..common.types import DataType, Schema, TypeKind


class Expr:
    """Base class. Subclasses are immutable, hashable plan-time objects."""

    #: result logical type — set by each subclass
    type: DataType

    def eval(self, chunk: StreamChunk) -> Column:
        raise NotImplementedError

    # operator sugar for plan building / tests
    def __add__(self, o): return call("add", self, _lit(o))
    def __sub__(self, o): return call("subtract", self, _lit(o))
    def __mul__(self, o): return call("multiply", self, _lit(o))
    def __truediv__(self, o): return call("divide", self, _lit(o))
    def __mod__(self, o): return call("modulus", self, _lit(o))
    def __eq__(self, o): return call("equal", self, _lit(o))  # type: ignore[override]
    def __ne__(self, o): return call("not_equal", self, _lit(o))  # type: ignore[override]
    def __lt__(self, o): return call("less_than", self, _lit(o))
    def __le__(self, o): return call("less_than_or_equal", self, _lit(o))
    def __gt__(self, o): return call("greater_than", self, _lit(o))
    def __ge__(self, o): return call("greater_than_or_equal", self, _lit(o))
    def __and__(self, o): return call("and", self, _lit(o))
    def __or__(self, o): return call("or", self, _lit(o))
    def __invert__(self): return call("not", self)
    def __hash__(self):  # keep Expr usable as dict key despite __eq__ override
        return id(self)


def _lit(v) -> "Expr":
    return v if isinstance(v, Expr) else Literal.infer(v)


@dataclasses.dataclass(frozen=True, eq=False)
class InputRef(Expr):
    """Reference to input column ``index`` (reference: expr/expr_input_ref.rs)."""

    index: int
    type: DataType

    def eval(self, chunk: StreamChunk) -> Column:
        return chunk.columns[self.index]


@dataclasses.dataclass(frozen=True, eq=False)
class Literal(Expr):
    value: Any
    type: DataType

    @staticmethod
    def infer(v: Any) -> "Literal":
        from ..common import types as T
        if isinstance(v, bool):
            return Literal(v, T.BOOL)
        if isinstance(v, int):
            return Literal(v, T.INT64)
        if isinstance(v, float):
            return Literal(v, T.FLOAT64)
        if isinstance(v, str):
            return Literal(v, T.VARCHAR)
        if v is None:
            return Literal(None, T.INT64)
        raise TypeError(f"cannot infer literal type for {v!r}")

    def eval(self, chunk: StreamChunk) -> Column:
        cap = chunk.capacity
        if self.value is None:
            data = jnp.zeros(cap, self.type.dtype)
            return Column(data, jnp.zeros(cap, jnp.bool_))
        phys = self.type.to_physical(self.value)
        return Column(
            jnp.full(cap, phys, self.type.dtype), jnp.ones(cap, jnp.bool_)
        )


# ---------------------------------------------------------------------------
# Scalar function registry
# ---------------------------------------------------------------------------

#: name -> (impl, type_infer). impl(datas, masks, out_type) -> (data, mask).
_REGISTRY: dict[str, tuple[Callable, Callable]] = {}


def register(name: str, type_infer: Callable[[Sequence[DataType]], DataType]):
    def deco(fn):
        _REGISTRY[name] = (fn, type_infer)
        return fn
    return deco


@dataclasses.dataclass(frozen=True, eq=False)
class FunctionCall(Expr):
    name: str
    args: tuple[Expr, ...]
    type: DataType

    def eval(self, chunk: StreamChunk) -> Column:
        impl, _ = _REGISTRY[self.name]
        cols = [a.eval(chunk) for a in self.args]
        data, mask = impl([c.data for c in cols], [c.mask for c in cols], self.type)
        return Column(data, mask)


_DECIMAL_ALIGN_FNS = {
    "add", "subtract", "modulus", "equal", "not_equal", "less_than",
    "less_than_or_equal", "greater_than", "greater_than_or_equal",
}


def _decimal_fixup(name: str, args: tuple) -> tuple:
    """Fixed-point scale handling (reference: Decimal arithmetic in
    src/common/src/types/decimal.rs). DECIMAL is a scaled int64; aligned
    scales make +/-/cmp plain int ops; ``multiply`` adds scales (its type
    inference); ``divide`` and any float operand lower decimals to f64."""
    if not any(a.type.kind == TypeKind.DECIMAL for a in args):
        return args
    if name == "divide" or any(a.type.is_float for a in args):
        return tuple(
            cast(a, T.FLOAT64) if a.type.kind == TypeKind.DECIMAL else a
            for a in args)
    from ..common.types import decimal as _dec
    s = max(a.type.scale for a in args)

    def align(a):
        return cast(a, _dec(s)) if (a.type.kind == TypeKind.DECIMAL
                                    or a.type.is_integral) else a

    if name in _DECIMAL_ALIGN_FNS or name == "coalesce":
        return tuple(align(a) for a in args)
    if name == "case":
        # value positions only: odd indices + the trailing ELSE
        has_else = len(args) % 2 == 1
        out = list(args)
        for i in range(1, len(args) - (1 if has_else else 0), 2):
            out[i] = align(args[i])
        if has_else:
            out[-1] = align(args[-1])
        return tuple(out)
    return args


_STR_ORDER_FNS = {
    "less_than", "less_than_or_equal", "greater_than",
    "greater_than_or_equal",
}


def call(name: str, *args: Expr) -> FunctionCall:
    if name not in _REGISTRY:
        raise KeyError(f"unknown function {name!r}")
    args = _decimal_fixup(name, tuple(args))
    # Ordering comparisons on VARCHAR/BYTEA compare lexicographic *ranks*,
    # never raw dictionary ids (ids are insertion-ordered — reference order
    # semantics: src/common/src/util/memcmp_encoding.rs). The str_ variant
    # fetches ONE rank table after both operands are evaluated, so operand
    # evaluation that interns new strings (literals, string functions)
    # cannot skew the two sides' rank spaces. Equality stays on ids
    # (bijective with strings).
    if name in _STR_ORDER_FNS and all(a.type.is_string for a in args):
        name = "str_" + name
    _, infer = _REGISTRY[name]
    out_type = infer([a.type for a in args])
    return FunctionCall(name, tuple(args), out_type)


def col(index: int, type: DataType) -> InputRef:
    return InputRef(index, type)


def input_refs(schema: Schema) -> list[InputRef]:
    return [InputRef(i, f.type) for i, f in enumerate(schema)]


# -- type inference helpers --------------------------------------------------

from ..common import types as T  # noqa: E402

_NUM_ORDER = [
    TypeKind.INT16, TypeKind.INT32, TypeKind.INT64, TypeKind.DECIMAL,
    TypeKind.FLOAT32, TypeKind.FLOAT64,
]


def _promote(ts: Sequence[DataType]) -> DataType:
    """Widest numeric type; a non-numeric operand (timestamp/date/interval
    arithmetic) wins regardless of position."""
    for t in ts:
        if t.kind not in _NUM_ORDER:
            return t
    best = ts[0]
    for t in ts[1:]:
        if t.kind == best.kind:
            if t.kind == TypeKind.DECIMAL and t.scale > best.scale:
                best = t
            continue
        if _NUM_ORDER.index(t.kind) > _NUM_ORDER.index(best.kind):
            best = t
    return best


def _t_bool(ts): return T.BOOL
def _t_same(ts): return _promote(ts)
def _t_first(ts): return ts[0]
def _t_float(ts): return T.FLOAT64
def _t_int64(ts): return T.INT64


def _strict_mask(masks):
    m = masks[0]
    for mm in masks[1:]:
        m = m & mm
    return m


def _binary(fn):
    def impl(datas, masks, out_type):
        a, b = datas
        ct = jnp.result_type(a.dtype, b.dtype)
        return fn(a.astype(ct), b.astype(ct)).astype(out_type.dtype), _strict_mask(masks)
    return impl


def _unary(fn):
    def impl(datas, masks, out_type):
        return fn(datas[0]).astype(out_type.dtype), masks[0]
    return impl


def _cmp(fn):
    def impl(datas, masks, out_type):
        a, b = datas
        ct = jnp.result_type(a.dtype, b.dtype)
        return fn(a.astype(ct), b.astype(ct)), _strict_mask(masks)
    return impl


def _t_mul(ts):
    """Fixed-point product: scales add (decimal(s1) * decimal(s2) →
    decimal(s1+s2)); mixed float operands were lowered by _decimal_fixup."""
    decs = [t for t in ts if t.kind == TypeKind.DECIMAL]
    if decs:
        return T.decimal(sum(t.scale for t in decs))
    return _promote(ts)


# arithmetic (reference: src/expr/src/vector_op/arithmetic_op.rs)
register("add", _t_same)(_binary(jnp.add))
register("subtract", _t_same)(_binary(jnp.subtract))
register("multiply", _t_mul)(_binary(jnp.multiply))
register("neg", _t_first)(_unary(jnp.negative))
register("abs", _t_first)(_unary(jnp.abs))


@register("divide", _t_same)
def _divide(datas, masks, out_type):
    a, b = datas
    mask = _strict_mask(masks) & (b != 0)  # div-by-zero -> NULL (SQL raises; we null)
    safe_b = jnp.where(b == 0, jnp.ones_like(b), b)
    if out_type.is_float:
        r = a.astype(out_type.dtype) / safe_b.astype(out_type.dtype)
    else:
        # SQL integer division truncates toward zero (lax.div is C-style),
        # unlike python/jnp floor division.
        ct = jnp.result_type(a.dtype, b.dtype)
        r = jax.lax.div(a.astype(ct), safe_b.astype(ct)).astype(out_type.dtype)
    return r, mask


@register("modulus", _t_same)
def _modulus(datas, masks, out_type):
    a, b = datas
    mask = _strict_mask(masks) & (b != 0)
    safe_b = jnp.where(b == 0, jnp.ones_like(b), b)
    # SQL modulus takes the dividend's sign (C-style rem), not jnp.mod's
    ct = jnp.result_type(a.dtype, b.dtype)
    return jax.lax.rem(a.astype(ct), safe_b.astype(ct)).astype(out_type.dtype), mask


# comparison (reference: src/expr/src/vector_op/cmp.rs)
register("equal", _t_bool)(_cmp(jnp.equal))
register("not_equal", _t_bool)(_cmp(jnp.not_equal))
register("less_than", _t_bool)(_cmp(jnp.less))
register("less_than_or_equal", _t_bool)(_cmp(jnp.less_equal))
register("greater_than", _t_bool)(_cmp(jnp.greater))
register("greater_than_or_equal", _t_bool)(_cmp(jnp.greater_equal))


# Kleene AND/OR (reference: src/expr/src/vector_op/conjunction.rs)
@register("and", _t_bool)
def _and(datas, masks, out_type):
    a, b = datas
    ma, mb = masks
    av = a & ma
    bv = b & mb
    false_a = ma & ~a
    false_b = mb & ~b
    result = av & bv
    known = (ma & mb) | false_a | false_b
    return result, known


@register("or", _t_bool)
def _or(datas, masks, out_type):
    a, b = datas
    ma, mb = masks
    true_a = ma & a
    true_b = mb & b
    result = true_a | true_b
    known = (ma & mb) | true_a | true_b
    return result, known


@register("not", _t_bool)
def _not(datas, masks, out_type):
    return ~datas[0], masks[0]


# null handling
@register("is_null", _t_bool)
def _is_null(datas, masks, out_type):
    return ~masks[0], jnp.ones_like(masks[0])


@register("is_not_null", _t_bool)
def _is_not_null(datas, masks, out_type):
    return masks[0], jnp.ones_like(masks[0])


@register("coalesce", _t_first)
def _coalesce(datas, masks, out_type):
    data = jnp.zeros_like(datas[0]).astype(out_type.dtype)
    mask = jnp.zeros_like(masks[0])
    # iterate last-arg-first so the first non-null argument wins
    for d, m in zip(reversed(datas), reversed(masks)):
        data = jnp.where(m, d.astype(out_type.dtype), data)
        mask = mask | m
    return data, mask


# conditional: case(cond1, val1, cond2, val2, ..., else_val)
@register("case", lambda ts: ts[1])
def _case(datas, masks, out_type):
    n = len(datas)
    has_else = n % 2 == 1
    if has_else:
        data = datas[-1].astype(out_type.dtype)
        mask = masks[-1]
        pairs = (n - 1) // 2
    else:
        data = jnp.zeros_like(datas[1]).astype(out_type.dtype)
        mask = jnp.zeros_like(masks[0])
        pairs = n // 2
    for i in reversed(range(pairs)):
        cond = datas[2 * i] & masks[2 * i]
        data = jnp.where(cond, datas[2 * i + 1].astype(out_type.dtype), data)
        mask = jnp.where(cond, masks[2 * i + 1], mask)
    return data, mask


# cast
@dataclasses.dataclass(frozen=True, eq=False)
class Cast(Expr):
    arg: Expr
    type: DataType

    def eval(self, chunk: StreamChunk) -> Column:
        c = self.arg.eval(chunk)
        src, dst = self.arg.type, self.type
        data = c.data

        def _round_div(d, factor):
            # PG rounds half away from zero when narrowing fixed point
            f = jnp.asarray(factor, d.dtype)
            half = jnp.where(d >= 0, f // 2, -(f // 2))
            return jax.lax.div(d + half, f)

        if src.kind == TypeKind.DECIMAL and dst.kind == TypeKind.DECIMAL:
            if dst.scale >= src.scale:
                data = data * (10 ** (dst.scale - src.scale))
            else:
                data = _round_div(data, 10 ** (src.scale - dst.scale))
        elif src.kind == TypeKind.DECIMAL and dst.is_float:
            data = data.astype(dst.dtype) / (10 ** src.scale)
        elif src.kind == TypeKind.DECIMAL:
            data = _round_div(data, 10 ** src.scale).astype(dst.dtype)
        elif dst.kind == TypeKind.DECIMAL:
            data = jnp.round(
                data.astype(jnp.float64) * 10 ** dst.scale).astype(jnp.int64)
        elif (src.kind == TypeKind.DATE and dst.kind == TypeKind.TIMESTAMP):
            data = data.astype(jnp.int64) * USECS_PER_DAY
        elif (src.kind == TypeKind.TIMESTAMP and dst.kind == TypeKind.DATE):
            data = (data.astype(jnp.int64) // USECS_PER_DAY).astype(dst.dtype)
        else:
            data = data.astype(dst.dtype)
        return Column(data, c.mask)


def cast(arg: Expr, to: DataType) -> Expr:
    return Cast(arg, to) if arg.type != to else arg


# math
register("round", _t_first)(_unary(jnp.round))
register("floor", _t_first)(_unary(jnp.floor))
register("ceil", _t_first)(_unary(jnp.ceil))


# temporal: epoch-microsecond arithmetic (reference: vector_op/extract.rs,
# tumble_start in vector_op/tumble.rs)
USECS_PER_SEC = 1_000_000
USECS_PER_MIN = 60 * USECS_PER_SEC
USECS_PER_HOUR = 60 * USECS_PER_MIN
USECS_PER_DAY = 24 * USECS_PER_HOUR


@register("tumble_start", lambda ts: T.TIMESTAMP)
def _tumble_start(datas, masks, out_type):
    ts, window = datas
    w = window.astype(jnp.int64)
    safe = jnp.where(w == 0, 1, w)
    return (ts.astype(jnp.int64) // safe) * safe, _strict_mask(masks) & (w != 0)


# (field-specific extract registrations are created on demand by
# make_extract below, keyed on the argument's logical type)


# ---------------------------------------------------------------------------
# Temporal extract family (reference: src/expr/src/vector_op/extract.rs)
# ---------------------------------------------------------------------------
# Vectorized civil-date math (Howard Hinnant's algorithm) — pure integer
# ops, fuses into the surrounding jitted step; no host round trip.


def _civil_from_days(days):
    z = days.astype(jnp.int64) + 719468
    era = z // 146097
    doe = z - era * 146097
    yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
    y = yoe + era * 400
    doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
    mp = (5 * doy + 2) // 153
    d = doy - (153 * mp + 2) // 5 + 1
    m = jnp.where(mp < 10, mp + 3, mp - 9)
    y = y + (m <= 2)
    return y, m, d, doy


def make_extract(field: str, arg: Expr) -> Expr:
    """extract() needs the argument's logical type (date vs timestamp) to
    find the day number — FunctionCall impls only see raw arrays, so the
    binder routes extract through per-(field, type) registered wrappers."""
    field = field.lower()
    t = arg.type
    days_div = 1 if t.kind == TypeKind.DATE else USECS_PER_DAY

    def with_days(fn):
        def impl(datas, masks, out_type):
            days = datas[0].astype(jnp.int64) // days_div
            return fn(days).astype(jnp.int64), masks[0]
        return impl

    def time_part(unit_us, modulo):
        def impl(datas, masks, out_type):
            us = datas[0].astype(jnp.int64)
            return (us % modulo) // unit_us, masks[0]
        return impl

    name = f"__extract_{field}_{t.kind.name.lower()}"
    if name not in _REGISTRY:
        if field == "year":
            impl = with_days(lambda d: _civil_from_days(d)[0])
        elif field == "month":
            impl = with_days(lambda d: _civil_from_days(d)[1])
        elif field == "day":
            impl = with_days(lambda d: _civil_from_days(d)[2])
        elif field == "quarter":
            impl = with_days(lambda d: (_civil_from_days(d)[1] + 2) // 3)
        elif field == "dow":        # Sunday = 0 (PG); 1970-01-01 = Thursday
            impl = with_days(lambda d: (d + 4) % 7)
        elif field == "doy":
            def impl(datas, masks, out_type):
                days = datas[0].astype(jnp.int64) // days_div
                y, m, _, _ = _civil_from_days(days)
                jan1 = _days_from_civil(y, jnp.ones_like(y), jnp.ones_like(y))
                return days - jan1 + 1, masks[0]
        elif field == "epoch":
            if t.kind == TypeKind.DATE:
                def impl(datas, masks, out_type):
                    return (datas[0].astype(jnp.int64)
                            * (USECS_PER_DAY // USECS_PER_SEC)), masks[0]
            else:
                def impl(datas, masks, out_type):
                    return datas[0].astype(jnp.int64) // USECS_PER_SEC, masks[0]
        elif field == "hour":
            impl = time_part(USECS_PER_HOUR, USECS_PER_DAY)
        elif field == "minute":
            impl = time_part(USECS_PER_MIN, USECS_PER_HOUR)
        elif field == "second":
            impl = time_part(USECS_PER_SEC, USECS_PER_MIN)
        else:
            raise KeyError(f"unsupported EXTRACT field {field!r}")
        _REGISTRY[name] = (impl, _t_int64)
    return call(name, arg)


def _days_from_civil(y, m, d):
    y = y - (m <= 2)
    era = y // 400
    yoe = y - era * 400
    doy = (153 * (jnp.where(m > 2, m - 3, m + 9)) + 2) // 5 + d - 1
    doe = yoe * 365 + yoe // 4 - yoe // 100 + doy
    return era * 146097 + doe - 719468


# ---------------------------------------------------------------------------
# String functions over dictionary ids (reference: src/expr/src/vector_op/
# {lower,upper,length,substr,concat_op,like}.rs)
# ---------------------------------------------------------------------------
# VARCHAR columns carry int32 dictionary ids; string *content* lives in the
# host dictionary. These impls compute on the HOST over concrete arrays,
# per UNIQUE id (dictionary-sized work, not row-sized), re-interning
# results — the survey's "varlen strings on device: dictionary-encode at
# ingest, host fallback path for string ops" (SURVEY.md §7). They must
# only run EAGERLY: Project/Filter detect them via ``uses_host_callback``
# and skip jit. Inside a trace the host transfer below raises
# TracerArrayConversionError, loudly.


def _lookup_str(i: int) -> str:
    from ..common.types import GLOBAL_STRING_DICT
    try:
        return GLOBAL_STRING_DICT.lookup(int(i))
    except (KeyError, IndexError):
        return ""


def _intern_str(s: str) -> int:
    from ..common.types import GLOBAL_STRING_DICT
    return GLOBAL_STRING_DICT.intern(s)


def _register_str_to_str(name: str, pyfn):
    """pyfn(str, *scalar_args) -> str; first arg is the id column, the rest
    are broadcast numeric columns. Work is per unique argument tuple
    (dictionary-sized), never per row."""
    def impl(datas, masks, out_type):
        import numpy as np
        cols = [np.asarray(d) for d in datas]    # host transfer (eager only)
        stacked = np.stack([c.astype(np.int64) for c in cols], axis=1)
        uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
        results = np.empty(len(uniq), np.int32)
        for u, tup in enumerate(uniq):
            results[u] = _intern_str(
                pyfn(_lookup_str(tup[0]), *(int(v) for v in tup[1:])))
        return jnp.asarray(results[inverse]), _strict_mask(masks)
    _REGISTRY[name] = (impl, lambda ts: T.VARCHAR)


_register_str_to_str("lower", lambda s: s.lower())
_register_str_to_str("upper", lambda s: s.upper())
_register_str_to_str("trim", lambda s: s.strip())
_register_str_to_str("ltrim", lambda s: s.lstrip())
_register_str_to_str("rtrim", lambda s: s.rstrip())
# PG semantics: the window is [start-1, start-1+n) in VIRTUAL positions —
# a start below 1 consumes length before the string begins
def _substr(s, start, n=None):
    if n is None:
        return s[max(start - 1, 0):]
    return s[max(start - 1, 0):max(start - 1 + n, 0)]


_register_str_to_str("substr", _substr)
_register_str_to_str("substring", _substr)


@register("length", _t_int64)
def _length(datas, masks, out_type):
    import numpy as np
    ids = np.asarray(datas[0])
    uniq, inverse = np.unique(ids, return_inverse=True)
    results = np.array([len(_lookup_str(u)) for u in uniq], np.int64)
    return jnp.asarray(results[inverse]), masks[0]


# regexp functions (reference: src/expr/src/vector_op/regexp.rs). Host
# impls over UNIQUE id tuples (dictionary-sized work), compiled patterns
# cached; eager-only like every dictionary-reading function.

import functools as _functools


@_functools.lru_cache(maxsize=256)
def _compile_re(pattern: str, py_flags: int = 0):
    import re
    return re.compile(pattern, py_flags)


def _re_flags(flags: str) -> int:
    # PG flag letters (ref src/expr/src/vector_op/regexp.rs options parse).
    # 'g' is handled by callers (it selects replace-all, not a re flag).
    import re
    f = 0
    for ch in flags:
        if ch == "i":
            f |= re.IGNORECASE
        elif ch in ("n", "m"):     # PG: newline-sensitive matching
            f |= re.MULTILINE
        elif ch == "s":            # PG: '.' matches newline
            f |= re.DOTALL
        elif ch == "x":
            f |= re.VERBOSE
        elif ch in ("c", "g"):     # 'c' = case-sensitive (the default)
            pass
        else:
            raise ValueError(f"invalid regexp flag: {ch!r}")
    return f


def _register_regexp(name: str, pyfn, type_infer):
    def impl(datas, masks, out_type):
        import numpy as np
        cols = [np.asarray(d).astype(np.int64) for d in datas]
        stacked = np.stack(cols, axis=1)
        uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
        results = np.zeros(len(uniq), out_type.np_dtype)
        valid = np.ones(len(uniq), bool)
        for u, tup in enumerate(uniq):
            strs = [_lookup_str(int(i)) for i in tup]
            r = pyfn(*strs)
            if r is None:                      # SQL NULL (e.g. no match)
                valid[u] = False
            else:
                results[u] = _intern_str(r) if out_type.is_string else r
        return (jnp.asarray(results[inverse]),
                _strict_mask(masks) & jnp.asarray(valid[inverse]))
    _REGISTRY[name] = (impl, type_infer)


_register_regexp("regexp_like",
                 lambda s, p: _compile_re(p).search(s) is not None,
                 _t_bool)
_register_regexp("regexp_count",
                 lambda s, p: len(_compile_re(p).findall(s)),
                 _t_int64)
def _pg_replacement_template(r: str) -> str:
    """Translate a PG replacement string to a Python re.sub template by a
    left-to-right escape scan: \\& (whole match) -> \\g<0>, \\1..\\9 kept,
    \\\\ kept as literal backslash, any other escape taken as the literal
    character (Python's template parser would reject e.g. \\g)."""
    out = []
    i = 0
    while i < len(r):
        c = r[i]
        if c == "\\" and i + 1 < len(r):
            n = r[i + 1]
            if n == "&":
                out.append("\\g<0>")
            elif n.isdigit() or n == "\\":
                out.append(c + n)
            else:
                out.append(n)
            i += 2
        elif c == "\\":                   # trailing lone backslash
            out.append("\\\\")
            i += 1
        else:
            out.append(c)
            i += 1
    return "".join(out)


def _pg_regexp_replace(s, p, r, flags=""):
    # PG semantics (ref src/expr/src/vector_op/regexp.rs): replace only
    # the FIRST match unless the 'g' flag is given; 'i' = case-insensitive.
    count = 0 if "g" in flags else 1
    return _compile_re(p, _re_flags(flags)).sub(
        _pg_replacement_template(r), s, count=count)


def _pg_regexp_match(s, p, flags=""):
    # PG regexp_match returns text[] of captures; until array types exist
    # we return the first capture group when the pattern has groups, else
    # the whole match (closest scalar approximation — divergence documented).
    if "g" in flags:
        raise ValueError(
            "regexp_match does not support the global option")  # as in PG
    m = _compile_re(p, _re_flags(flags)).search(s)
    if m is None:
        return None
    return m.group(1) if m.re.groups else m.group(0)


_register_regexp("regexp_replace", _pg_regexp_replace, lambda ts: T.VARCHAR)
_register_regexp("regexp_match", _pg_regexp_match, lambda ts: T.VARCHAR)


def _register_host_fn(name: str, str_args: tuple, pyfn, type_infer,
                      convert=None):
    """Generic host-tier registration: ``str_args`` marks which positions
    carry dictionary ids (decoded to str); the rest pass as ints. Work is
    per UNIQUE argument tuple over rows whose args are all non-NULL —
    NULL/masked lanes hold dtype sentinels that must never reach pyfn (a
    sentinel 0 position argument would crash split_part, a garbage
    timestamp would overflow to_char). A None result is SQL NULL.
    ``convert(result, out_type)`` maps pyfn's python result to the
    physical scalar (default: intern strings, pass numerics)."""
    if convert is None:
        def convert(r, out_type):
            return _intern_str(r) if out_type.is_string else r

    def impl(datas, masks, out_type):
        import numpy as np
        cols = [np.asarray(d).astype(np.int64) for d in datas]
        in_valid = np.asarray(_strict_mask(masks))
        if in_valid.ndim == 0:
            in_valid = np.full(len(cols[0]), bool(in_valid))
        stacked = np.stack(cols, axis=1)
        stacked[~in_valid] = 0        # collapse masked lanes to one tuple
        uniq, inverse = np.unique(stacked, axis=0, return_inverse=True)
        results = np.zeros(len(uniq), out_type.np_dtype)
        valid = np.ones(len(uniq), bool)
        evaluated = np.zeros(len(uniq), bool)
        evaluated[inverse[in_valid]] = True
        for u, tup in enumerate(uniq):
            if not evaluated[u]:
                valid[u] = False
                continue
            args = [_lookup_str(int(v)) if i in str_args else int(v)
                    for i, v in enumerate(tup)]
            r = pyfn(*args)
            if r is None:
                valid[u] = False
            else:
                results[u] = convert(r, out_type)
        return (jnp.asarray(results[inverse]),
                jnp.asarray(in_valid) & jnp.asarray(valid[inverse]))
    _REGISTRY[name] = (impl, type_infer)


def _split_part(s: str, delim: str, n: int):
    # PG split_part: 1-based field index; negative counts from the end;
    # out-of-range yields '' (ref: src/expr/src/vector_op/split_part.rs)
    if n == 0:
        raise ValueError("field position must not be zero")
    parts = s.split(delim) if delim else [s]
    i = n - 1 if n > 0 else len(parts) + n
    return parts[i] if 0 <= i < len(parts) else ""


_register_host_fn("split_part", (0, 1), _split_part, lambda ts: T.VARCHAR)


def _regexp_match_group(s: str, p: str, n: int):
    """(regexp_match(s, p))[n] — 1-based group of the first match, NULL on
    no match / out-of-range. With no capture groups, [1] is the whole
    match (regexp_match then returns a 1-element array in PG)."""
    m = _compile_re(p).search(s)
    if m is None:
        return None
    if m.re.groups == 0:
        return m.group(0) if n == 1 else None
    if 1 <= n <= m.re.groups:
        return m.group(n)
    return None


_register_host_fn("regexp_match_group", (0, 1), _regexp_match_group,
                  lambda ts: T.VARCHAR)


def _array_access(list_id: int, n: int):
    """1-based element access over a list-dictionary id; out-of-range is
    NULL (PG array subscript semantics)."""
    from ..common.types import GLOBAL_LIST_DICT
    elems = GLOBAL_LIST_DICT.lookup(int(list_id))
    return elems[n - 1] if 1 <= n <= len(elems) else None


_register_host_fn("array_access", (), _array_access,
                  lambda ts: ts[0].elem_type)


# JSONB operators (reference: src/expr/src/vector_op/jsonb_access.rs).
# JSONB values are dictionary ids of canonical JSON text; access parses
# per UNIQUE id (dictionary-sized work), results re-canonicalized.

import json as _json


@_functools.lru_cache(maxsize=4096)
def _jsonb_parse(s: str):
    try:
        return _json.loads(s) if s else None
    except ValueError:
        return None


def _jsonb_canon(v) -> str:
    return _json.dumps(v, separators=(",", ":"), sort_keys=True)


_MISSING = object()    # distinguishes an ABSENT key from a JSON null value


def _jsonb_get(j, key):
    if isinstance(j, dict):
        return j.get(key, _MISSING) if isinstance(key, str) else _MISSING
    if isinstance(j, list) and isinstance(key, int):
        return j[key] if -len(j) <= key < len(j) else _MISSING
    return _MISSING


def _jsonb_access(s: str, key, as_text: bool):
    v = _jsonb_get(_jsonb_parse(s), key)
    if v is _MISSING:
        return None                 # absent key → SQL NULL
    if as_text:
        # ->> maps a present JSON null to SQL NULL (PG semantics)
        if v is None:
            return None
        return v if isinstance(v, str) else _jsonb_canon(v)
    return _jsonb_canon(v)          # -> on a null value yields jsonb 'null'


def _register_jsonb(name, key_is_str, as_text, out_infer):
    str_args = (0, 1) if key_is_str else (0,)
    _register_host_fn(
        name, str_args,
        lambda s, k: _jsonb_access(s, k, as_text), out_infer)


def _t_jsonb(ts):
    from ..common.types import JSONB as _J
    return _J


_register_jsonb("jsonb_get_field", True, False, _t_jsonb)
_register_jsonb("jsonb_get_elem", False, False, _t_jsonb)
_register_jsonb("jsonb_get_field_text", True, True, lambda ts: T.VARCHAR)
_register_jsonb("jsonb_get_elem_text", False, True, lambda ts: T.VARCHAR)


def _jsonb_typeof(s: str):
    v = _jsonb_parse(s)
    if s == "null":
        return "null"
    if v is None:
        return None
    return {dict: "object", list: "array", str: "string", bool: "boolean",
            int: "number", float: "number"}.get(type(v))


_register_host_fn("jsonb_typeof", (0,), _jsonb_typeof,
                  lambda ts: T.VARCHAR)


def _jsonb_array_length(s: str):
    v = _jsonb_parse(s)
    return len(v) if isinstance(v, list) else None


_register_host_fn("jsonb_array_length", (0,), _jsonb_array_length,
                  _t_int64)


def _struct_field(sid: int, fi: int):
    """(struct).field — element fi of the interned field tuple; the
    binder sets the out type from the declared field type (reference
    composite access: src/expr/src/expr/expr_field.rs)."""
    from ..common.types import GLOBAL_LIST_DICT
    fields = GLOBAL_LIST_DICT.lookup(sid)
    return fields[fi] if 0 <= fi < len(fields) else None


_register_host_fn("struct_field", (), _struct_field, _t_int64,
                  convert=lambda r, out_type: out_type.to_physical(r))


@register("array_length", _t_int64)
def _array_length(datas, masks, out_type):
    import numpy as np
    from ..common.types import GLOBAL_LIST_DICT
    ids = np.asarray(datas[0])
    uniq, inverse = np.unique(ids, return_inverse=True)
    results = np.array([len(GLOBAL_LIST_DICT.lookup(int(u))) for u in uniq],
                       np.int64)
    return jnp.asarray(results[inverse]), masks[0]


# to_char over timestamps (reference: src/expr/src/vector_op/to_char.rs —
# a Postgres-pattern subset: YYYY/YY/MM/DD/HH24/HH12/HH/MI/SS/MS/AM/PM;
# numeric patterns match case-insensitively as in PG)

_TO_CHAR_PATTERNS = [
    ("YYYY", lambda dt: f"{dt[0]:04d}"),
    ("YY", lambda dt: f"{dt[0] % 100:02d}"),
    ("MM", lambda dt: f"{dt[1]:02d}"),
    ("DD", lambda dt: f"{dt[2]:02d}"),
    ("HH24", lambda dt: f"{dt[3]:02d}"),
    ("HH12", lambda dt: f"{(dt[3] % 12) or 12:02d}"),
    ("HH", lambda dt: f"{(dt[3] % 12) or 12:02d}"),
    ("MI", lambda dt: f"{dt[4]:02d}"),
    ("SS", lambda dt: f"{dt[5]:02d}"),
    ("MS", lambda dt: f"{dt[6] // 1000:03d}"),
    ("AM", lambda dt: "AM" if dt[3] < 12 else "PM"),
    ("PM", lambda dt: "AM" if dt[3] < 12 else "PM"),
]


@_functools.lru_cache(maxsize=64)
def _to_char_compile(fmt: str):
    """fmt -> [literal | pattern-fn] segments, longest pattern first."""
    segs: list = []
    i = 0
    up = fmt.upper()
    while i < len(fmt):
        for pat, fn in _TO_CHAR_PATTERNS:
            if up.startswith(pat, i):
                segs.append(fn)
                i += len(pat)
                break
        else:
            segs.append(fmt[i])
            i += 1
    return segs


def _to_char(us: int, fmt: str) -> str:
    import datetime
    d = datetime.datetime(1970, 1, 1) + datetime.timedelta(microseconds=us)
    dt = (d.year, d.month, d.day, d.hour, d.minute, d.second, d.microsecond)
    return "".join(seg if isinstance(seg, str) else seg(dt)
                   for seg in _to_char_compile(fmt))


_register_host_fn("to_char", (1,), _to_char, lambda ts: T.VARCHAR)


@register("str_rank", _t_int64)
def _str_rank(datas, masks, out_type):
    """id -> lexicographic rank via the dictionary's rank side table.

    Eager-only (in HOST_CALLBACK_FNS): the table refreshes as strings are
    interned, so it must be fetched fresh per evaluation — baked into a jit
    trace it would go stale and silently mis-order."""
    from ..common.types import GLOBAL_STRING_DICT
    table = GLOBAL_STRING_DICT.device_ranks()
    ids = jnp.clip(datas[0].astype(jnp.int32), 0, table.shape[0] - 1)
    return table[ids], masks[0]


def _str_cmp(fn):
    """String ordering comparison: both ids map through a SINGLE rank-table
    fetch taken after operand evaluation, so in-evaluation interning (a
    literal's first eval, upper()/substr() products) can never put the two
    sides in different rank spaces. Eager-only, like str_rank."""
    def impl(datas, masks, out_type):
        from ..common.types import GLOBAL_STRING_DICT
        table = GLOBAL_STRING_DICT.device_ranks()
        n = table.shape[0]
        a = table[jnp.clip(datas[0].astype(jnp.int32), 0, n - 1)]
        b = table[jnp.clip(datas[1].astype(jnp.int32), 0, n - 1)]
        return fn(a, b), _strict_mask(masks)
    return impl


register("str_less_than", _t_bool)(_str_cmp(jnp.less))
register("str_less_than_or_equal", _t_bool)(_str_cmp(jnp.less_equal))
register("str_greater_than", _t_bool)(_str_cmp(jnp.greater))
register("str_greater_than_or_equal", _t_bool)(_str_cmp(jnp.greater_equal))


@register("concat_op", lambda ts: T.VARCHAR)
def _concat_op(datas, masks, out_type):
    import numpy as np
    a, b = np.asarray(datas[0]), np.asarray(datas[1])
    pairs, inverse = np.unique(np.stack([a, b], axis=1), axis=0,
                               return_inverse=True)
    results = np.array([
        _intern_str(_lookup_str(pa) + _lookup_str(pb)) for pa, pb in pairs],
        np.int32)
    return jnp.asarray(results[inverse]), _strict_mask(masks)


def _like_to_regex(pattern: str) -> "re.Pattern":
    import re
    out = []
    i = 0
    while i < len(pattern):
        ch = pattern[i]
        if ch == "\\" and i + 1 < len(pattern):
            # LIKE's default escape: \% and \_ match literally
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if ch == "%":
            out.append(".*")
        elif ch == "_":
            out.append(".")
        else:
            out.append(re.escape(ch))
        i += 1
    return re.compile("^" + "".join(out) + "$", re.DOTALL)


def _make_like(negated: bool, name: str):
    def impl(datas, masks, out_type):
        import numpy as np
        ids, pat_ids = np.asarray(datas[0]), np.asarray(datas[1])
        pairs, inverse = np.unique(np.stack([ids, pat_ids], axis=1), axis=0,
                                   return_inverse=True)
        rx_cache: dict = {}
        results = np.empty(len(pairs), np.bool_)
        for u, (uid, pid) in enumerate(pairs):
            rx = rx_cache.get(pid)
            if rx is None:
                rx = rx_cache[pid] = _like_to_regex(_lookup_str(pid))
            results[u] = (rx.match(_lookup_str(uid)) is not None) != negated
        return jnp.asarray(results[inverse]), _strict_mask(masks)
    _REGISTRY[name] = (impl, _t_bool)


_make_like(False, "like")
_make_like(True, "not_like")


#: functions computed on the host dictionary — they cannot appear inside
#: a jitted step; operators check ``uses_host_callback`` and evaluate
#: them eagerly
HOST_CALLBACK_FNS = {
    "lower", "upper", "trim", "ltrim", "rtrim", "substr", "substring",
    "length", "concat_op", "like", "not_like",
    "regexp_like", "regexp_count", "regexp_replace", "regexp_match",
    "regexp_match_group", "split_part", "to_char", "array_access",
    "array_length", "struct_field", "jsonb_get_field", "jsonb_get_elem",
    "jsonb_get_field_text", "jsonb_get_elem_text", "jsonb_typeof",
    "jsonb_array_length",
    # not host callbacks, but must run eagerly: they read the live rank table
    "str_rank", "str_less_than", "str_less_than_or_equal",
    "str_greater_than", "str_greater_than_or_equal",
}


def uses_host_callback(e: Expr) -> bool:
    if isinstance(e, FunctionCall):
        return (e.name in HOST_CALLBACK_FNS
                or any(uses_host_callback(a) for a in e.args))
    if isinstance(e, Cast):
        return uses_host_callback(e.arg)
    return False


def eval_many(exprs: Sequence[Expr], chunk: StreamChunk) -> tuple[Column, ...]:
    return tuple(e.eval(chunk) for e in exprs)
