"""RowExpression -> JAX lowering (the bytecode-generation replacement).

Trino compiles row expressions to JVM classes at runtime
(sql/gen/PageFunctionCompiler.java:104 ``compileProjection:167`` /
``compileFilter:374``, ExpressionCompiler.java:63).  Here the same IR lowers
to closures over ``jax.numpy`` ops; wrapping them in ``jax.jit`` hands XLA a
whole operator pipeline to fuse (filter+project collapse into one kernel, the
ScanFilterAndProjectOperator analogue).

Evaluation model:
- every expression evaluates to ``(data, valid)`` — fixed-shape value array +
  optional validity (None == all valid), SQL three-valued logic throughout;
- scalars broadcast: literals stay 0-d until the caller broadcasts;
- **strings never reach the device as bytes**: a varchar expression carries a
  compile-time host-side sorted dictionary; string functions (LIKE, substring,
  upper, ...) are evaluated host-side over the dictionary and become device
  gathers of the precomputed result (`mask[codes]` / `remap[codes]`).  This is
  the TPU-native replacement for Trino's per-row UTF-8 kernels
  (likematcher/DenseDfaMatcher.java, operator/scalar/StringFunctions.java).

Division/modulo by zero currently yields NULL rather than raising
(Trino raises DIVISION_BY_ZERO; a lane-error side channel is a later round).
"""

from __future__ import annotations

import re
import threading
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Any, Callable, Optional, Sequence

import numpy as np

from . import datetime as dt  # noqa: F401  (registers jax config via package)
import jax.numpy as jnp

from ..spi.types import (
    BIGINT,
    BOOLEAN,
    DATE,
    DOUBLE,
    INTEGER,
    TIMESTAMP,
    UNKNOWN,
    VARCHAR,
    ArrayType,
    DecimalType,
    Type,
    is_string,
)
from ..spi.batch import rescale_scaled_int
from ..sql.ir import Call, InputRef, Literal, RowExpression
from ..telemetry import metrics as tm
from ..telemetry import profiler

__all__ = ["CompiledExpression", "compile_expression", "compile_projection"]

Cols = Sequence[tuple[Any, Optional[Any]]]  # per-channel (data, valid)


def _and_valid(a, b):
    if a is None:
        return b
    if b is None:
        return a
    return a & b


def _all_valids(vs):
    out = None
    for v in vs:
        out = _and_valid(out, v)
    return out


# ---------------------------------------------------------------------------
# masked-lane error channel (reference: StandardErrorCode DIVISION_BY_ZERO /
# NUMERIC_VALUE_OUT_OF_RANGE / INVALID_CAST_ARGUMENT).  Vectorized evaluation
# computes every lane of every branch, so errors cannot raise eagerly: an
# erroring op RECORDS a lane mask into the active scope instead, conditionals
# ($if / $and / $or / coalesce) mask the lanes their branch doesn't select,
# and the compiled program reduces the surviving lanes to one error-code
# scalar that the runner checks (batched with the result fetch — a query
# without error-capable ops pays nothing).

DIVISION_BY_ZERO = 1
NUMERIC_OUT_OF_RANGE = 2
INVALID_CAST = 3
SUBQUERY_MULTIPLE_ROWS = 4
ERROR_NAMES = {
    DIVISION_BY_ZERO: "DIVISION_BY_ZERO: division by zero",
    NUMERIC_OUT_OF_RANGE: "NUMERIC_VALUE_OUT_OF_RANGE: value out of range",
    INVALID_CAST: "INVALID_CAST_ARGUMENT: invalid cast",
    SUBQUERY_MULTIPLE_ROWS:
        "SUBQUERY_MULTIPLE_ROWS: scalar subquery returned multiple rows",
}


class QueryError(RuntimeError):
    def __init__(self, code: int):
        super().__init__(ERROR_NAMES.get(code, f"error code {code}"))
        self.code = code


class _ErrState(threading.local):
    acc = None  # list[(code, lane_mask)] while a scope is active
    mask = None  # current conditional lane mask (None = all lanes)


_ERRS = _ErrState()


@contextmanager
def expr_error_scope():
    """Collect (code, lanes) pairs recorded while tracing expression fns.
    Active only inside a compiled program body — evaluation outside any
    scope keeps the legacy NULL-on-error semantics."""
    prev_acc, prev_mask = _ERRS.acc, _ERRS.mask
    _ERRS.acc = acc = []
    _ERRS.mask = None
    try:
        yield acc
    finally:
        _ERRS.acc, _ERRS.mask = prev_acc, prev_mask


@contextmanager
def expr_condition_mask(mask):
    """Lanes where ``mask`` is False cannot raise (unselected branch /
    filtered-out row)."""
    prev = _ERRS.mask
    if mask is not None:
        _ERRS.mask = mask if prev is None else (prev & mask)
    try:
        yield
    finally:
        _ERRS.mask = prev


def _record_error(code: int, lanes) -> None:
    if _ERRS.acc is None:
        return
    if _ERRS.mask is not None:
        lanes = lanes & _ERRS.mask
    _ERRS.acc.append((code, lanes))


def reduce_error_lanes(acc, shape):
    """Combine a scope's recordings into ONE int32 lane array (0 = ok), or
    None when nothing error-capable was traced.  A zero-row shape (empty
    partition / fully-pruned batch) has no lanes that can raise — return
    None so callers never reduce over a zero-size array."""
    if shape and int(shape[0]) == 0:
        return None
    err = None
    for code, lanes in acc:
        lanes = jnp.broadcast_to(lanes, shape)
        e = jnp.where(lanes, jnp.int32(code), jnp.int32(0))
        err = e if err is None else jnp.maximum(err, e)
    return err


def check_error_scalars(scalars) -> None:
    """One batched device fetch; raises QueryError on the worst code."""
    if not scalars:
        return
    from ..exec import syncguard as SG

    codes = [int(c) for c in SG.fetch(list(scalars), "exec.error-scalars")]
    worst = max(codes)
    if worst:
        raise QueryError(worst)


@dataclass
class Lowered:
    type: Type
    dictionary: Optional[np.ndarray]
    fn: Callable[[Cols], tuple[Any, Optional[Any]]]


@dataclass
class CompiledExpression:
    """Public handle: callable on per-channel (data, valid) pairs."""

    type: Type
    dictionary: Optional[np.ndarray]
    _fn: Callable[[Cols], tuple[Any, Optional[Any]]]

    def __call__(self, cols: Cols) -> tuple[Any, Optional[Any]]:
        return self._fn(cols)


# ---------------------------------------------------------------------------
# elementwise numeric helpers


def _trunc_div(a, b):
    """SQL integer division truncates toward zero (jnp // floors)."""
    q = jnp.abs(a) // jnp.abs(b)
    return jnp.where((a < 0) ^ (b < 0), -q, q)


def _round_half_up_div(a, b):
    """Rounded division for decimal rescale: round(a/b) half away from zero."""
    q = (2 * jnp.abs(a) + jnp.abs(b)) // (2 * jnp.abs(b))
    return jnp.where((a < 0) ^ (b < 0), -q, q)


def _decimal_rescale(data, from_scale: int, to_scale: int):
    if to_scale == from_scale:
        return data
    if to_scale > from_scale:
        return data * (10 ** (to_scale - from_scale))
    return _round_half_up_div(data, 10 ** (from_scale - to_scale))


def _scale_of(t: Type) -> int:
    return t.scale if isinstance(t, DecimalType) else 0


def _is_long_dec(t: Type) -> bool:
    return isinstance(t, DecimalType) and t.precision > 18


def _long_dec_transform(col: Lowered, pyfn, out_type: Type) -> Lowered:
    """Exact host transform over a long-decimal dictionary (python ints);
    ``pyfn`` returns a scaled int at out_type.scale or None (NULL, e.g.
    division by zero).  Mirrors the string _dict_transform idiom —
    spi/type/Int128Math.java's role is played by python bignums over the
    (small) dictionary, never per row."""
    vals = [pyfn(int(v)) for v in col.dictionary]
    uniq = sorted({v for v in vals if v is not None} or {0})
    pos = {v: i for i, v in enumerate(uniq)}
    remap = np.array([pos.get(v, 0) for v in vals], dtype=np.int32)
    entry_ok = np.array([v is not None for v in vals])
    newdict = np.empty(len(uniq), dtype=object)
    for i, v in enumerate(uniq):
        newdict[i] = v
    all_ok = bool(entry_ok.all())

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        data = jnp.asarray(remap)[codes]
        if not all_ok:
            ok = jnp.asarray(entry_ok)[codes]
            valid = ok if valid is None else (jnp.asarray(valid) & ok)
        return data, valid

    return Lowered(out_type, newdict, fn)


def _long_dec_literal_value(x: Lowered):
    """Scaled-int value of a long-decimal literal Lowered (or None)."""
    if x.dictionary is not None and len(x.dictionary) == 1 and \
            hasattr(x.fn, "_literal_value"):
        return int(x.fn._literal_value)
    return None


def _long_arith_value(name: str, va, sa, vb, sb, os: int):
    """Exact scaled-int arithmetic (python bignums), HALF_UP rounding.
    Runs under an 80-digit context: the default 28-digit context would
    silently round wide decimals."""
    import decimal as _d

    with _d.localcontext() as ctx:
        ctx.prec = 80
        return _long_arith_ctx(name, va, sa, vb, sb, os)


def _long_arith_ctx(name: str, va, sa, vb, sb, os: int):
    import decimal as _d

    A = _d.Decimal(va).scaleb(-sa)
    B = _d.Decimal(vb).scaleb(-sb)
    if name == "add":
        r = A + B
    elif name == "subtract":
        r = A - B
    elif name == "multiply":
        r = A * B
    elif name == "divide":
        if B == 0:
            return None
        r = A / B
    else:  # modulus
        if B == 0:
            return None
        r = A % B
    return int(r.scaleb(os).quantize(0, rounding=_d.ROUND_HALF_UP))


def _arith_handler(name: str):
    def handler(out_type: Type, args: list[Lowered]) -> Lowered:
        a, b = args
        if _is_long_dec(a.type) or _is_long_dec(b.type) or _is_long_dec(out_type):
            if getattr(a.fn, "_literal_null", False) or getattr(
                    b.fn, "_literal_null", False):
                # NULL operand: the whole expression is NULL (Trino
                # three-valued arithmetic), no transform needed
                d0 = None
                if _is_long_dec(out_type):
                    d0 = np.empty(1, dtype=object)
                    d0[0] = 0

                def fn_null(cols: Cols):
                    return (jnp.zeros((), out_type.storage_dtype),
                            jnp.zeros((), bool))

                return Lowered(out_type, d0, fn_null)
            os = _scale_of(out_type)
            sa, sb = _scale_of(a.type), _scale_of(b.type)
            la, lb = _long_dec_literal_value(a), _long_dec_literal_value(b)
            # literal sides that are short decimals/integers also qualify
            if la is None and hasattr(a.fn, "_literal_value") and not _is_long_dec(a.type):
                la = int(a.fn._literal_value)
            if lb is None and hasattr(b.fn, "_literal_value") and not _is_long_dec(b.type):
                lb = int(b.fn._literal_value)
            if _is_long_dec(a.type) and a.dictionary is not None and lb is not None:
                out = _long_dec_transform(
                    a, lambda v: _long_arith_value(name, v, sa, lb, sb, os),
                    out_type)
                return _and_extra_valid(out, [b])
            if _is_long_dec(b.type) and b.dictionary is not None and la is not None:
                out = _long_dec_transform(
                    b, lambda v: _long_arith_value(name, la, sa, v, sb, os),
                    out_type)
                return _and_extra_valid(out, [a])
            raise NotImplementedError(
                "long-decimal arithmetic between two columns is not "
                "supported (dictionary-encoded int128 path; rewrite with a "
                "literal operand or cast to double)")

        def fn(cols: Cols):
            (av, avalid), (bv, bvalid) = a.fn(cols), b.fn(cols)
            valid = _and_valid(avalid, bvalid)
            if isinstance(out_type, DecimalType):
                os = out_type.scale
                if name in ("add", "subtract"):
                    av2 = _decimal_rescale(av, _scale_of(a.type), os)
                    bv2 = _decimal_rescale(bv, _scale_of(b.type), os)
                    data = av2 + bv2 if name == "add" else av2 - bv2
                elif name == "multiply":
                    data = _decimal_rescale(
                        av * bv, _scale_of(a.type) + _scale_of(b.type), os
                    )
                elif name == "divide":
                    # value = a/b at scale os:  round(a * 10^(os - sa + sb) / b)
                    shift = os - _scale_of(a.type) + _scale_of(b.type)
                    num = av * (10**shift) if shift >= 0 else _round_half_up_div(av, 10**-shift)
                    safe_b = jnp.where(bv == 0, 1, bv)
                    data = _round_half_up_div(num, safe_b)
                    _record_error(DIVISION_BY_ZERO, (bv == 0) if valid is None
                                  else ((bv == 0) & valid))
                    valid = _and_valid(valid, bv != 0)
                else:  # modulus
                    s = max(_scale_of(a.type), _scale_of(b.type))
                    av2 = _decimal_rescale(av, _scale_of(a.type), s)
                    bv2 = _decimal_rescale(bv, _scale_of(b.type), s)
                    safe_b = jnp.where(bv2 == 0, 1, bv2)
                    data = av2 - _trunc_div(av2, safe_b) * bv2
                    _record_error(DIVISION_BY_ZERO, (bv2 == 0) if valid is None
                                  else ((bv2 == 0) & valid))
                    valid = _and_valid(valid, bv2 != 0)
                return data, valid
            dtype = out_type.storage_dtype
            av = av.astype(dtype)
            bv = bv.astype(dtype)
            is_int = bool(np.issubdtype(np.dtype(dtype), np.integer))

            def ovf_err(ovf):
                _record_error(NUMERIC_OUT_OF_RANGE,
                              ovf if valid is None else (ovf & valid))

            signed = bool(np.issubdtype(np.dtype(dtype), np.signedinteger))
            if name == "add":
                data = av + bv
                if signed:  # wraparound flips the sign against both operands
                    ovf_err(((av ^ data) & (bv ^ data)) < 0)
            elif name == "subtract":
                data = av - bv
                if signed:
                    ovf_err(((av ^ bv) & (av ^ data)) < 0)
            elif name == "multiply":
                data = av * bv
                if signed:  # wrapped product no longer divides back
                    safe_a = jnp.where(av == 0, 1, av)
                    ovf_err((av != 0) & (_trunc_div(data, safe_a) != bv))
            elif name == "divide":
                if is_int:
                    safe_b = jnp.where(bv == 0, 1, bv)
                    data = _trunc_div(av, safe_b)
                    _record_error(DIVISION_BY_ZERO, (bv == 0) if valid is None
                                  else ((bv == 0) & valid))
                    valid = _and_valid(valid, bv != 0)
                else:
                    safe_b = jnp.where(bv == 0, 1.0, bv)
                    data = av / safe_b
                    if (isinstance(a.type, DecimalType)
                            or isinstance(b.type, DecimalType)
                            or getattr(a.fn, "_from_decimal", False)
                            or getattr(b.fn, "_from_decimal", False)):
                        # decimal division folded to double still carries
                        # exact-arithmetic semantics: /0 raises (Trino
                        # DIVISION_BY_ZERO); pure double /0 stays NULL
                        _record_error(
                            DIVISION_BY_ZERO, (bv == 0) if valid is None
                            else ((bv == 0) & valid))
                    valid = _and_valid(valid, bv != 0)
            else:  # modulus
                safe_b = jnp.where(bv == 0, 1, bv)
                if is_int:
                    data = av - _trunc_div(av, safe_b) * bv
                    _record_error(DIVISION_BY_ZERO, (bv == 0) if valid is None
                                  else ((bv == 0) & valid))
                else:
                    data = av - jnp.trunc(av / safe_b) * bv
                valid = _and_valid(valid, bv != 0)
            return data, valid

        return Lowered(out_type, None, fn)

    return handler


# ---------------------------------------------------------------------------
# comparisons (dictionary-aware)

_CMP = {
    "eq": jnp.equal,
    "ne": jnp.not_equal,
    "lt": jnp.less,
    "le": jnp.less_equal,
    "gt": jnp.greater,
    "ge": jnp.greater_equal,
}


def _dicts_equal(a: Optional[np.ndarray], b: Optional[np.ndarray]) -> bool:
    if a is None or b is None:
        return False
    return a is b or (a.shape == b.shape and (a == b).all())


def _cmp_dict_literal(name: str, col: Lowered, lit_value: str):
    """Compare dictionary codes against a string literal using only the
    host-side sorted dictionary (order-correct by construction)."""
    d = col.dictionary
    if isinstance(lit_value, tuple):
        # array dictionary: numpy would treat a tuple needle as an array of
        # elements, and entries sort by _canon_key not raw order — linear
        # scan (only eq/ne reach here for arrays; dictionaries are small)
        hits = [i for i, v in enumerate(d) if v == lit_value]
        lo = hits[0] if hits else 0
        hi = lo + 1 if hits else 0
    else:
        lo = int(np.searchsorted(d, lit_value, side="left"))
        hi = int(np.searchsorted(d, lit_value, side="right"))
    present = lo < hi

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        if name == "eq":
            data = (codes == lo) if present else jnp.zeros_like(codes, dtype=bool)
        elif name == "ne":
            data = (codes != lo) if present else jnp.ones_like(codes, dtype=bool)
        elif name == "lt":
            data = codes < lo
        elif name == "le":
            data = codes < hi
        elif name == "gt":
            data = codes >= hi
        else:  # ge
            data = codes >= lo
        return data, valid

    return Lowered(BOOLEAN, None, fn)


def _cmp_handler(name: str):
    def handler(out_type: Type, args: list[Lowered]) -> Lowered:
        a, b = args
        from ..spi.types import MapType, RowType

        is_arr = any(isinstance(t, (ArrayType, RowType, MapType))
                     for t in (a.type, b.type))
        is_ldec = _is_long_dec(a.type) or _is_long_dec(b.type)
        if is_arr and name not in ("eq", "ne"):
            raise NotImplementedError("array/row/map ordering comparison")
        if is_string(a.type) or is_string(b.type) or is_arr or is_ldec:
            # array/row/map dictionaries hold python tuples, long-decimal
            # dictionaries hold python ints — comparable/sortable like
            # strings, but never coerced through str()
            def lit(d):
                if is_arr:
                    return d[0]
                if is_ldec:
                    return int(d[0])
                return str(d[0])

            # literal vs column: route through the sorted dictionary
            if b.dictionary is not None and len(b.dictionary) == 1 and a.dictionary is not None and len(a.dictionary) != 1:
                return _cmp_dict_literal(name, a, lit(b.dictionary))
            if a.dictionary is not None and len(a.dictionary) == 1 and b.dictionary is not None and len(b.dictionary) != 1:
                flip = {"lt": "gt", "le": "ge", "gt": "lt", "ge": "le", "eq": "eq", "ne": "ne"}
                return _cmp_dict_literal(flip[name], b, lit(a.dictionary))
            if _dicts_equal(a.dictionary, b.dictionary):
                pass  # codes comparable directly (sorted dictionary)
            elif name in ("eq", "ne") and a.dictionary is not None and b.dictionary is not None:
                # translate b's code space into a's
                trans = np.searchsorted(a.dictionary, b.dictionary).clip(0, len(a.dictionary) - 1).astype(np.int32)
                hit = (a.dictionary[trans] == b.dictionary)

                def fn_ne(cols: Cols):
                    (ac, avalid), (bc, bvalid) = a.fn(cols), b.fn(cols)
                    eq = (ac == jnp.asarray(trans)[bc]) & jnp.asarray(hit)[bc]
                    return (eq if name == "eq" else ~eq), _and_valid(avalid, bvalid)

                return Lowered(BOOLEAN, None, fn_ne)
            else:
                raise NotImplementedError(
                    f"ordering comparison across distinct dictionaries ({name})"
                )

        def fn(cols: Cols):
            (av, avalid), (bv, bvalid) = a.fn(cols), b.fn(cols)
            return _CMP[name](av, bv), _and_valid(avalid, bvalid)

        return Lowered(BOOLEAN, None, fn)

    return handler


# ---------------------------------------------------------------------------
# boolean logic (three-valued)


def _and_handler(out_type, args):
    # 3VL: FALSE if any definite FALSE; else NULL if any NULL.  NULL lanes
    # normalize to TRUE so garbage values can't force a definite FALSE.
    def fn(cols: Cols):
        data, valid = None, None
        for a in args:
            # short-circuit masking: once an earlier term is definite FALSE
            # the remaining terms cannot raise on that lane
            with expr_condition_mask(data):
                v, vv = a.fn(cols)
            eff = v if vv is None else (v | ~vv)
            data = eff if data is None else (data & eff)
            valid = _and_valid(valid, vv)
        if valid is not None:
            valid = valid | ~data  # definite false wins over null
        return data, valid

    return Lowered(BOOLEAN, None, fn)


def _or_handler(out_type, args):
    # 3VL dual: TRUE if any definite TRUE; NULL lanes normalize to FALSE.
    def fn(cols: Cols):
        data, valid = None, None
        for a in args:
            with expr_condition_mask(None if data is None else ~data):
                v, vv = a.fn(cols)
            eff = v if vv is None else (v & vv)
            data = eff if data is None else (data | eff)
            valid = _and_valid(valid, vv)
        if valid is not None:
            valid = valid | data  # definite true wins over null
        return data, valid

    return Lowered(BOOLEAN, None, fn)


def _not_handler(out_type, args):
    (a,) = args

    def fn(cols: Cols):
        v, vv = a.fn(cols)
        return ~v, vv

    return Lowered(BOOLEAN, None, fn)


def _is_null_handler(out_type, args):
    (a,) = args

    def fn(cols: Cols):
        v, vv = a.fn(cols)
        if vv is None:
            return jnp.zeros(jnp.shape(v), dtype=bool), None
        return ~vv, None

    return Lowered(BOOLEAN, None, fn)


# ---------------------------------------------------------------------------
# conditionals


def _unify_pair(a: Lowered, b: Lowered) -> tuple[Lowered, Lowered, Optional[np.ndarray]]:
    """Remap two dictionary-typed lowerings onto one merged dictionary."""
    if a.dictionary is None and b.dictionary is None:
        return a, b, None
    da = a.dictionary if a.dictionary is not None else np.array([], dtype=object)
    db = b.dictionary if b.dictionary is not None else np.array([], dtype=object)
    if _dicts_equal(da, db):
        return a, b, da
    merged = np.unique(np.concatenate([da, db]))

    def remapped(x: Lowered, d: np.ndarray) -> Lowered:
        remap = np.searchsorted(merged, d).astype(np.int32) if len(d) else None

        def fn(cols: Cols):
            v, vv = x.fn(cols)
            return (jnp.asarray(remap)[v] if remap is not None else v), vv

        return Lowered(x.type, merged, fn)

    return remapped(a, da), remapped(b, db), merged


def _if_handler(out_type, args):
    cond, t, f = args
    t2, f2, merged = _unify_pair(t, f)

    def fn(cols: Cols):
        cv, cvalid = cond.fn(cols)
        take_true = cv if cvalid is None else (cv & cvalid)
        # a branch's errors only count on the lanes that select it (CASE
        # WHEN x = 0 THEN 0 ELSE 1/x END must not raise on x = 0 lanes)
        with expr_condition_mask(take_true):
            tv, tvalid = t2.fn(cols)
        with expr_condition_mask(~take_true):
            fv, fvalid = f2.fn(cols)
        data = jnp.where(take_true, tv, fv)
        if tvalid is None and fvalid is None:
            valid = None
        else:
            tvv = tvalid if tvalid is not None else jnp.ones(jnp.shape(tv), bool)
            fvv = fvalid if fvalid is not None else jnp.ones(jnp.shape(fv), bool)
            valid = jnp.where(take_true, tvv, fvv)
        return data, valid

    return Lowered(out_type, merged, fn)


def _coalesce_handler(out_type, args):
    out = args[-1]
    for a in reversed(args[:-1]):
        a2, out2, merged = _unify_pair(a, out)
        prev = out2

        def make_fn(a2=a2, prev=prev):
            def fn(cols: Cols):
                av, avalid = a2.fn(cols)
                if avalid is None:
                    return av, None
                with expr_condition_mask(~avalid):
                    pv, pvalid = prev.fn(cols)
                data = jnp.where(avalid, av, pv)
                if pvalid is None:
                    return data, None  # fallback is never null
                return data, jnp.where(avalid, True, pvalid)

            return fn

        out = Lowered(out_type, merged, make_fn())
    return out


# ---------------------------------------------------------------------------
# IN / LIKE / string functions via dictionary transforms


def _in_handler(out_type, args):
    col, *items = args
    if col.dictionary is not None:
        vals = []
        for it in items:
            if it.dictionary is None or len(it.dictionary) != 1:
                raise NotImplementedError("IN over non-literal strings")
            vals.append(str(it.dictionary[0]))
        mask = np.isin(col.dictionary, np.array(vals, dtype=object))

        def fn(cols: Cols):
            codes, valid = col.fn(cols)
            return jnp.asarray(mask)[codes], valid

        return Lowered(BOOLEAN, None, fn)

    def fn(cols: Cols):
        cv, cvalid = col.fn(cols)
        data = None
        for it in items:
            iv, _ = it.fn(cols)
            hit = cv == iv
            data = hit if data is None else (data | hit)
        return data, cvalid

    return Lowered(BOOLEAN, None, fn)


def like_to_regex(pattern: str, escape: Optional[str] = None) -> str:
    out = []
    i = 0
    while i < len(pattern):
        c = pattern[i]
        if escape and c == escape and i + 1 < len(pattern):
            out.append(re.escape(pattern[i + 1]))
            i += 2
            continue
        if c == "%":
            out.append(".*")
        elif c == "_":
            out.append(".")
        else:
            out.append(re.escape(c))
        i += 1
    return "".join(out)


def _like_handler(out_type, args):
    col = args[0]
    pat = args[1]
    esc = args[2] if len(args) > 2 else None
    if col.dictionary is None or pat.dictionary is None or len(pat.dictionary) != 1:
        raise NotImplementedError("LIKE requires a dictionary column and literal pattern")
    escape = str(esc.dictionary[0]) if esc is not None and esc.dictionary is not None else None
    # bit-parallel NFA over the whole dictionary (ops/like_dfa.py — the
    # DenseDfaMatcher.java:23 role); small dictionaries keep the re loop.
    # Host work at lowering: its event and counter touch no device value
    from .like_dfa import like_match

    t0 = profiler.now()
    mask, tokens, path = like_match(col.dictionary, str(pat.dictionary[0]),
                                    escape)
    tm.LIKE_DICTIONARY_ENTRIES.inc(len(mask))
    profiler.event(profiler.LIKE_MATCH, "like", t0, entries=len(mask),
                   matched=int(np.count_nonzero(mask)), tokens=tokens,
                   path=path)

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        return jnp.asarray(mask)[codes], valid

    return Lowered(BOOLEAN, None, fn)


def _dict_transform(col: Lowered, pyfn, out_type: Type) -> Lowered:
    """str->str function as a host dictionary transform + device remap."""
    vals = np.array([pyfn(str(v)) for v in col.dictionary], dtype=object)
    newdict, remap = np.unique(vals, return_inverse=True)
    remap = remap.astype(np.int32)

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        return jnp.asarray(remap)[codes], valid

    return Lowered(out_type, newdict, fn)


def _dict_scalar(col: Lowered, pyfn, out_type: Type) -> Lowered:
    """str->number function as host precompute + device gather."""
    arr = np.array([pyfn(str(v)) for v in col.dictionary], dtype=out_type.storage_dtype)

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        return jnp.asarray(arr)[codes], valid

    return Lowered(out_type, None, fn)


def _literal_int(x: Lowered) -> int:
    if not isinstance(x, Lowered) or not hasattr(x.fn, "_literal_value"):
        raise NotImplementedError("expected integer literal argument")
    return int(x.fn._literal_value)


def _substring_handler(out_type, args):
    col = args[0]
    start = _literal_int(args[1])
    length = _literal_int(args[2]) if len(args) > 2 else None
    if col.dictionary is None:
        raise NotImplementedError("substring on non-dictionary column")

    def sub(s: str) -> str:
        i = start - 1 if start > 0 else len(s) + start
        return s[i : i + length] if length is not None else s[i:]

    return _dict_transform(col, sub, VARCHAR)


def _strfn_handler(pyfn, result="str"):
    def handler(out_type, args):
        col = args[0]
        if col.dictionary is None:
            raise NotImplementedError("string function on non-dictionary column")
        if result == "str":
            return _dict_transform(col, pyfn, VARCHAR)
        return _dict_scalar(col, pyfn, out_type)

    return handler


def _literal_str(x: Lowered) -> str:
    if x.dictionary is None or len(x.dictionary) != 1:
        raise NotImplementedError("expected string literal argument")
    return str(x.dictionary[0])


def _and_extra_valid(base: Lowered, extras: Sequence[Lowered]) -> Lowered:
    """AND additional operands' validity into a result whose value was
    computed from their dictionaries alone (a NULL literal lowers to
    dictionary [""] + valid=False — the value shortcut must not drop it)."""
    extras = [x for x in extras if x is not None]
    if not extras:
        return base

    def fn(cols: Cols):
        d, v = base.fn(cols)
        for x in extras:
            _, xv = x.fn(cols)
            v = _and_valid(v, xv)
        return d, v

    return Lowered(base.type, base.dictionary, fn)


_CONCAT_DICT_LIMIT = 1 << 20  # max product-dictionary size for col || col


def _concat_pair(a: Lowered, b: Lowered) -> Lowered:
    """String concatenation in dictionary space: literal sides transform the
    other side's dictionary; column||column builds the (small) product
    dictionary and remaps the combined code on device."""
    if a.dictionary is None or b.dictionary is None:
        raise NotImplementedError("concat on non-dictionary operands")
    if len(b.dictionary) == 1:
        lit = str(b.dictionary[0])
        return _and_extra_valid(
            _dict_transform(a, lambda s: s + lit, VARCHAR), [b])
    if len(a.dictionary) == 1:
        lit = str(a.dictionary[0])
        return _and_extra_valid(
            _dict_transform(b, lambda s: lit + s, VARCHAR), [a])
    na, nb = len(a.dictionary), len(b.dictionary)
    if na * nb > _CONCAT_DICT_LIMIT:
        raise NotImplementedError(
            f"concat product dictionary too large ({na}x{nb})")
    prod = np.array([str(x) + str(y) for x in a.dictionary
                     for y in b.dictionary], dtype=object)
    newdict, remap = np.unique(prod, return_inverse=True)
    remap = remap.astype(np.int32)

    def fn(cols: Cols):
        (ac, av), (bc, bv) = a.fn(cols), b.fn(cols)
        code = ac.astype(jnp.int64) * nb + bc.astype(jnp.int64)
        return jnp.asarray(remap)[code], _and_valid(av, bv)

    return Lowered(VARCHAR, newdict, fn)


def _concat_handler(out_type, args):
    out = args[0]
    for nxt in args[1:]:
        out = _concat_pair(out, nxt)
    return out


def _replace_handler(out_type, args):
    col = args[0]
    search = _literal_str(args[1])
    rep = _literal_str(args[2]) if len(args) > 2 else ""
    if col.dictionary is None:
        raise NotImplementedError("replace on non-dictionary column")
    return _and_extra_valid(
        _dict_transform(col, lambda s: s.replace(search, rep), VARCHAR),
        args[1:])


def _strpos_handler(out_type, args):
    col = args[0]
    sub = _literal_str(args[1])
    if col.dictionary is None:
        raise NotImplementedError("strpos on non-dictionary column")
    return _and_extra_valid(
        _dict_scalar(col, lambda s: s.find(sub) + 1, BIGINT), args[1:])


def _starts_with_handler(out_type, args):
    col = args[0]
    prefix = _literal_str(args[1])
    if col.dictionary is None:
        raise NotImplementedError("starts_with on non-dictionary column")
    arr = np.array([str(v).startswith(prefix) for v in col.dictionary])

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        return jnp.asarray(arr)[codes], valid

    return _and_extra_valid(Lowered(BOOLEAN, None, fn), args[1:])


def _split_part_handler(out_type, args):
    col = args[0]
    delim = _literal_str(args[1])
    idx = _literal_int(args[2])
    if col.dictionary is None:
        raise NotImplementedError("split_part on non-dictionary column")
    if not delim:
        raise ValueError("split_part: delimiter must not be empty")
    if idx < 1:
        raise ValueError("split_part: index must be >= 1")

    def fn(s: str):
        parts = str(s).split(delim)
        # Trino: NULL when the index exceeds the number of fields
        return parts[idx - 1] if idx <= len(parts) else None

    return _and_extra_valid(
        _array_table_lookup(col, [fn(v) for v in col.dictionary], VARCHAR),
        args[1:])


def _pad_handler(left: bool):
    def handler(out_type, args):
        col = args[0]
        size = _literal_int(args[1])
        fill = _literal_str(args[2]) if len(args) > 2 else " "
        if col.dictionary is None:
            raise NotImplementedError("pad on non-dictionary column")
        if size < 0:
            raise ValueError("pad: target size must not be negative")
        if not fill:
            raise ValueError("pad: padding string must not be empty")

        def fn(s: str) -> str:
            if len(s) >= size:
                return s[:size]
            pad = (fill * size)[: size - len(s)]
            return pad + s if left else s + pad

        return _and_extra_valid(_dict_transform(col, fn, VARCHAR), args[1:])

    return handler


def _repeat_handler(out_type, args):
    """repeat(element, count) -> array(T) (reference:
    operator/scalar/RepeatFunction.java).  Element dictionaries (varchar /
    array) transform entry-wise; literal scalars build a one-entry constant
    array dictionary."""
    col = args[0]
    n = max(_literal_int(args[1]), 0)
    if col.dictionary is not None:
        vals = np.empty(len(col.dictionary), dtype=object)
        for i, v in enumerate(col.dictionary):
            elem = v if isinstance(v, tuple) else str(v)
            vals[i] = (elem,) * n
        newdict, remap = np.unique(vals, return_inverse=True)
        remap = remap.astype(np.int32)

        def fn(cols: Cols):
            codes, valid = col.fn(cols)
            return jnp.asarray(remap)[codes], valid

        return _and_extra_valid(Lowered(out_type, newdict, fn), args[1:])
    if hasattr(col.fn, "_literal_value"):
        newdict = np.empty(1, dtype=object)
        newdict[0] = (col.fn._literal_value,) * n

        def fn(cols: Cols):
            _, valid = col.fn(cols)
            return jnp.zeros((), dtype=jnp.int32), valid

        return _and_extra_valid(Lowered(out_type, newdict, fn), args[1:])
    raise NotImplementedError("repeat element must be a dictionary column "
                              "or literal")


def _translate_handler(out_type, args):
    col = args[0]
    src = _literal_str(args[1])
    dst = _literal_str(args[2])
    if col.dictionary is None:
        raise NotImplementedError("translate on non-dictionary column")
    table: dict = {}
    for i, a in enumerate(src):  # first duplicate wins (Trino semantics)
        table.setdefault(ord(a), dst[i] if i < len(dst) else None)
    return _and_extra_valid(
        _dict_transform(col, lambda s: s.translate(table), VARCHAR),
        args[1:])


def _codepoint_handler(out_type, args):
    col = args[0]
    if col.dictionary is None:
        raise NotImplementedError("codepoint on non-dictionary column")
    # Trino errors unless the input is exactly one character; dictionary
    # entries are evaluated eagerly (rows may never select a bad entry), so
    # the faithful per-row error degrades to NULL here
    return _array_table_lookup(
        col,
        [ord(str(v)) if len(str(v)) == 1 else None for v in col.dictionary],
        BIGINT)


def _variadic_minmax(jfn):
    """greatest/least: NULL if any argument is NULL (Trino semantics)."""

    def handler(out_type, args):
        def fn(cols: Cols):
            vals, valids = zip(*[a.fn(cols) for a in args])
            data = vals[0]
            for v in vals[1:]:
                data = jfn(data, v)
            return data.astype(out_type.storage_dtype), _all_valids(valids)

        return Lowered(out_type, None, fn)

    return handler


def _date_trunc_handler(truncfn):
    """date_trunc on DATE (days) or TIMESTAMP (micros since epoch)."""

    def handler(out_type, args):
        (a,) = args

        def fn(cols: Cols):
            v, vv = a.fn(cols)
            if a.type == TIMESTAMP:
                days = jnp.floor_divide(v, dt.MICROS_PER_DAY)
                return truncfn(days) * dt.MICROS_PER_DAY, vv
            return truncfn(v).astype(out_type.storage_dtype), vv

        return Lowered(out_type, None, fn)

    return handler


def _days_field_handler(field_fn):
    """Calendar field extraction over DATE (days) or TIMESTAMP (micros)."""

    def handler(out_type, args):
        (a,) = args

        def fn(cols: Cols):
            v, vv = a.fn(cols)
            if a.type == TIMESTAMP:
                v = jnp.floor_divide(v, dt.MICROS_PER_DAY)
            return field_fn(v).astype(out_type.storage_dtype), vv

        return Lowered(out_type, None, fn)

    return handler


def _const_handler(value):
    def handler(out_type, args):
        def fn(cols: Cols):
            return jnp.asarray(value, dtype=out_type.storage_dtype), None

        return Lowered(out_type, None, fn)

    return handler


def _truncate_handler(out_type, args):
    (a,) = args

    def fn(cols: Cols):
        v, vv = a.fn(cols)
        if isinstance(a.type, DecimalType):
            f = 10 ** a.type.scale
            return _trunc_div(v, f) * f, vv
        if np.issubdtype(v.dtype, np.integer):
            return v, vv
        return jnp.trunc(v), vv

    return Lowered(out_type, None, fn)


# ---------------------------------------------------------------------------
# CAST


def _nested_repr_compatible(a: Type, b: Type) -> bool:
    """True when two nested types share an identical python-value
    representation in dictionaries (so codes can pass through a cast)."""
    from ..spi.types import MapType, RowType

    def kind(t: Type):
        if isinstance(t, ArrayType):
            return ("array", kind(t.element))
        if isinstance(t, RowType):
            return ("row", tuple(kind(ft) for _, ft in t.fields))
        if isinstance(t, MapType):
            return ("map", kind(t.key), kind(t.value))
        if is_string(t):
            return "str"
        if isinstance(t, DecimalType):
            return ("dec", t.scale)
        k = np.dtype(t.storage_dtype).kind
        return {"i": "int", "u": "int", "f": "float", "b": "bool"}.get(k, t.name)

    return kind(a) == kind(b)


def _cast_handler(out_type, args):
    (a,) = args
    src = a.type
    if src == out_type:
        return a
    if is_string(src) and is_string(out_type):
        return a
    from ..spi.types import MapType, RowType

    if isinstance(src, (ArrayType, RowType, MapType)) and isinstance(
            out_type, (ArrayType, RowType, MapType)):
        # nested casts pass codes through ONLY when the python-value
        # representation is identical (same kind, matching element repr:
        # named vs anonymous row fields, int-width changes); anything else
        # (string->number elements, array->map) must not silently mistype
        if _nested_repr_compatible(src, out_type):
            return Lowered(out_type, a.dictionary, a.fn)
        raise NotImplementedError(
            f"cast {src} -> {out_type}: nested element conversion is not "
            "supported")
    ss, ds = _scale_of(src), _scale_of(out_type)
    if _is_long_dec(out_type):
        if _is_long_dec(src) and a.dictionary is not None:
            return _long_dec_transform(
                a, lambda v: rescale_scaled_int(v, ss, ds), out_type)
        if is_string(src) and a.dictionary is not None:
            # varchar -> decimal(38): exact parse over the dictionary
            from ..spi.batch import _to_scaled_int

            vals = [_to_scaled_int(str(v), ds) for v in a.dictionary]
            uniq = sorted(set(vals))
            pos = {v: i for i, v in enumerate(uniq)}
            remap = np.array([pos[v] for v in vals], dtype=np.int32)
            newdict = np.empty(len(uniq), dtype=object)
            for i, v in enumerate(uniq):
                newdict[i] = v

            def fn_vd(cols: Cols):
                codes, valid = a.fn(cols)
                return jnp.asarray(remap)[codes], valid

            return Lowered(out_type, newdict, fn_vd)
        if hasattr(a.fn, "_literal_value"):
            raw = rescale_scaled_int(int(a.fn._literal_value), ss, ds)
            d = np.empty(1, dtype=object)
            d[0] = raw

            def fn_lit(cols: Cols):
                _, valid = a.fn(cols)
                return jnp.zeros((), dtype=np.int32), valid

            fn_lit._literal_value = raw
            return Lowered(out_type, d, fn_lit)
        raise NotImplementedError(
            "cast of a device-resident column to decimal(>18) "
            "(dictionary-encoded int128 path) — cast to decimal(18,s) or "
            "double instead")
    if _is_long_dec(src):
        if a.dictionary is None:
            raise NotImplementedError("long-decimal column without dictionary")
        if np.issubdtype(out_type.storage_dtype, np.floating):
            return _dict_scalar(a, lambda s: int(s) / (10.0 ** ss), out_type)
        if is_string(out_type):
            import decimal as _d

            def fmt(s: str) -> str:
                with _d.localcontext() as ctx:
                    ctx.prec = 80
                    return str(_d.Decimal(int(s)).scaleb(-ss))

            return _dict_transform(a, fmt, VARCHAR)
        if isinstance(out_type, DecimalType) or np.issubdtype(
                out_type.storage_dtype, np.integer):
            shift = ds if isinstance(out_type, DecimalType) else 0
            return _dict_scalar(
                a, lambda s: rescale_scaled_int(int(s), ss, shift), out_type)
        raise NotImplementedError(f"cast decimal(38) -> {out_type}")

    def fn(cols: Cols):
        v, vv = a.fn(cols)
        ss, ds = _scale_of(src), _scale_of(out_type)
        if isinstance(out_type, DecimalType):
            if isinstance(src, DecimalType) or np.issubdtype(v.dtype, np.integer):
                data = _decimal_rescale(v.astype(np.int64), ss, ds)
            else:  # float -> decimal
                scaled = v * (10.0**ds)
                data = jnp.round(scaled).astype(np.int64)
        elif isinstance(src, DecimalType):
            if np.issubdtype(out_type.storage_dtype, np.floating):
                data = (v / (10.0**ss)).astype(out_type.storage_dtype)
            else:
                data = _decimal_rescale(v, ss, 0).astype(out_type.storage_dtype)
        elif src == DATE and out_type == TIMESTAMP:
            data = v.astype(np.int64) * dt.MICROS_PER_DAY
        elif src == TIMESTAMP and out_type == DATE:
            data = jnp.floor_divide(v, dt.MICROS_PER_DAY).astype(np.int32)
        elif out_type == BOOLEAN:
            data = v != 0
        else:
            data = v.astype(out_type.storage_dtype)
        return data, vv

    # exact-literal propagation: handlers that need a static operand (long-
    # decimal arithmetic, LIMIT-style ints) see through scalar casts
    if hasattr(a.fn, "_literal_value") and isinstance(
            a.fn._literal_value, (int, np.integer)) and (
            isinstance(out_type, DecimalType)
            or np.issubdtype(out_type.storage_dtype, np.integer)):
        fn._literal_value = rescale_scaled_int(int(a.fn._literal_value), ss, ds)
    if isinstance(src, DecimalType) or getattr(a.fn, "_from_decimal", False):
        # provenance marker: decimal operands folded to double keep exact-
        # arithmetic error semantics (the analyzer casts decimal -> double
        # before divide, which would otherwise hide DIVISION_BY_ZERO)
        fn._from_decimal = True
    return Lowered(out_type, None, fn)


# ---------------------------------------------------------------------------
# elementwise math / date registry


def _elementwise(jfn, null_on=None):
    def handler(out_type, args):
        def fn(cols: Cols):
            vals, valids = zip(*[a.fn(cols) for a in args])
            valid = _all_valids(valids)
            if null_on is not None:
                valid = _and_valid(valid, ~null_on(*vals))
            return jfn(*vals).astype(out_type.storage_dtype), valid

        return Lowered(out_type, None, fn)

    return handler


def _round_handler(out_type, args):
    x = args[0]
    nd = _literal_int(args[1]) if len(args) > 1 else 0

    def fn(cols: Cols):
        v, vv = x.fn(cols)
        if isinstance(x.type, DecimalType):
            s = x.type.scale
            if nd >= s:
                return v, vv
            f = 10 ** (s - nd)
            return _round_half_up_div(v, f) * f, vv
        if np.issubdtype(v.dtype, np.integer):
            return v, vv
        f = 10.0**nd
        return jnp.round(v * f) / f, vv

    return Lowered(out_type, None, fn)


# ---------------------------------------------------------------------------
# array functions: host dictionary transforms + device gathers (same stance
# as strings; reference operator/scalar/ArrayFunctions / ArraySubscript)


def _array_table_lookup(col, values, out_type: Type):
    """Per-dictionary-code precomputed result table -> device gather.
    ``values`` holds one python value (or None) per array-dictionary entry;
    Column.from_values performs type-correct storage conversion (decimal
    scaling, date days, string re-dictionarying) for the output."""
    from ..spi.batch import Column

    tab = Column.from_values(out_type, list(values))
    data_tab = np.asarray(tab.data)
    valid_tab = tab.valid_mask()
    all_valid = bool(valid_tab.all())

    def fn(cols: Cols):
        codes, valid = col.fn(cols)
        data = jnp.asarray(data_tab)[codes]
        v = valid if all_valid else _and_valid(
            valid, jnp.asarray(valid_tab)[codes])
        return data, v

    return Lowered(out_type, tab.dictionary, fn)


def _require_array_dict(col, what: str):
    if col.dictionary is None:
        raise NotImplementedError(f"{what} on non-dictionary array column")


def _row_field_handler(out_type, args):
    """ROW field access (sql/tree/DereferenceExpression): host table of the
    selected field per row-dictionary entry + device gather."""
    col = args[0]
    _require_array_dict(col, "row field access")
    fi = _literal_int(args[1])
    vals = [v[fi] if fi < len(v) else None for v in col.dictionary]
    return _array_table_lookup(col, vals, out_type)


def _map_element_at_handler(out_type, args):
    """element_at(map, key): per-dictionary-entry lookup (entries are
    key-sorted pair tuples) + device gather."""
    col, key = args[0], args[1]
    _require_array_dict(col, "element_at(map)")
    if hasattr(key.fn, "_literal_value"):
        needle = key.fn._literal_value
    elif key.dictionary is not None and len(key.dictionary) == 1:
        needle = str(key.dictionary[0])
    else:
        raise NotImplementedError("map key must be a literal")
    vals = [dict(v).get(needle) for v in col.dictionary]
    return _and_extra_valid(
        _array_table_lookup(col, vals, out_type), args[1:])


def _map_parts_handler(which: int):
    def handler(out_type, args):
        col = args[0]
        _require_array_dict(col, "map_keys/map_values")
        vals = [tuple(p[which] for p in v) for v in col.dictionary]
        return _array_table_lookup(col, vals, out_type)

    return handler


def _cardinality_handler(out_type, args):
    col = args[0]
    _require_array_dict(col, "cardinality")
    return _array_table_lookup(col, [len(v) for v in col.dictionary], BIGINT)


def _element_at_dispatch(out_type, args):
    from ..spi.types import MapType

    if isinstance(args[0].type, MapType):
        return _map_element_at_handler(out_type, args)
    return _element_at_handler(out_type, args)


def _element_at_handler(out_type, args):
    col = args[0]
    _require_array_dict(col, "element_at")
    i = _literal_int(args[1])
    if i == 0:
        raise NotImplementedError("SQL array indexes are 1-based")

    def pick(v):
        j = i - 1 if i > 0 else len(v) + i
        return v[j] if 0 <= j < len(v) else None

    return _and_extra_valid(
        _array_table_lookup(col, [pick(v) for v in col.dictionary], out_type),
        args[1:])


def _array_needle(x) -> object:
    if hasattr(x.fn, "_literal_value") and not isinstance(x.type, DecimalType):
        return x.fn._literal_value
    if x.dictionary is not None and len(x.dictionary) == 1:
        return str(x.dictionary[0])
    raise NotImplementedError("contains/array_position needle must be a "
                              "non-decimal literal")


def _contains_handler(out_type, args):
    col = args[0]
    _require_array_dict(col, "contains")
    needle = _array_needle(args[1])
    return _and_extra_valid(
        _array_table_lookup(
            col, [needle in v for v in col.dictionary], BOOLEAN),
        args[1:])


def _array_position_handler(out_type, args):
    col = args[0]
    _require_array_dict(col, "array_position")
    needle = _array_needle(args[1])

    def pos(v):
        try:
            return v.index(needle) + 1
        except ValueError:
            return 0

    return _and_extra_valid(
        _array_table_lookup(col, [pos(v) for v in col.dictionary], BIGINT),
        args[1:])


def _json_extract_handler(scalar: bool):
    """json_extract[_scalar](json, '$.path'): host path evaluation over the
    dictionary + device gather (ops/json_fns.py; reference:
    json/JsonPathEvaluator.java, operator/scalar/JsonFunctions)."""

    def handler(out_type, args):
        import json as _json

        from .json_fns import eval_json_path, json_scalar_text, parse_json_path

        col = args[0]
        if col.dictionary is None:
            raise NotImplementedError("json function on non-dictionary column")
        steps = parse_json_path(_literal_str(args[1]))
        vals = []
        for v in col.dictionary:
            r = eval_json_path(str(v), steps)
            if scalar:
                vals.append(json_scalar_text(r))
            else:
                vals.append(None if r is None else _json.dumps(r))
        return _and_extra_valid(
            _array_table_lookup(col, vals, VARCHAR), args[1:])

    return handler


def _json_array_length_handler(out_type, args):
    import json as _json

    col = args[0]
    if col.dictionary is None:
        raise NotImplementedError("json function on non-dictionary column")

    def length(v):
        try:
            doc = _json.loads(str(v))
        except (ValueError, TypeError):
            return None
        return len(doc) if isinstance(doc, list) else None

    return _array_table_lookup(
        col, [length(v) for v in col.dictionary], BIGINT)


def _grouping_mask_handler(out_type, args):
    """grouping() lowering: constant-table gather by the $groupid channel
    (args = [groupid column, one mask literal per grouping set])."""
    gid = args[0]
    masks = np.asarray([_literal_int(a) for a in args[1:]], dtype=np.int64)

    def fn(cols: Cols):
        v, vv = gid.fn(cols)
        return jnp.asarray(masks)[v], vv

    return Lowered(out_type, None, fn)


HANDLERS: dict[str, Callable] = {
    "$grouping_mask": _grouping_mask_handler,
    "cardinality": _cardinality_handler,
    "json_extract": _json_extract_handler(scalar=False),
    "json_extract_scalar": _json_extract_handler(scalar=True),
    "json_array_length": _json_array_length_handler,
    "element_at": _element_at_dispatch,
    "$row_field": _row_field_handler,
    "map_keys": _map_parts_handler(0),
    "map_values": _map_parts_handler(1),
    "contains": _contains_handler,
    "array_position": _array_position_handler,
    "add": _arith_handler("add"),
    "subtract": _arith_handler("subtract"),
    "multiply": _arith_handler("multiply"),
    "divide": _arith_handler("divide"),
    "modulus": _arith_handler("modulus"),
    "eq": _cmp_handler("eq"),
    "ne": _cmp_handler("ne"),
    "lt": _cmp_handler("lt"),
    "le": _cmp_handler("le"),
    "gt": _cmp_handler("gt"),
    "ge": _cmp_handler("ge"),
    "$and": _and_handler,
    "$or": _or_handler,
    "$not": _not_handler,
    "$is_null": _is_null_handler,
    "$if": _if_handler,
    "$coalesce": _coalesce_handler,
    "$in": _in_handler,
    "$like": _like_handler,
    "$cast": _cast_handler,
    "negate": _elementwise(lambda a: -a),
    "abs": _elementwise(jnp.abs),
    "sqrt": _elementwise(jnp.sqrt),
    "floor": _elementwise(jnp.floor),
    "ceiling": _elementwise(jnp.ceil),
    "ceil": _elementwise(jnp.ceil),
    "exp": _elementwise(jnp.exp),
    "ln": _elementwise(jnp.log),
    "log10": _elementwise(jnp.log10),
    "power": _elementwise(jnp.power),
    "pow": _elementwise(jnp.power),
    "round": _round_handler,
    "year": _days_field_handler(dt.year_of),
    "month": _days_field_handler(dt.month_of),
    "day": _days_field_handler(dt.day_of),
    "quarter": _days_field_handler(dt.quarter_of),
    "add_months": _elementwise(dt.add_months),
    "substring": _substring_handler,
    "substr": _substring_handler,
    "upper": _strfn_handler(str.upper),
    "lower": _strfn_handler(str.lower),
    "trim": _strfn_handler(str.strip),
    "ltrim": _strfn_handler(str.lstrip),
    "rtrim": _strfn_handler(str.rstrip),
    "length": _strfn_handler(len, result="scalar"),
    "reverse": _strfn_handler(lambda s: s[::-1]),
    "concat": _concat_handler,
    "replace": _replace_handler,
    "strpos": _strpos_handler,
    "starts_with": _starts_with_handler,
    "split_part": _split_part_handler,
    "lpad": _pad_handler(left=True),
    "rpad": _pad_handler(left=False),
    "repeat": _repeat_handler,
    "translate": _translate_handler,
    "codepoint": _codepoint_handler,
    "greatest": _variadic_minmax(jnp.maximum),
    "least": _variadic_minmax(jnp.minimum),
    "sign": _elementwise(jnp.sign),
    "truncate": _truncate_handler,
    "cbrt": _elementwise(jnp.cbrt),
    "degrees": _elementwise(jnp.degrees),
    "radians": _elementwise(jnp.radians),
    "sin": _elementwise(jnp.sin),
    "cos": _elementwise(jnp.cos),
    "tan": _elementwise(jnp.tan),
    "asin": _elementwise(jnp.arcsin),
    "acos": _elementwise(jnp.arccos),
    "atan": _elementwise(jnp.arctan),
    "atan2": _elementwise(jnp.arctan2),
    "log2": _elementwise(jnp.log2),
    "pi": _const_handler(np.pi),
    "e": _const_handler(np.e),
    "is_nan": _elementwise(jnp.isnan),
    "day_of_week": _days_field_handler(dt.day_of_week),
    "dow": _days_field_handler(dt.day_of_week),
    "day_of_year": _days_field_handler(dt.day_of_year),
    "doy": _days_field_handler(dt.day_of_year),
    "date_trunc_year": _date_trunc_handler(dt.trunc_year),
    "date_trunc_quarter": _date_trunc_handler(dt.trunc_quarter),
    "date_trunc_month": _date_trunc_handler(dt.trunc_month),
    "date_trunc_week": _date_trunc_handler(dt.trunc_week),
    "date_trunc_day": _date_trunc_handler(lambda d: d),
}


# ---------------------------------------------------------------------------
# compiler entry points


def _lower(
    expr: RowExpression,
    input_types: Sequence[Type],
    input_dicts: Sequence[Optional[np.ndarray]],
) -> Lowered:
    if isinstance(expr, InputRef):
        idx = expr.index

        def fn(cols: Cols):
            return cols[idx]

        return Lowered(expr.type, input_dicts[idx] if input_dicts else None, fn)

    if isinstance(expr, Literal):
        from ..spi.types import MapType, RowType

        t = expr.type
        v = expr.value
        if v is None:

            def fn_null(cols: Cols):
                return jnp.zeros((), dtype=t.storage_dtype), jnp.zeros((), dtype=bool)

            fn_null._literal_null = True
            if isinstance(t, (ArrayType, RowType, MapType)):
                d0 = np.empty(1, dtype=object)
                d0[0] = ()
                return Lowered(t, d0, fn_null)
            if _is_long_dec(t):
                d0 = np.empty(1, dtype=object)
                d0[0] = 0
                return Lowered(t, d0, fn_null)
            return Lowered(t, np.array([""], dtype=object) if is_string(t) else None, fn_null)
        if _is_long_dec(t):
            from ..spi.batch import _to_scaled_int

            raw = _to_scaled_int(v, t.scale)
            d = np.empty(1, dtype=object)
            d[0] = raw

            def fn_ldec(cols: Cols):
                return jnp.zeros((), dtype=np.int32), None

            fn_ldec._literal_value = raw
            return Lowered(t, d, fn_ldec)
        if isinstance(t, (RowType, MapType)):
            d = np.empty(1, dtype=object)
            d[0] = (tuple(sorted(v.items())) if isinstance(v, dict)
                    else tuple(v))

            def fn_rowmap(cols: Cols):
                return jnp.zeros((), dtype=np.int32), None

            return Lowered(t, d, fn_rowmap)
        if isinstance(t, ArrayType):
            d = np.empty(1, dtype=object)
            d[0] = tuple(v)

            def fn_arr(cols: Cols):
                return jnp.zeros((), dtype=np.int32), None

            return Lowered(t, d, fn_arr)
        if is_string(t):
            d = np.array([v], dtype=object)

            def fn_str(cols: Cols):
                return jnp.zeros((), dtype=np.int32), None

            return Lowered(t, d, fn_str)
        if isinstance(t, DecimalType):
            from ..spi.batch import _to_scaled_int

            raw = _to_scaled_int(v, t.scale)
        elif t == DATE:
            from ..spi.batch import _to_days

            raw = _to_days(v)
        elif t == TIMESTAMP:
            from ..spi.batch import _to_micros

            raw = _to_micros(v)
        else:
            raw = v

        def fn_lit(cols: Cols):
            return jnp.asarray(raw, dtype=t.storage_dtype), None

        fn_lit._literal_value = raw  # for handlers needing static args
        return Lowered(t, None, fn_lit)

    assert isinstance(expr, Call), expr
    handler = HANDLERS.get(expr.name)
    if handler is None:
        raise NotImplementedError(f"scalar function not implemented: {expr.name}")
    args = [_lower(a, input_types, input_dicts) for a in expr.args]
    return handler(expr.type, args)


def compile_expression(
    expr: RowExpression,
    input_types: Sequence[Type],
    input_dicts: Optional[Sequence[Optional[np.ndarray]]] = None,
) -> CompiledExpression:
    dicts = list(input_dicts) if input_dicts is not None else [None] * len(input_types)
    low = _lower(expr, list(input_types), dicts)
    return CompiledExpression(low.type, low.dictionary, low.fn)


def compile_projection(
    exprs: Sequence[RowExpression],
    input_types: Sequence[Type],
    input_dicts: Optional[Sequence[Optional[np.ndarray]]] = None,
):
    """Compile a list of projections into one traceable function
    ``cols -> [(data, valid), ...]`` (fused by jit at the operator level)."""
    compiled = [compile_expression(e, input_types, input_dicts) for e in exprs]

    def fn(cols: Cols):
        return [c(cols) for c in compiled]

    return compiled, fn
