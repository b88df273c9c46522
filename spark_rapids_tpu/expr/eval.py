"""Lowering bound expression trees to fused XLA computations.

Reference analog: GpuExpression.columnarEval (GpuExpressions.scala:380) where
each node launches a cudf kernel. TPU re-design: `compile_projection` traces
the WHOLE bound tree once per (expressions, schema, capacity-bucket) into a
single jitted function, letting XLA fuse every elementwise op into one HBM
pass. The executable cache is keyed structurally (frozen dataclass hashing),
the TPU analog of the reference's per-op kernel dispatch being amortized by
cudf's own compiled kernels.

Value representation inside a trace:
  ColV(data, validity)            fixed-width column piece
  StrV(offsets, chars, validity)  string column piece (Arrow layout)

Null semantics follow Spark exactly (three-valued logic, null-on-divide-by-
zero, Java cast saturation); differential tests in tests/test_expressions.py
pin this against the independent CPU interpreter.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import types as T
from ..columnar import ColumnarBatch, DeviceColumn
from ..types import DataType
from . import expressions as E
from .values import (  # noqa: F401
    ColV,
    DictV,
    StrV,
    Val,
    UnsupportedExpressionError,
    as_plain_str,
    dict_gather_col,
    materialize_dict,
)


_INT_INFO = {
    "tinyint": (np.int8, -(2**7), 2**7 - 1),
    "smallint": (np.int16, -(2**15), 2**15 - 1),
    "int": (np.int32, -(2**31), 2**31 - 1),
    "bigint": (np.int64, -(2**63), 2**63 - 1),
}


def _storage(dt: DataType):
    return jnp.dtype(dt.to_numpy()) if not isinstance(dt, (T.StringType, T.BinaryType)) else None


def _cast_data(data: jax.Array, frm: DataType, to: DataType) -> jax.Array:
    """Value cast with Java/Spark numeric semantics (reference: GpuCast.scala)."""
    if frm == to:
        return data
    # date/timestamp pairs (GpuCast.scala datetime rows): DATE = days int32,
    # TIMESTAMP = micros int64, UTC
    if isinstance(frm, T.DateType) and isinstance(to, T.TimestampType):
        return data.astype(jnp.int64) * 86_400_000_000
    if isinstance(frm, T.TimestampType) and isinstance(to, T.DateType):
        return jnp.floor_divide(data, 86_400_000_000).astype(jnp.int32)
    if isinstance(frm, T.TimestampType):
        if isinstance(to, T.BooleanType):
            return data != 0  # micros != 0 (Spark timestampToBoolean)
        if to.is_floating:
            return (data.astype(jnp.float64) / 1e6).astype(to.to_numpy())
        return jnp.floor_divide(data, 1_000_000).astype(to.to_numpy())
    if isinstance(to, T.TimestampType):
        if frm.is_floating:
            # Scala (d * 1e6).toLong saturates; non-finite handled (nulled)
            # by the Cast lowering itself
            x = data.astype(jnp.float64) * 1e6
            in_range = jnp.isfinite(x) & (jnp.abs(x) < float(2**63))
            i = jnp.where(in_range, x, 0.0).astype(jnp.int64)
            i = jnp.where(x >= float(2**63), jnp.int64(2**63 - 1), i)
            return jnp.where(
                jnp.isfinite(x) & (x <= float(-(2**63))),
                jnp.int64(-(2**63)), i)
        if isinstance(frm, T.BooleanType):
            return data.astype(jnp.int64)  # Spark: true -> 1 MICROsecond
        return data.astype(jnp.int64) * 1_000_000  # integral seconds
    if isinstance(frm, T.DateType) or isinstance(to, T.DateType):
        raise UnsupportedExpressionError(
            f"cast {frm.simpleString} -> {to.simpleString} is not supported")
    if isinstance(to, T.BooleanType):
        return data != 0
    if isinstance(frm, T.BooleanType):
        return data.astype(to.to_numpy())
    if to.name in _INT_INFO and (frm.is_floating):
        # Java narrowing: NaN -> 0; saturate at int32 (or int64 for bigint)
        # range; byte/short then wrap-narrow from int32 (so (byte)inf == -1).
        npdt, _, _ = _INT_INFO[to.name]
        wide = "bigint" if to.name == "bigint" else "int"
        wdt, lo, hi = _INT_INFO[wide]
        d = jnp.where(jnp.isnan(data), 0.0, data)
        # handle +-inf via masks BEFORE trunc: emulated-f64 backends turn
        # trunc(inf) into NaN, which would defeat the saturation compares
        fin = jnp.isfinite(d)
        t = jnp.trunc(jnp.where(fin, d, 0.0))
        sat = jnp.where(
            jnp.isposinf(d) | (fin & (t >= float(hi))), hi, 0).astype(wdt)
        mid = jnp.where(
            fin & (t > float(lo)) & (t < float(hi)), t, 0.0).astype(wdt)
        low = jnp.where(
            jnp.isneginf(d) | (fin & (t <= float(lo))), lo, 0).astype(wdt)
        w = sat + mid + low
        return w.astype(npdt)
    if isinstance(to, T.DecimalType):
        # comparison/promote coercion: upscale to the common (max) scale —
        # exact by the promote() precision check
        fs = frm.scale if isinstance(frm, T.DecimalType) else 0
        d = data.astype(jnp.int64)
        if to.scale > fs:
            d = d * jnp.int64(10 ** (to.scale - fs))
        return d
    if isinstance(frm, T.DecimalType):
        if to.is_floating:
            den = jax.lax.optimization_barrier(
                jnp.float64(float(10 ** frm.scale)))
            return (data.astype(jnp.float64) / den).astype(to.to_numpy())
        return data.astype(to.to_numpy())  # unscaled passthrough (same scale)
    # int->int wraps (Java), int/float->float exact-ish
    return data.astype(to.to_numpy())


def _promote2(l: ColV, ldt, r: ColV, rdt, target: DataType) -> Tuple[jax.Array, jax.Array]:
    return _cast_data(l.data, ldt, target), _cast_data(r.data, rdt, target)


# ---------------------------------------------------------------------------
# DECIMAL64 kernels: int64 unscaled values (reference: the DECIMAL64 rows
# of GpuCast.scala / decimalExpressions.scala, capped like
# GpuOverrides.scala:562). Plan-time precision checks (decimal_binary_result)
# guarantee every intermediate below fits int64; overflow vs the RESULT
# precision nulls the row (Spark non-ANSI nullOnOverflow).
# ---------------------------------------------------------------------------
def _pow10(k: int) -> int:
    return 10 ** k


def _dec_upscale(data: jax.Array, delta: int) -> jax.Array:
    """unscaled * 10^delta (delta >= 0; plan-time bounds keep it exact)."""
    if delta == 0:
        return data
    return data * jnp.int64(_pow10(delta))


def _div_half_up(num: jax.Array, den: jax.Array) -> jax.Array:
    """round_half_up(num/den) on int64, den > 0, sign-correct (HALF_UP =
    away from zero on .5, matching java.math.RoundingMode.HALF_UP)."""
    q = _trunc_div(num, den)
    rem = num - q * den
    bump = (jnp.abs(rem) * 2) >= den
    return jnp.where(bump, q + jnp.sign(num).astype(jnp.int64), q)


def _dec_rescale(data: jax.Array, frm_scale: int, to_scale: int) -> jax.Array:
    if to_scale >= frm_scale:
        return _dec_upscale(data, to_scale - frm_scale)
    return _div_half_up(data, jnp.int64(_pow10(frm_scale - to_scale)))


def _dec_fits(data: jax.Array, precision: int) -> jax.Array:
    bound = jnp.int64(_pow10(precision)) if precision < 19 else None
    if bound is None:
        return jnp.ones_like(data, jnp.bool_)
    return (data < bound) & (data > -bound)


def _decimal_arith(expr, l: ColV, r: ColV, out) -> ColV:
    lt, rt = T.as_decimal(expr.left.dtype), T.as_decimal(expr.right.dtype)
    ld = l.data.astype(jnp.int64)
    rd = r.data.astype(jnp.int64)
    valid = l.validity & r.validity
    if isinstance(expr, E.Multiply):
        res = ld * rd  # scale s1+s2 == out.scale by construction
    else:
        ld = _dec_upscale(ld, out.scale - lt.scale)
        rd = _dec_upscale(rd, out.scale - rt.scale)
        res = ld + rd if isinstance(expr, E.Add) else ld - rd
    ok = _dec_fits(res, out.precision)
    return ColV(jnp.where(ok, res, 0), valid & ok)


def _decimal_divide(expr, l: ColV, r: ColV, out) -> ColV:
    lt, rt = T.as_decimal(expr.left.dtype), T.as_decimal(expr.right.dtype)
    # result_unscaled = round(l / r * 10^out.scale)
    #                 = round(l_unscaled * 10^(out.scale - s1 + s2) / r_unscaled)
    shift = out.scale - lt.scale + rt.scale
    # plan-time feasibility: |l_unscaled| < 10^p1, so the shifted numerator
    # needs p1 + shift <= 18 to stay exact in int64
    if lt.precision + shift > 18:
        raise UnsupportedExpressionError(
            f"decimal divide needs {lt.precision + shift} digits > DECIMAL64")
    ld = _dec_upscale(l.data.astype(jnp.int64), shift)
    rd = r.data.astype(jnp.int64)
    valid = l.validity & r.validity & (rd != 0)
    safe_r = jnp.where(rd == 0, 1, rd)
    num = jnp.where(rd < 0, -ld, ld)  # make denominator positive
    res = _div_half_up(num, jnp.abs(safe_r))
    ok = _dec_fits(res, out.precision)
    return ColV(jnp.where(ok, res, 0), valid & ok)


def _decimal_cast(c: ColV, frm, to) -> ColV:
    data = c.data
    valid = c.validity
    if isinstance(frm, T.DecimalType) and isinstance(to, T.DecimalType):
        delta = to.scale - frm.scale
        if delta > 0 and frm.precision + delta > 18:
            raise UnsupportedExpressionError(
                "decimal rescale exceeds DECIMAL64 headroom")
        res = _dec_rescale(data.astype(jnp.int64), frm.scale, to.scale)
        ok = _dec_fits(res, to.precision)
        return ColV(jnp.where(ok, res, 0), valid & ok)
    if isinstance(to, T.DecimalType):
        if frm.is_floating:
            raise UnsupportedExpressionError(
                "float->decimal cast not supported (string-mediated in "
                "Spark; falls back like the reference's gated casts)")
        d = data.astype(jnp.int64)
        if to.scale > 0:
            # overflow-safe: values needing more than 18-scale integer
            # digits null out; test BEFORE multiplying
            limit = jnp.int64(_pow10(18 - to.scale))
            pre_ok = (d < limit) & (d > -limit)
            res = jnp.where(pre_ok, d, 0) * jnp.int64(_pow10(to.scale))
        else:
            pre_ok = jnp.ones_like(d, jnp.bool_)
            res = d
        ok = pre_ok & _dec_fits(res, to.precision)
        return ColV(jnp.where(ok, res, 0), valid & ok)
    # FROM decimal
    assert isinstance(frm, T.DecimalType)
    if to.is_floating:
        # the barrier stops XLA folding /10^s into a reciprocal multiply,
        # which is 1 ulp off the correctly-rounded quotient Java produces
        den = jax.lax.optimization_barrier(
            jnp.float64(float(_pow10(frm.scale))))
        f = data.astype(jnp.float64) / den
        return ColV(f.astype(to.to_numpy()), valid)
    if isinstance(to, T.BooleanType):
        return ColV(data != 0, valid)
    # integral: truncate toward zero on the scaled value, then wrap-narrow
    # (Scala BigDecimal.toLong semantics)
    whole = _trunc_div(
        data.astype(jnp.int64), jnp.int64(_pow10(frm.scale)))
    return ColV(whole.astype(to.to_numpy()), valid)


def _trunc_div(l: jax.Array, r: jax.Array) -> jax.Array:
    """Java integer division: truncation toward zero (numpy // floors)."""
    rs = jnp.where(r == 0, 1, r)
    q = l // rs
    rem = l - q * rs
    fix = (rem != 0) & ((l < 0) != (rs < 0))
    return jnp.where(fix, q + 1, q)


def _java_rem(l: jax.Array, r: jax.Array) -> jax.Array:
    if jnp.issubdtype(l.dtype, jnp.floating):
        # C fmod == Java %: NaN for zero divisor/inf dividend, x % inf == x
        # (the inf-divisor case restored explicitly: emulated-f64 fmod
        # NaNs out on it)
        m = jnp.fmod(l, r)
        return jnp.where(jnp.isinf(r) & jnp.isfinite(l), l, m)
    rs = jnp.where(r == 0, 1, r)
    return l - _trunc_div(l, rs) * rs


def lower(expr: E.Expression, cols: Sequence[Val], cap: int) -> Val:
    """Recursively lower a bound expression to traced jnp ops."""
    ev = lambda e: lower(e, cols, cap)  # noqa: E731

    if isinstance(expr, E.Alias):
        return ev(expr.child)

    if isinstance(expr, E.BoundReference):
        return cols[expr.ordinal]

    if isinstance(expr, E.Literal):
        if isinstance(expr.data_type, (T.StringType, T.BinaryType)):
            raw = (
                expr.value.encode("utf-8")
                if isinstance(expr.value, str)
                else (expr.value or b"")
            )
            nb = np.frombuffer(raw, dtype=np.uint8)
            # Arrow offsets must be monotonic, so the literal bytes are tiled
            # per row; XLA constant-folds the broadcast.
            if len(nb):
                chars = jnp.tile(jnp.asarray(nb), cap)
            else:
                chars = jnp.zeros(1, jnp.uint8)
            offsets = (jnp.arange(cap + 1, dtype=jnp.int32)) * len(nb)
            valid = jnp.full((cap,), expr.value is not None)
            return StrV(offsets, chars, valid)
        if isinstance(expr.data_type, T.NullType):
            return ColV(jnp.zeros(cap, jnp.bool_), jnp.zeros(cap, jnp.bool_))
        dt = _storage(expr.data_type)
        v = expr.value
        if v is not None and isinstance(expr.data_type, T.DecimalType):
            import decimal as _d

            v = int(
                _d.Decimal(str(v)).scaleb(expr.data_type.scale)
                .to_integral_value(rounding=_d.ROUND_HALF_UP))
        data = jnp.full((cap,), v if v is not None else 0, dtype=dt)
        valid = jnp.full((cap,), v is not None)
        return ColV(data, valid)

    if isinstance(expr, E.Murmur3Hash):
        # fixed-width children lower inline; string children are routed
        # through the project exec's context path (needs a host-synced
        # byte bound) — reference: HashFunctions.scala:43
        from ..ops import hashing

        vals = [ev(c) for c in expr.exprs]
        h = hashing.murmur3(vals, [c.dtype for c in expr.exprs], expr.seed)
        return ColV(h, jnp.ones(cap, jnp.bool_))

    if isinstance(expr, E._DecimalSumCheck):
        c = ev(expr.child)
        ok = _dec_fits(c.data.astype(jnp.int64), expr.result.precision)
        return ColV(jnp.where(ok, c.data, 0), c.validity & ok)

    if isinstance(expr, E._DecimalAvgEval):
        s, cnt = ev(expr.sum), ev(expr.count)
        sum_dt = expr.sum.dtype
        out = expr.result
        sd = s.data.astype(jnp.int64)
        cd = cnt.data.astype(jnp.int64)
        valid = s.validity & cnt.validity & (cd > 0)
        safe_c = jnp.where(cd <= 0, 1, cd)
        shift = jnp.int64(_pow10(out.scale - sum_dt.scale))
        # avg = round((sum * 10^shift) / count) without overflowing:
        # q*10^shift + round(rem*10^shift / count); |rem| < count so the
        # scaled remainder stays far inside int64
        q = _trunc_div(sd, safe_c)
        rem = sd - q * safe_c
        frac = _div_half_up(rem * shift, safe_c)
        res = q * shift + frac
        ok = _dec_fits(res, out.precision)
        return ColV(jnp.where(ok, res, 0), valid & ok)

    if isinstance(expr, E.NativeUDF):
        # native UDF (reference: RapidsUDF.evaluateColumnar) traced INTO
        # the fused projection program
        vals = [ev(c) for c in expr.children_]
        return expr.columnar_fn(cap, *vals)

    # ----- arithmetic -----------------------------------------------------
    if isinstance(expr, (E.Add, E.Subtract, E.Multiply)):
        out = expr.dtype
        l, r = ev(expr.left), ev(expr.right)
        if isinstance(out, T.DecimalType):
            return _decimal_arith(expr, l, r, out)
        ld, rd = _promote2(l, expr.left.dtype, r, expr.right.dtype, out)
        op = {E.Add: jnp.add, E.Subtract: jnp.subtract, E.Multiply: jnp.multiply}[type(expr)]
        return ColV(op(ld, rd), l.validity & r.validity)

    if isinstance(expr, E.Divide):
        out = expr.dtype
        l, r = ev(expr.left), ev(expr.right)
        if isinstance(out, T.DecimalType):
            return _decimal_divide(expr, l, r, out)
        ld = _cast_data(l.data, expr.left.dtype, T.DOUBLE)
        rd = _cast_data(r.data, expr.right.dtype, T.DOUBLE)
        valid = l.validity & r.validity & (rd != 0)
        return ColV(ld / jnp.where(rd == 0, 1.0, rd), valid)

    if isinstance(expr, E.IntegralDivide):
        l, r = ev(expr.left), ev(expr.right)
        ld = _cast_data(l.data, expr.left.dtype, T.LONG)
        rd = _cast_data(r.data, expr.right.dtype, T.LONG)
        valid = l.validity & r.validity & (rd != 0)
        return ColV(_trunc_div(ld, rd), valid)

    if isinstance(expr, E.Remainder):
        out = expr.dtype
        l, r = ev(expr.left), ev(expr.right)
        ld, rd = _promote2(l, expr.left.dtype, r, expr.right.dtype, out)
        valid = l.validity & r.validity
        if not out.is_floating:
            valid = valid & (rd != 0)
        return ColV(_java_rem(ld, rd), valid)

    if isinstance(expr, E.Pmod):
        out = expr.dtype
        l, r = ev(expr.left), ev(expr.right)
        ld, rd = _promote2(l, expr.left.dtype, r, expr.right.dtype, out)
        valid = l.validity & r.validity
        if not out.is_floating:
            valid = valid & (rd != 0)
        m = _java_rem(ld, rd)
        m = jnp.where(m < 0, _java_rem(m + rd, rd), m)
        return ColV(m, valid)

    if isinstance(expr, E.UnaryMinus):
        c = ev(expr.child)
        return ColV(-c.data, c.validity)

    if isinstance(expr, E.UnaryPositive):
        return ev(expr.child)

    if isinstance(expr, E.Abs):
        c = ev(expr.child)
        return ColV(jnp.abs(c.data), c.validity)

    # ----- comparisons ----------------------------------------------------
    if isinstance(expr, E._BinaryComparison):
        l, r = ev(expr.left), ev(expr.right)
        if isinstance(l, (StrV, DictV)) or isinstance(r, (StrV, DictV)):
            if not (isinstance(l, (StrV, DictV))
                    and isinstance(r, (StrV, DictV))):
                raise UnsupportedExpressionError(
                    "comparison between string and non-string")
            from .eval_strings import compare_strings, dict_compare_literal

            # dict vs literal: one compare over the dictionary, then an
            # int32 gather — O(cardinality) instead of O(total chars)
            if isinstance(l, DictV) and isinstance(expr.right, E.Literal) \
                    and not isinstance(r, DictV):
                return dict_compare_literal(expr, l, expr.right.value, cap)
            if isinstance(r, DictV) and isinstance(expr.left, E.Literal) \
                    and not isinstance(l, DictV):
                return dict_compare_literal(
                    expr, r, expr.left.value, cap, flipped=True)
            return compare_strings(
                expr, as_plain_str(l), as_plain_str(r), cap)
        tgt = (
            T.promote(expr.left.dtype, expr.right.dtype)
            if expr.left.dtype != expr.right.dtype
            else expr.left.dtype
        )
        ld, rd = _promote2(l, expr.left.dtype, r, expr.right.dtype, tgt)
        if tgt.is_floating:
            # Spark SQL ordering: NaN == NaN is TRUE and NaN sorts largest
            # (unlike IEEE; reference handles this via hasNans configs)
            nl, nr = jnp.isnan(ld), jnp.isnan(rd)
            eq = (ld == rd) | (nl & nr)
            lt = (ld < rd) | (nr & ~nl)
            gt = (rd < ld) | (nl & ~nr)
            res = {
                E.EqualTo: eq, E.EqualNullSafe: eq,
                E.LessThan: lt, E.LessThanOrEqual: lt | eq,
                E.GreaterThan: gt, E.GreaterThanOrEqual: gt | eq,
            }[type(expr)]
        else:
            ops = {
                E.EqualTo: jnp.equal,
                E.EqualNullSafe: jnp.equal,
                E.LessThan: jnp.less,
                E.LessThanOrEqual: jnp.less_equal,
                E.GreaterThan: jnp.greater,
                E.GreaterThanOrEqual: jnp.greater_equal,
            }
            res = ops[type(expr)](ld, rd)
        if isinstance(expr, E.EqualNullSafe):
            both_null = ~l.validity & ~r.validity
            val = (l.validity & r.validity & res) | both_null
            return ColV(val, jnp.ones(cap, jnp.bool_))
        return ColV(res, l.validity & r.validity)

    if isinstance(expr, E.In):
        c = ev(expr.child)
        if isinstance(c, DictV):
            from .eval_strings import string_in

            return dict_gather_col(
                c, string_in(c.dictionary, expr.values, c.dict_size))
        if isinstance(c, StrV):
            from .eval_strings import string_in

            return string_in(c, expr.values, cap)
        child_dt = expr.child.dtype
        non_null = [v for v in expr.values if v is not None]
        has_null_value = len(non_null) != len(expr.values)
        # pick a comparison dtype host-side so out-of-range literals widen
        # instead of crashing/truncating in jnp.asarray
        cmp_dt = child_dt
        if child_dt.is_floating or any(isinstance(v, float) for v in non_null):
            cmp_dt = T.DOUBLE if child_dt != T.FLOAT or any(
                isinstance(v, float) for v in non_null) else T.FLOAT
        elif isinstance(child_dt, T.DecimalType):
            # the column holds UNSCALED int64 values: scale each literal
            # to match; literals with more fractional digits than the
            # scale can never equal a column value and drop out
            import decimal as _dec

            conv = []
            for v in non_null:
                d = _dec.Decimal(str(v)).scaleb(child_dt.scale)
                if d == d.to_integral_value() and abs(int(d)) < 10 ** 18:
                    conv.append(int(d))
            non_null = conv
        elif child_dt.name in _INT_INFO:
            _, lo, hi = _INT_INFO[child_dt.name]
            if any(not (lo <= v <= hi) for v in non_null):
                cmp_dt = T.LONG
                # literals beyond int64 can never match an integral column
                non_null = [v for v in non_null if -(2**63) <= v < 2**63]
        cd = _cast_data(c.data, child_dt, cmp_dt)
        match = jnp.zeros(cap, jnp.bool_)
        for v in non_null:
            match = match | (cd == jnp.asarray(v, dtype=cd.dtype))
        valid = c.validity & (match | (not has_null_value))
        return ColV(match, valid)

    # ----- boolean logic (3-valued) --------------------------------------
    if isinstance(expr, E.And):
        # Kleene AND: false dominates null (F AND NULL = F, T AND NULL = NULL)
        l, r = ev(expr.left), ev(expr.right)
        valid = (l.validity & r.validity) | (l.validity & ~l.data) | (r.validity & ~r.data)
        return ColV(
            jnp.where(valid, jnp.where(l.validity, l.data, True) & jnp.where(r.validity, r.data, True), False),
            valid,
        )

    if isinstance(expr, E.Or):
        # Kleene OR: true dominates null
        l, r = ev(expr.left), ev(expr.right)
        valid = (l.validity & r.validity) | (l.validity & l.data) | (r.validity & r.data)
        return ColV(
            jnp.where(valid, (jnp.where(l.validity, l.data, False) | jnp.where(r.validity, r.data, False)), False),
            valid,
        )

    if isinstance(expr, E.Not):
        c = ev(expr.child)
        return ColV(~c.data, c.validity)

    # ----- null ops -------------------------------------------------------
    if isinstance(expr, E.IsNull):
        c = ev(expr.child)
        return ColV(~c.validity, jnp.ones(cap, jnp.bool_))

    if isinstance(expr, E.IsNotNull):
        c = ev(expr.child)
        return ColV(jnp.asarray(c.validity), jnp.ones(cap, jnp.bool_))

    if isinstance(expr, E.IsNan):
        c = ev(expr.child)
        d = c.data
        isnan = jnp.isnan(d) if jnp.issubdtype(d.dtype, jnp.floating) else jnp.zeros(cap, jnp.bool_)
        return ColV(isnan & c.validity, jnp.ones(cap, jnp.bool_))

    if isinstance(expr, E.Coalesce):
        out = expr.dtype
        if isinstance(out, (T.StringType, T.BinaryType)):
            from .eval_strings import as_strv, select_strings

            vals = [as_strv(ev(e), cap) for e in expr.exprs]
            valid = vals[0].validity
            for v in vals[1:]:
                valid = valid | v.validity
            sel = jnp.full(cap, len(vals) - 1, jnp.int32)
            for k in reversed(range(len(vals))):
                sel = jnp.where(vals[k].validity, k, sel)
            return select_strings(vals, sel, valid, cap)
        acc = None
        for e in expr.exprs:
            v = ev(e)
            d = _cast_data(v.data, e.dtype if e.dtype != T.NULL else out, out)
            if acc is None:
                acc = ColV(d, v.validity)
            else:
                take_new = ~acc.validity & v.validity
                acc = ColV(jnp.where(take_new, d, acc.data), acc.validity | v.validity)
        return acc

    if isinstance(expr, E.NaNvl):
        l, r = ev(expr.left), ev(expr.right)
        out = expr.dtype
        ld = _cast_data(l.data, expr.left.dtype, out)
        rd = _cast_data(r.data, expr.right.dtype, out)
        use_r = l.validity & jnp.isnan(ld)
        data = jnp.where(use_r, rd, ld)
        valid = jnp.where(use_r, r.validity, l.validity)
        return ColV(data, valid)

    # ----- conditionals ---------------------------------------------------
    if isinstance(expr, E.If):
        out = expr.dtype
        if isinstance(out, (T.StringType, T.BinaryType)):
            from .eval_strings import as_strv, select_strings

            p = ev(expr.predicate)
            t = as_strv(ev(expr.true_value), cap)
            f = as_strv(ev(expr.false_value), cap)
            cond = p.validity & p.data
            sel = jnp.where(cond, 0, 1).astype(jnp.int32)
            valid = jnp.where(cond, t.validity, f.validity)
            return select_strings([t, f], sel, valid, cap)
        p = ev(expr.predicate)
        t, f = ev(expr.true_value), ev(expr.false_value)
        td = _cast_data(t.data, expr.true_value.dtype if expr.true_value.dtype != T.NULL else out, out)
        fd = _cast_data(f.data, expr.false_value.dtype if expr.false_value.dtype != T.NULL else out, out)
        cond = p.validity & p.data
        return ColV(jnp.where(cond, td, fd), jnp.where(cond, t.validity, f.validity))

    if isinstance(expr, E.CaseWhen):
        out = expr.dtype
        if isinstance(out, (T.StringType, T.BinaryType)):
            from .eval_strings import as_strv, select_strings

            branch_vals = [as_strv(ev(v), cap) for _, v in expr.branches]
            if expr.else_value is not None:
                branch_vals.append(as_strv(ev(expr.else_value), cap))
            else:
                branch_vals.append(as_strv(None, cap))
            k_else = len(expr.branches)
            sel = jnp.full(cap, k_else, jnp.int32)
            valid = branch_vals[k_else].validity
            taken = jnp.zeros(cap, jnp.bool_)
            for k, (cond_e, _) in enumerate(expr.branches):
                cnd = ev(cond_e)
                fire = ~taken & cnd.validity & cnd.data
                sel = jnp.where(fire, k, sel)
                valid = jnp.where(fire, branch_vals[k].validity, valid)
                taken = taken | fire
            return select_strings(branch_vals, sel, valid, cap)
        if expr.else_value is not None:
            e = ev(expr.else_value)
            edt = expr.else_value.dtype
            data = _cast_data(e.data, edt if edt != T.NULL else out, out)
            valid = e.validity
        else:
            data = jnp.zeros(cap, dtype=out.to_numpy())
            valid = jnp.zeros(cap, jnp.bool_)
        taken = jnp.zeros(cap, jnp.bool_)
        for cond_e, val_e in expr.branches:
            c = ev(cond_e)
            v = ev(val_e)
            vdt = val_e.dtype
            vd = _cast_data(v.data, vdt if vdt != T.NULL else out, out)
            fire = ~taken & c.validity & c.data
            data = jnp.where(fire, vd, data)
            valid = jnp.where(fire, v.validity, valid)
            taken = taken | fire
        return ColV(data, valid)

    if isinstance(expr, E.Cast):
        frm, to = expr.child.dtype, expr.to
        c = ev(expr.child)
        if isinstance(c, DictV):
            if isinstance(to, (T.StringType, T.BinaryType)):
                return c
            from .eval_strings import lower_string_cast

            # cast the dictionary once, gather the per-row result
            out = lower_string_cast(c.dictionary, to, c.dict_size)
            if isinstance(out, StrV):  # unreachable today; stay safe
                return lower_string_cast(materialize_dict(c), to, cap)
            return dict_gather_col(c, out)
        if isinstance(c, StrV):
            from .eval_strings import lower_string_cast

            return lower_string_cast(c, to, cap)
        if isinstance(to, (T.StringType, T.BinaryType)):
            from .eval_strings import lower_cast_to_string

            return lower_cast_to_string(c, frm, cap)
        if isinstance(frm, T.DecimalType) or isinstance(to, T.DecimalType):
            return _decimal_cast(c, frm, to)
        valid = c.validity
        if frm.is_floating and isinstance(to, T.TimestampType):
            valid = valid & jnp.isfinite(c.data)  # Spark: NaN/inf -> null
        return ColV(_cast_data(c.data, frm, to), valid)

    # ----- math -----------------------------------------------------------
    if isinstance(expr, E._UnaryMathDouble):
        c = ev(expr.child)
        x = _cast_data(c.data, expr.child.dtype, T.DOUBLE)
        fns = {
            E.Sqrt: jnp.sqrt, E.Exp: jnp.exp, E.Sin: jnp.sin, E.Cos: jnp.cos,
            E.Tan: jnp.tan, E.Asin: jnp.arcsin, E.Acos: jnp.arccos,
            E.Atan: jnp.arctan, E.Sinh: jnp.sinh, E.Cosh: jnp.cosh,
            E.Tanh: jnp.tanh, E.Cbrt: jnp.cbrt, E.Expm1: jnp.expm1,
            E.Log1p: jnp.log1p,
            E.ToDegrees: jnp.degrees, E.ToRadians: jnp.radians,
        }
        kind = type(expr)
        if kind in (E.Log, E.Log10, E.Log2, E.Log1p):
            # Spark: null when x <= 0 (or <= -1 for log1p); NaN passes the
            # guard (NaN <= 0 is false in Java) and yields NaN
            t = -1.0 if kind is E.Log1p else 0.0
            bad = x <= t
            safe = jnp.where(bad, 1.0 - t, x)
            base = {E.Log: jnp.log, E.Log10: jnp.log10, E.Log2: jnp.log2,
                    E.Log1p: jnp.log1p}[kind]
            r = base(safe)
            # emulated-f64 backends lose inf through the kernel: log(inf)
            # is inf by IEEE, restore it explicitly
            r = jnp.where(jnp.isposinf(x), jnp.inf, r)
            return ColV(r, c.validity & ~bad)
        r = fns[kind](x)
        if kind is E.Sqrt:
            r = jnp.where(jnp.isposinf(x), jnp.inf, r)
        elif kind is E.Tanh:
            # emulated tanh NaNs out for large |x|; the limit is +-1
            r = jnp.where(jnp.abs(x) > 30.0, jnp.sign(x), r)
        elif kind in (E.Sinh, E.Cosh):
            r = jnp.where(jnp.isposinf(x), jnp.inf, r)
            if kind is E.Sinh:
                r = jnp.where(jnp.isneginf(x), -jnp.inf, r)
            else:
                r = jnp.where(jnp.isneginf(x), jnp.inf, r)
        return ColV(r, c.validity)

    if isinstance(expr, (E.Floor, E.Ceil)):
        c = ev(expr.child)
        if not expr.child.dtype.is_floating:
            return c
        fn = jnp.floor if isinstance(expr, E.Floor) else jnp.ceil
        x = c.data
        # emulated-f64 floor/ceil NaN out on +-inf; they are identities
        # there, and the long cast saturates them
        d = jnp.where(jnp.isfinite(x), fn(jnp.where(jnp.isfinite(x), x, 0.0)),
                      x)
        return ColV(_cast_data(d, T.DOUBLE, T.LONG), c.validity)

    if isinstance(expr, E.Round):
        c = ev(expr.child)
        dt = expr.child.dtype
        s = expr.scale
        if dt.is_floating:
            f = 10.0 ** s
            x = c.data.astype(jnp.float64)
            r = jnp.sign(x) * jnp.floor(jnp.abs(x) * f + 0.5) / f
            return ColV(r.astype(dt.to_numpy()), c.validity)
        if s >= 0:
            return c
        f = int(10 ** (-s))
        x = c.data.astype(jnp.int64)
        r = jnp.sign(x) * ((jnp.abs(x) + f // 2) // f) * f
        return ColV(r.astype(dt.to_numpy()), c.validity)

    if isinstance(expr, E.Rint):
        # Math.rint = round half to even, built from floor + fraction
        # compare: the composed form stays correct on pair-emulated f64
        # where the fused round primitive drops the low word at .5 ties
        c = ev(expr.child)
        x = _cast_data(c.data, expr.child.dtype, T.DOUBLE)
        fin = jnp.isfinite(x)
        xs = jnp.where(fin, x, 0.0)
        f = jnp.floor(xs)
        d = xs - f
        even_down = (f % 2.0) == 0.0
        r = jnp.where(
            d > 0.5, f + 1.0,
            jnp.where(d < 0.5, f, jnp.where(even_down, f, f + 1.0)))
        return ColV(jnp.where(fin, r, x), c.validity)

    if isinstance(expr, E.Pow):
        l, r = ev(expr.left), ev(expr.right)
        ld = _cast_data(l.data, expr.left.dtype, T.DOUBLE)
        rd = _cast_data(r.data, expr.right.dtype, T.DOUBLE)
        return ColV(jnp.power(ld, rd), l.validity & r.validity)

    if isinstance(expr, E.Atan2):
        l, r = ev(expr.left), ev(expr.right)
        ld = _cast_data(l.data, expr.left.dtype, T.DOUBLE)
        rd = _cast_data(r.data, expr.right.dtype, T.DOUBLE)
        return ColV(jnp.arctan2(ld, rd), l.validity & r.validity)

    if isinstance(expr, E.Signum):
        c = ev(expr.child)
        return ColV(jnp.sign(_cast_data(c.data, expr.child.dtype, T.DOUBLE)), c.validity)

    # ----- bitwise --------------------------------------------------------
    if isinstance(expr, (E.BitwiseAnd, E.BitwiseOr, E.BitwiseXor)):
        out = expr.dtype
        l, r = ev(expr.left), ev(expr.right)
        ld, rd = _promote2(l, expr.left.dtype, r, expr.right.dtype, out)
        op = {
            E.BitwiseAnd: jnp.bitwise_and,
            E.BitwiseOr: jnp.bitwise_or,
            E.BitwiseXor: jnp.bitwise_xor,
        }[type(expr)]
        return ColV(op(ld, rd), l.validity & r.validity)

    if isinstance(expr, E.BitwiseNot):
        c = ev(expr.child)
        return ColV(~c.data, c.validity)

    if isinstance(expr, (E.ShiftLeft, E.ShiftRight, E.ShiftRightUnsigned)):
        l, r = ev(expr.left), ev(expr.right)
        bits = l.data.dtype.itemsize * 8
        sh = (r.data & (bits - 1)).astype(l.data.dtype)
        if isinstance(expr, E.ShiftLeft):
            res = l.data << sh
        elif isinstance(expr, E.ShiftRight):
            res = l.data >> sh
        else:
            u = l.data.astype(jnp.uint32 if bits == 32 else jnp.uint64)
            res = (u >> sh.astype(u.dtype)).astype(l.data.dtype)
        return ColV(res, l.validity & r.validity)

    # ----- strings (minimal) ----------------------------------------------
    if isinstance(expr, E.Length):
        c = ev(expr.child)
        if isinstance(c, DictV):
            # char-count the dictionary entries, gather through the codes
            d = c.dictionary
            cont_d = ((d.chars & 0xC0) == 0x80).astype(jnp.int32)
            cs_d = jnp.concatenate(
                [jnp.zeros(1, jnp.int32), jnp.cumsum(cont_d)])
            bl = d.offsets[1:] - d.offsets[:-1]
            cl = cs_d[d.offsets[1:]] - cs_d[d.offsets[:-1]]
            return dict_gather_col(
                c, ColV((bl - cl).astype(jnp.int32),
                        jnp.ones(c.dict_size, jnp.bool_)))
        if not isinstance(c, StrV):
            raise UnsupportedExpressionError("length() on non-string")
        cont = ((c.chars & 0xC0) == 0x80).astype(jnp.int32)
        cs = jnp.concatenate([jnp.zeros(1, jnp.int32), jnp.cumsum(cont)])
        byte_len = c.offsets[1:] - c.offsets[:-1]
        cont_in_row = cs[c.offsets[1:]] - cs[c.offsets[:-1]]
        return ColV((byte_len - cont_in_row).astype(jnp.int32), c.validity)

    from .eval_strings import lower_strings

    sv = lower_strings(expr, ev, cap)
    if sv is not None:
        return sv

    from .eval_datetime import lower_datetime

    dv = lower_datetime(expr, ev, cap)
    if dv is not None:
        return dv

    raise UnsupportedExpressionError(f"no TPU lowering for {type(expr).__name__}")


# ---------------------------------------------------------------------------
# Compile cache + public entry points
# ---------------------------------------------------------------------------
def _col_to_vals(col: DeviceColumn) -> Val:
    if col.is_dict:
        from ..columnar import column as _colmod

        if _colmod.DICT_MATERIALIZE_EAGERLY:
            col = col.materialize()
        else:
            return col.dictv
    if col.is_string:
        return StrV(col.offsets, col.chars, col.validity)
    return ColV(col.data, col.validity)


@functools.lru_cache(maxsize=512)
def _compiled(exprs: Tuple[E.Expression, ...], cap: int, schema_sig: tuple):
    """One XLA executable per (bound exprs, capacity bucket, input layout)."""

    def eval_exprs(cols):
        return [lower(e, cols, cap) for e in exprs]

    return jax.jit(eval_exprs)


@functools.lru_cache(maxsize=512)
def _compiled_elided(exprs: Tuple[E.Expression, ...], cap: int,
                     schema_sig: tuple, nonnull: Tuple[bool, ...]):
    """Like :func:`_compiled`, but with the plan analyzer's validity
    elision applied at entry: statically NON_NULL columns swap their
    stored validity plane for the iota-derived liveness mask (see
    ops/filter_gather.elide_validity) — the traced row count makes the
    mask, so the plane is never read from HBM."""

    def eval_exprs(cols, num_rows):
        from ..ops.filter_gather import elide_validity, live_of

        live = live_of(num_rows, cap)
        cols = elide_validity(cols, live, nonnull)
        return [lower(e, cols, cap) for e in exprs]

    return jax.jit(eval_exprs)


def tpu_supports(expr: E.Expression, schema: T.StructType) -> Tuple[bool, str]:
    """Static supportability probe used by the planner: trace with abstract
    values; UnsupportedExpressionError means fallback."""
    import jax.numpy as _jnp  # noqa: F401

    try:
        bound = E.bind_references(expr, schema)
        cap = 8
        cols = []
        for f in schema.fields:
            if isinstance(f.dataType, (T.StringType, T.BinaryType)):
                cols.append(
                    StrV(
                        jnp.zeros(cap + 1, jnp.int32),
                        jnp.zeros(1, jnp.uint8),
                        jnp.zeros(cap, jnp.bool_),
                    )
                )
            else:
                cols.append(
                    ColV(
                        jnp.zeros(cap, dtype=f.dataType.to_numpy()),
                        jnp.zeros(cap, jnp.bool_),
                    )
                )
        jax.eval_shape(lambda cs: lower(bound, cs, cap), cols)
        return True, ""
    except UnsupportedExpressionError as e:
        return False, str(e)
    except TypeError as e:
        return False, str(e)
    except Exception as e:  # noqa: BLE001
        # a native UDF's columnar function may raise anything during the
        # abstract trace (reference: a RapidsUDF throwing in
        # evaluateColumnar falls back to the row path)
        if any(isinstance(n, E.NativeUDF)
               for n in _walk_expressions(expr)):
            return False, f"native UDF columnar trace failed: {e}"
        raise


def _walk_expressions(expr: E.Expression):
    yield expr
    for c in expr.children:
        yield from _walk_expressions(c)


def evaluate_projection(
    bound_exprs: Sequence[E.Expression], batch: ColumnarBatch,
    nonnull: Optional[Tuple[bool, ...]] = None,
    conf=None,
) -> List[DeviceColumn]:
    """Evaluate bound expressions against a batch, one fused XLA call.

    Reference analog: GpuProjectExec.project (basicPhysicalOperators.scala:48)
    doing per-expression columnarEval; here it is a single executable.
    ``nonnull``: per-column validity-elision flags (the plan analyzer's
    nullability lattice; a flagged column's stored validity plane is
    skipped in favor of the liveness mask — bit-identical, see
    ops/filter_gather.elide_validity). When not given, flags derive from
    the batch schema through plananalysis.entry_nonnull_flags IF a
    ``conf`` (RapidsConf) is passed — which honors
    sql.analysis.nullElision.enabled, so disabling the conf forces the
    mask-carrying path here exactly as it does in the exec pipelines.
    With neither, the mask-carrying path runs.
    """
    if nonnull is None:
        if conf is not None:
            from ..plugin.plananalysis import entry_nonnull_flags

            nonnull = entry_nonnull_flags(batch.schema, conf)
        else:
            nonnull = ()
    cap = batch.capacity  # batches carry their bucket even zero-column
    from ..exec.base import batch_signature, count_scalar

    schema_sig = batch_signature(batch)
    if nonnull and any(nonnull):
        fn = _compiled_elided(tuple(bound_exprs), cap, schema_sig,
                              tuple(nonnull))
        vals = fn([_col_to_vals(c) for c in batch.columns],
                  count_scalar(batch.num_rows_lazy))
    else:
        fn = _compiled(tuple(bound_exprs), cap, schema_sig)
        vals = fn([_col_to_vals(c) for c in batch.columns])
    out = []
    for e, v in zip(bound_exprs, vals):
        if isinstance(v, DictV):
            out.append(DeviceColumn.dict_encoded(e.dtype, batch.num_rows, v))
        elif isinstance(v, StrV):
            out.append(
                DeviceColumn(e.dtype, batch.num_rows, None, v.validity, v.offsets, v.chars)
            )
        else:
            out.append(DeviceColumn(e.dtype, batch.num_rows, v.data, v.validity))
    return out
