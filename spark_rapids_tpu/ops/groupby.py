"""Sort-based group-by aggregation kernels.

Reference analog: cudf ``table.groupBy(...).aggregate(...)`` as called from
GpuHashAggregateExec (aggregate.scala:806). cudf hash-aggregates; on TPU a
hash table of dynamic size fights XLA, so the design is the classic
sort-compatible alternative the same exec supports: stable-sort rows by the
grouping keys (ops/sort.py), derive segment ids from key-change boundaries,
and reduce each segment with ``jax.ops.segment_*`` — one fused XLA program,
fully static shapes (worst case: every row its own group, so num_segments =
capacity). Null keys form their own group (Spark semantics); aggregate
inputs skip nulls; NaN groups as equal to NaN.

Reductions provided: count_star, count, sum, min, max, first/last (+
ignore-null variants). Average is decomposed by the exec layer into
sum+count partials, mirroring Spark's update/merge model.

String min/max (lexicographic, Spark UTF8String byte order) reduce via
RANKS so every numeric fast path applies unchanged: a dictionary-encoded
column ranks its (small) dictionary once in sorted-code order — the cudf
dictionary32 trick, O(cardinality) — while a plain string column ranks
rows with one radix-chunk sort; the winning rank then maps back to a
code (dict) or row (plain) and the string is gathered out.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from .. import types as T
from ..expr.eval import ColV, DictV, StrV, Val
from ..expr.values import materialize_dict
from .filter_gather import gather
from .sort import SortOrder, sort_with_radix_keys, string_chunk_keys


def segment_ids_from_radix_keys(
    sorted_radix_keys: Sequence[jax.Array],
    num_rows: Union[int, jax.Array],
) -> Tuple[jax.Array, jax.Array]:
    """(segment_ids, num_segments) from the co-sorted radix key arrays.

    Two adjacent rows belong to the same group iff every radix key matches
    — the radix encoding already folds Spark's equality rules in
    (null==null via the null-rank key, NaN canonicalized, -0.0 -> 0.0,
    strings as byte chunks). Padding rows get an out-of-range id so every
    segment_* scatter drops them.
    """
    cap = sorted_radix_keys[0].shape[0]
    eq = jnp.ones(cap, jnp.bool_)
    for k in sorted_radix_keys:
        eq = eq & (k == jnp.roll(k, 1))
    from .filter_gather import live_of

    live = live_of(num_rows, cap)
    new_seg = live & (~eq | (jnp.arange(cap) == 0))
    seg = jnp.cumsum(new_seg.astype(jnp.int32)) - 1
    num_segments = jnp.max(jnp.where(live, seg, -1)) + 1
    seg = jnp.where(live, seg, cap)  # out-of-range for padding
    return seg, num_segments


_INT_MIN_MAX = {
    jnp.dtype(jnp.int8): (-(2**7), 2**7 - 1),
    jnp.dtype(jnp.int16): (-(2**15), 2**15 - 1),
    jnp.dtype(jnp.int32): (-(2**31), 2**31 - 1),
    jnp.dtype(jnp.int64): (-(2**63), 2**63 - 1),
}


def _segment_count(valid: jax.Array, seg: jax.Array, ncap: int) -> jax.Array:
    return jax.ops.segment_sum(valid.astype(jnp.int64), seg, num_segments=ncap)


def segment_reduce(
    op: str,
    col: Optional[ColV],
    seg: jax.Array,
    ncap: int,
    live: jax.Array,
) -> ColV:
    """One aggregation over segments. Returns (ncap,)-shaped ColV."""
    if op == "count_star":
        cnt = jax.ops.segment_sum(live.astype(jnp.int64), seg, num_segments=ncap)
        return ColV(cnt, jnp.ones(ncap, jnp.bool_))
    assert col is not None
    valid = col.validity & live
    data = col.data
    if op == "count":
        cnt = _segment_count(valid, seg, ncap)
        return ColV(cnt, jnp.ones(ncap, jnp.bool_))
    cnt = _segment_count(valid, seg, ncap)
    has = cnt > 0
    if op == "sum":
        z = jnp.zeros((), data.dtype)
        s = jax.ops.segment_sum(jnp.where(valid, data, z), seg, num_segments=ncap)
        return ColV(s, has)
    if op in ("min", "max"):
        isfloat = jnp.issubdtype(data.dtype, jnp.floating)
        if isfloat:
            if op == "max":
                # Spark: NaN is the largest double; IEEE max propagates NaN,
                # which is exactly the desired result, so plain masking works
                fill = jnp.array(-jnp.inf, data.dtype)
                d = jnp.where(valid, data, fill)
                r = jax.ops.segment_max(d, seg, num_segments=ncap)
            else:
                # min must *skip* NaN unless the group is all-NaN
                nan_as_inf = jnp.where(jnp.isnan(data), jnp.inf, data)
                d = jnp.where(valid, nan_as_inf, jnp.inf).astype(data.dtype)
                r = jax.ops.segment_min(d, seg, num_segments=ncap)
                non_nan = _segment_count(valid & ~jnp.isnan(data), seg, ncap)
                r = jnp.where((non_nan == 0) & has, jnp.nan, r)
        else:
            lo, hi = _INT_MIN_MAX.get(
                jnp.dtype(data.dtype), (0, 1)
            )
            if data.dtype == jnp.bool_:
                fill = jnp.array(op == "min", jnp.bool_)
                d = jnp.where(valid, data, fill)
                r = (
                    jax.ops.segment_max(d, seg, num_segments=ncap)
                    if op == "max"
                    else jax.ops.segment_min(d, seg, num_segments=ncap)
                )
            else:
                fill = jnp.array(lo if op == "max" else hi, data.dtype)
                d = jnp.where(valid, data, fill)
                r = (
                    jax.ops.segment_max(d, seg, num_segments=ncap)
                    if op == "max"
                    else jax.ops.segment_min(d, seg, num_segments=ncap)
                )
        z = jnp.zeros((), r.dtype)
        return ColV(jnp.where(has, r, z), has)
    if op in ("first", "last", "first_ignorenulls", "last_ignorenulls"):
        cap = data.shape[0]
        idx = jnp.arange(cap, dtype=jnp.int32)
        consider = valid if op.endswith("ignorenulls") else live
        big = jnp.int32(cap)
        if op.startswith("first"):
            pos = jax.ops.segment_min(
                jnp.where(consider, idx, big), seg, num_segments=ncap
            )
        else:
            pos = jax.ops.segment_max(
                jnp.where(consider, idx, jnp.int32(-1)), seg, num_segments=ncap
            )
        found = (pos >= 0) & (pos < cap)
        safe = jnp.clip(pos, 0, cap - 1)
        vals = jnp.take(data, safe, mode="clip")
        val_valid = jnp.take(col.validity, safe, mode="clip") & found
        z = jnp.zeros((), vals.dtype)
        return ColV(jnp.where(val_valid, vals, z), val_valid)
    raise ValueError(f"unknown aggregation op {op!r}")


def _dict_rank(v: DictV) -> Tuple[jax.Array, ColV]:
    """(order, per-row rank) of a dictionary-encoded column: ``order[p]``
    is the dictionary index of the p-th smallest entry (lexicographic
    UTF8 byte order), and the per-row rank rides the codes through one
    int32 gather. ``max_len`` is static metadata — no host sync."""
    d = v.dictionary
    keys = string_chunk_keys(
        StrV(d.offsets, d.chars, jnp.ones(v.dict_size, jnp.bool_)),
        SortOrder(True, True), max(1, v.max_len))
    iota = jnp.arange(v.dict_size, dtype=jnp.int32)
    sorted_ops = lax.sort(list(keys) + [iota], num_keys=len(keys),
                          is_stable=True)
    order = sorted_ops[-1]
    rank = jnp.zeros(v.dict_size, jnp.int32).at[order].set(
        iota, mode="drop")
    from ..expr.values import dict_gather_col

    return order, dict_gather_col(v, ColV(rank, jnp.ones(
        v.dict_size, jnp.bool_)))


def _plain_rank(v: StrV, num_rows, max_len: int) -> Tuple[jax.Array, ColV]:
    """(perm, per-row rank) of a plain string column via one radix-chunk
    sort: ``perm[p]`` is the row holding the p-th smallest string."""
    cap = v.offsets.shape[0] - 1
    perm, _ = sort_with_radix_keys(
        [v], [T.STRING], [SortOrder(True, True)], num_rows, [max_len])
    rank = jnp.zeros(cap, jnp.int32).at[perm].set(
        jnp.arange(cap, dtype=jnp.int32), mode="drop")
    return perm, ColV(rank, v.validity)


def string_minmax_ranks(
    value_cols: List[Optional[ColV]],
    agg_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    str_val_max_lens: Sequence[int] = (),
):
    """Replace string-typed min/max inputs with their rank columns.

    Returns ``recover``: agg index -> callable mapping the reduced rank
    column back to the winning strings (a DictV rewrap for dictionary
    columns, a row gather for plain ones). ``str_val_max_lens`` supplies
    the static byte-length bound per string-typed min/max input, in
    order of appearance (dictionary columns ignore theirs — their bound
    is static metadata)."""
    from .filter_gather import gather_string

    recover = {}
    rank_cache = {}  # id(value) -> (order/perm, rank rows): min(s)+max(s)
    si = 0           # over one column share ONE rank sort
    for ai, (op, v) in enumerate(zip(agg_ops, value_cols)):
        if op not in ("min", "max") or not isinstance(v, (StrV, DictV)):
            continue
        ml = str_val_max_lens[si] if si < len(str_val_max_lens) else 64
        si += 1
        cached = rank_cache.get(id(v))
        if cached is None:
            cached = rank_cache[id(v)] = (
                _dict_rank(v) if isinstance(v, DictV)
                else _plain_rank(v, num_rows, ml))
        order_or_perm, rank_rows = cached
        if isinstance(v, DictV):
            def rec(r: ColV, order=order_or_perm, t=v) -> DictV:
                hi = max(t.dict_size - 1, 0)
                codes = jnp.take(order, jnp.clip(r.data, 0, hi), mode="clip")
                return DictV(codes.astype(jnp.int32), t.dictionary,
                             r.validity, t.mat_cap, t.max_len, t.unique)
        else:
            def rec(r: ColV, perm=order_or_perm, src=v) -> StrV:
                cap = src.offsets.shape[0] - 1
                rows = jnp.take(perm, jnp.clip(r.data, 0, cap - 1),
                                mode="clip")
                # winners are distinct source rows, so the source byte
                # pool bounds the output
                return gather_string(src, rows, r.validity,
                                     int(src.chars.shape[0]))
        value_cols[ai] = rank_rows
        recover[ai] = rec
    return recover


def _jnp_reduce_dtype(dtype) -> T.DataType:
    """Engine DataType standing in for a jnp dtype when only the RADIX
    encoding family matters (float total-order trick vs bool cast vs int
    sign flip — :func:`ops.sort.fixed_radix_keys` reads the VALUE dtype
    from the array itself)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return T.DOUBLE
    if jnp.dtype(dtype) == jnp.bool_:
        return T.BOOLEAN
    return T.LONG


def _radix_groupby(
    key_cols: Sequence[Val],
    value_cols: Sequence[Optional[ColV]],
    agg_ops: Sequence[str],
    perm: jax.Array,
    radix: Sequence[jax.Array],
    live_in: jax.Array,
    cap: int,
) -> Tuple[List[Val], List[ColV], jax.Array]:
    """RADIX strategy: every aggregate family reduces on the tiled
    radix-binned machinery (ops/radix_bin.py) over the sort's binned row
    order — zero scatter instructions, no one-hot, and every per-row
    temporary is tile-sized, so the program's bytes-accessed approaches
    the layout bound instead of amplifying it ~25x (BENCH_r09, ROADMAP
    open item 1). Streams stay in ORIGINAL row order; the loop gathers
    one tile at a time. Integer sums/counts are bit-identical to the
    other lowerings (prefix sums wrap mod 2^64); float sums use the
    NORMAL/BIG/flag stream split (order-insensitive, strictly tighter
    than the matmul hi/lo split — AUTO only picks RADIX for exact float
    sums when variableFloatAgg opted in); min/max/first/last reduce as
    winner-ROW streams via the sort machinery's total-order words, so
    Spark's NaN-largest / -0.0 folding falls out of the encoding."""
    from . import radix_bin as RBX

    # streams are NOT materialized here: every spec carries a builder
    # closure that gathers the RAW column one tile at a time inside the
    # reduction loop and derives its stream in tile-local registers
    # (XLA CSE collapses repeated gathers of the same column), so no
    # cap-sized derived array is ever charged against the byte budget
    adds: List[RBX.AddSpec] = []
    poss: List[RBX.PosSpec] = []
    winners: List[RBX.MinMaxSpec] = []
    plan: List[tuple] = []
    cnt_idx: dict = {}
    nfam = {"u64": 0, "u32": 0, "f64": 0, "or": 0}

    def add_spec(fam, build, is_or=False):
        adds.append(RBX.AddSpec(build, {
            "u64": jnp.uint64, "u32": jnp.uint32, "f64": jnp.float64,
            "or": jnp.uint64}[fam], is_or=is_or))
        nfam[fam] += 1
        return nfam[fam] - 1

    def want_count(valid, key):
        # valid None = live rows only (dead rows zero structurally)
        if key not in cnt_idx:
            if valid is None:
                def build(tk):
                    return jnp.ones(tk.p_t.shape[0], jnp.uint32)
            else:
                def build(tk, v=valid):
                    return tk.take(v).astype(jnp.uint32)
            cnt_idx[key] = add_spec("u32", build)
        return cnt_idx[key]

    # pos stream 0: the group-representative (first live) row, for key
    # output — stability makes first-in-sorted == min original row
    poss.append(RBX.PosSpec(
        lambda tk: jnp.ones(tk.p_t.shape[0], jnp.bool_), "min"))
    for ai, (op, v) in enumerate(zip(agg_ops, value_cols)):
        if op == "count_star":
            plan.append(("cnt", want_count(None, ("star",))))
        elif op == "count":
            plan.append(("cnt", want_count(v.validity, ("c", ai))))
        elif op == "sum" and not jnp.issubdtype(v.data.dtype, jnp.floating):
            ci = want_count(v.validity, ("c", ai))

            def ibuild(tk, d=v.data, vv=v.validity):
                return jnp.where(tk.take(vv),
                                 tk.take(d).astype(jnp.int64),
                                 jnp.int64(0)).astype(jnp.uint64)

            plan.append(("isum", (add_spec("u64", ibuild), ci,
                                  v.data.dtype)))
        elif op == "sum":
            ci = want_count(v.validity, ("c", ai))

            def fpart(tk, i, d=v.data, vv=v.validity):
                return RBX.float_sum_streams(tk.take(d), tk.take(vv))[i]

            fi = add_spec("f64", lambda tk, f=fpart: f(tk, 0))
            add_spec("f64", lambda tk, f=fpart: f(tk, 1))
            oi = add_spec("or", lambda tk, f=fpart: f(tk, 2), is_or=True)
            plan.append(("fsum", (fi, oi, ci, v.data.dtype)))
        elif op in ("min", "max"):
            rdt = _jnp_reduce_dtype(v.data.dtype)

            def wbuild(tk, d=v.data, vv=v.validity, rdt=rdt, op=op):
                return RBX.order_word(tk.take(d), tk.take(vv), rdt, op)

            wi = len(winners)
            winners.append(RBX.MinMaxSpec(
                wbuild, lambda tk, vv=v.validity: tk.take(vv), op))
            plan.append(("winner", (wi, v)))
        elif op in ("first", "last", "first_ignorenulls",
                    "last_ignorenulls"):
            if op.endswith("ignorenulls"):
                def cons(tk, vv=v.validity):
                    return tk.take(vv)
            else:
                def cons(tk):
                    return jnp.ones(tk.p_t.shape[0], jnp.bool_)
            pi = len(poss)
            poss.append(RBX.PosSpec(
                cons, "min" if op.startswith("first") else "max"))
            plan.append(("pos", (pi, v)))
        else:
            raise ValueError(f"unknown aggregation op {op!r}")

    out = RBX.tiled_segment_groupby(
        perm, radix, live_in, adds, poss, winners)
    nseg = out.nseg
    out_live = jnp.arange(cap, dtype=jnp.int32) < nseg

    def row_col(rw, v) -> ColV:
        safe = jnp.clip(rw, 0, cap - 1)
        vals = jnp.take(v.data, safe, mode="clip")
        vv = jnp.take(v.validity, safe, mode="clip") & (rw >= 0)
        return ColV(jnp.where(vv, vals, jnp.zeros((), vals.dtype)), vv)

    out_aggs: List[ColV] = []
    for kind, payload in plan:
        if kind == "cnt":
            out_aggs.append(ColV(out.u32[payload].astype(jnp.int64),
                                 jnp.ones(cap, jnp.bool_)))
        elif kind == "isum":
            si, ci, dt = payload
            data = out.u64[si].astype(jnp.int64)
            if dt != jnp.int64:
                data = data.astype(dt)  # mod-2^32 of a mod-2^64 sum: exact
            has = out.u32[ci] > 0
            out_aggs.append(ColV(jnp.where(has, data,
                                           jnp.zeros((), data.dtype)), has))
        elif kind == "fsum":
            fi, oi, ci, dt = payload
            s = RBX.combine_float_sum(out.f64[fi], out.f64[fi + 1],
                                      out.flags[oi]).astype(dt)
            has = out.u32[ci] > 0
            out_aggs.append(ColV(jnp.where(has, s, jnp.zeros((), dt)), has))
        elif kind == "pos":
            pi, v = payload
            out_aggs.append(row_col(out.pos_rows[pi], v))
        else:
            wi, v = payload
            out_aggs.append(row_col(out.winner_rows[wi], v))

    rep = jnp.clip(out.pos_rows[0], 0, cap - 1)
    out_keys = gather(key_cols, rep, out_live)
    out_aggs = [
        ColV(jnp.where(out_live, a.data, jnp.zeros((), a.data.dtype)),
             a.validity & out_live)
        for a in out_aggs
    ]
    return out_keys, out_aggs, nseg


def sort_groupby(
    key_cols: Sequence[Val],
    key_dtypes: Sequence[T.DataType],
    value_cols: Sequence[Optional[ColV]],
    agg_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    str_max_lens: Sequence[int] = (),
    radix_reduce: bool = False,
) -> Tuple[List[Val], List[ColV], jax.Array]:
    """Full groupby via sort: sort by keys, segment, reduce.

    ``value_cols[i]`` is the (pre-cast) input for ``agg_ops[i]`` (None for
    count_star). Returns (group key columns, aggregate columns, num_groups);
    outputs are compacted to the front at the input capacity.
    ``radix_reduce`` (the RADIX strategy) reduces EVERY aggregate family
    — float sums and min/max/first/last included — on the tiled
    radix-binned machinery with zero scatters (:func:`_radix_groupby`).
    """
    cap = (
        key_cols[0].offsets.shape[0] - 1
        if isinstance(key_cols[0], StrV)
        else key_cols[0].validity.shape[0]
    )
    from .filter_gather import live_of

    orders = [SortOrder(True, True) for _ in key_cols]
    perm, radix = sort_with_radix_keys(
        key_cols, key_dtypes, orders, num_rows, str_max_lens
    )
    live_in = live_of(num_rows, cap)
    if radix_reduce:
        return _radix_groupby(key_cols, value_cols, agg_ops, perm, radix,
                              live_in, cap)
    # dead rows sort last (pad_rank is the leading sort key), so liveness in
    # sorted order is the permuted mask — equivalently a prefix of n_live.
    # Using the RAW mask here mislabels rows whenever the mask isn't already
    # a prefix (e.g. after a fused filter) — a real dropped-row bug.
    live = jnp.take(live_in, perm, mode="clip")
    sorted_keys = gather(key_cols, perm, live)
    sorted_vals: List[Optional[ColV]] = []
    for v in value_cols:
        if v is None:
            sorted_vals.append(None)
        else:
            g = gather([v], perm, live)[0]
            assert isinstance(g, ColV)
            sorted_vals.append(g)
    seg, nseg = segment_ids_from_radix_keys(radix, live)

    # representative row (first) of each segment, for key output
    idx = jnp.arange(cap, dtype=jnp.int32)
    first_row = jax.ops.segment_min(
        jnp.where(live, idx, jnp.int32(cap)), seg, num_segments=cap
    )
    out_live = jnp.arange(cap, dtype=jnp.int32) < nseg
    first_row = jnp.clip(first_row, 0, cap - 1)
    out_keys = gather(sorted_keys, first_row, out_live)
    out_aggs = [
        segment_reduce(op, v, seg, cap, live)
        for op, v in zip(agg_ops, sorted_vals)
    ]
    # aggregate outputs: zero validity in dead slots
    out_aggs = [
        ColV(jnp.where(out_live, a.data, jnp.zeros((), a.data.dtype)),
             a.validity & out_live)
        for a in out_aggs
    ]
    return out_keys, out_aggs, nseg


def reduce_no_keys(
    value_cols: Sequence[Optional[ColV]],
    agg_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    str_val_max_lens: Sequence[int] = (),
) -> List[Val]:
    """Grand aggregate (no grouping keys): one output row.

    Reference analog: cudf reduce path in aggregate.scala:806.
    String min/max inputs reduce through their lexicographic ranks (see
    :func:`string_minmax_ranks`).
    """
    if not value_cols:
        return []
    value_cols = list(value_cols)
    recover = string_minmax_ranks(
        value_cols, agg_ops, num_rows, str_val_max_lens)
    cap = next(
        v.validity.shape[0] for v in value_cols if v is not None
    ) if any(v is not None for v in value_cols) else 0
    if cap == 0:
        # only count(*) over an implicit capacity — caller supplies rows
        if isinstance(num_rows, jax.Array) and num_rows.dtype == jnp.bool_:
            cnt = jnp.sum(num_rows.astype(jnp.int64)).reshape(1)
        else:
            cnt = jnp.asarray(num_rows, jnp.int64).reshape(1)
        return [ColV(cnt, jnp.ones(1, jnp.bool_)) for _ in agg_ops]
    from .filter_gather import live_of

    live = live_of(num_rows, cap)
    outs: List[Val] = []
    seg = None  # built lazily for the first/last path only
    for op, v in zip(agg_ops, value_cols):
        outs.append(_reduce_one(op, v, live))
        if outs[-1] is None:
            if seg is None:
                seg = jnp.where(live, 0, 1)
            outs[-1] = segment_reduce(op, v, seg, 1, live)
    for ai, rec in recover.items():
        outs[ai] = rec(outs[ai])
    return outs


def _reduce_one(op: str, col: Optional[ColV], live: jax.Array) -> Optional[ColV]:
    """Grand-aggregate reduction as a PLAIN masked jnp reduce.

    scatter-based segment_* to one segment costs ~60ns/row on TPU
    (emulated-int64 scatter adds); a tree reduce is HBM-bandwidth bound.
    Returns None for ops that still need the segment path (first/last)."""
    if op == "count_star":
        cnt = jnp.sum(live.astype(jnp.int64)).reshape(1)
        return ColV(cnt, jnp.ones(1, jnp.bool_))
    assert col is not None
    valid = col.validity & live
    data = col.data
    if op == "count":
        cnt = jnp.sum(valid.astype(jnp.int64)).reshape(1)
        return ColV(cnt, jnp.ones(1, jnp.bool_))
    has = jnp.any(valid).reshape(1)
    if op == "sum":
        z = jnp.zeros((), data.dtype)
        s = jnp.sum(jnp.where(valid, data, z)).reshape(1)
        return ColV(s, has)
    if op in ("min", "max"):
        if jnp.issubdtype(data.dtype, jnp.floating):
            if op == "max":
                fill = jnp.array(-jnp.inf, data.dtype)
                r = jnp.max(jnp.where(valid, data, fill)).reshape(1)
            else:
                nan_as_inf = jnp.where(jnp.isnan(data), jnp.inf, data)
                d = jnp.where(valid, nan_as_inf, jnp.inf).astype(data.dtype)
                r = jnp.min(d).reshape(1)
                non_nan = jnp.sum(
                    (valid & ~jnp.isnan(data)).astype(jnp.int32)).reshape(1)
                r = jnp.where((non_nan == 0) & has, jnp.nan, r)
        elif data.dtype == jnp.bool_:
            fill = jnp.array(op == "min", jnp.bool_)
            d = jnp.where(valid, data, fill)
            r = (jnp.max(d) if op == "max" else jnp.min(d)).reshape(1)
        else:
            lo, hi = _INT_MIN_MAX.get(jnp.dtype(data.dtype), (0, 1))
            fill = jnp.array(lo if op == "max" else hi, data.dtype)
            d = jnp.where(valid, data, fill)
            r = (jnp.max(d) if op == "max" else jnp.min(d)).reshape(1)
        z = jnp.zeros((), r.dtype)
        return ColV(jnp.where(has, r, z), has)
    return None


# ---------------------------------------------------------------------------
# Hash-bucket groupby (TPU fast path)
# ---------------------------------------------------------------------------
def hash_groupby(
    key_cols: Sequence[ColV],
    key_dtypes: Sequence[T.DataType],
    value_cols: Sequence[Optional[ColV]],
    agg_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    num_buckets: int,
    approx_float_sum: bool = False,
    reduce_strategy: Optional[str] = None,
    report: Optional[dict] = None,
) -> Tuple[List[ColV], List[ColV], jax.Array, jax.Array]:
    """O(n) groupby: bucket keys, reduce on the MXU.

    Bucketing tiers:
      1. direct-range: when every key's value range is dense enough that
         the composed (value - min) index fits ``num_buckets`` — the
         TPC-DS dim-key/date case — buckets are injective BY CONSTRUCTION:
         no hash, no collision check, and group keys are reconstructed
         algebraically from the bucket id (zero scatter ops).
      2. murmur3 + exact collision detection (limb-matmul lookups against
         each bucket's representative row); a collision makes
         :func:`groupby_agg` fall back to the sort path.

    Sums/counts run as one-hot limb matmuls (ops/bucket_reduce.py — exact
    for integers); min/max/first/last use scatter segment ops. A float
    sum under ``approx_float_sum`` rides the matmul as hi/lo f32 limbs
    (order-insensitive, the reference's variableFloatAgg tradeoff); one
    that may not be approximate rides it as exact fixed-point limbs where
    the reduction's resolved lowering is MATMUL
    (``bucket_reduce._fixed_point_limbs``: no row-sized scatter, and no
    dependence on row order or on the split into chunks and shards), and
    is one scatter op under the other lowerings.

    ``report``, when given, is filled at trace time with how this call's
    plan lowers (exec/mesh reads it into its span counts): ``lowering``,
    a word an aggregate; ``float_sums_fixed``; ``row_scatters``, the
    row-sized ``segment_*`` operations outside any ``lax.cond``; and
    ``float_detour``, the traced flag "a fixed-point sum's detour ran".

    Returns (out_keys, out_aggs, num_groups, collision_free); outputs are
    bucket-compacted to the front at the input capacity.
    """
    from .bucket_reduce import (
        _resolve_strategy, bucket_equal_check, bucket_reduce)
    from .filter_gather import live_of
    from .hashing import murmur3
    from .sort import SortOrder, fixed_radix_keys

    cap = key_cols[0].validity.shape[0]
    B = num_buckets
    live = live_of(num_rows, cap)
    idx = jnp.arange(cap, dtype=jnp.int32)
    any_live = jnp.any(live)

    # --- tier 1: direct-range binning -----------------------------------
    direct_capable = all(not dt.is_floating for dt in key_dtypes)
    mns, spans, strides = [], [], []
    if direct_capable:
        direct_ok = any_live
        stride = jnp.int64(1)
        bucket_direct = jnp.zeros(cap, jnp.int64)
        for c, dt in zip(key_cols, key_dtypes):
            d = c.data.astype(jnp.int64)
            lv = live & c.validity
            has_val = jnp.any(lv)
            mn = jnp.where(has_val, jnp.min(jnp.where(lv, d, jnp.int64(2**62))), 0)
            mx = jnp.where(has_val, jnp.max(jnp.where(lv, d, jnp.int64(-(2**62)))), -1)
            # exact range via u64 (no overflow even at int64 extremes)
            ru = mx.astype(jnp.uint64) - mn.astype(jnp.uint64)
            span = jnp.where(
                ru < jnp.uint64(B), ru.astype(jnp.int64) + 2, jnp.int64(B + 1))
            kidx = jnp.where(
                c.validity,
                (d.astype(jnp.uint64) - mn.astype(jnp.uint64)).astype(jnp.int64) + 1,
                0,
            )
            bucket_direct = bucket_direct + kidx * stride
            mns.append(mn)
            spans.append(span)
            strides.append(stride)
            stride = stride * span
            direct_ok = direct_ok & (stride <= jnp.int64(B))
        bucket_direct = jnp.clip(bucket_direct, 0, B - 1).astype(jnp.int32)
    else:
        direct_ok = jnp.bool_(False)
        bucket_direct = jnp.zeros(cap, jnp.int32)

    # --- tier 2: murmur3 buckets (computed only when tier 1 declines) ----
    def _hash_buckets(_):
        h = murmur3(list(key_cols), list(key_dtypes))
        return (h.astype(jnp.uint32) & jnp.uint32(B - 1)).astype(jnp.int32)

    if direct_capable:
        bucket = lax.cond(
            direct_ok, lambda _: bucket_direct, _hash_buckets, operand=None)
    else:
        bucket = _hash_buckets(None)
    seg = jnp.where(live, bucket, B)  # out-of-range ids drop out everywhere

    # --- reductions (all sums/counts of EVERY column in ONE matmul pass;
    # min/max batched into one scatter family per (op, dtype), their
    # nullability counts riding the same matmul) -------------------------
    int_specs, cnt_specs, flt_specs, fix_specs = [], [], [], []
    resolved = _resolve_strategy(reduce_strategy)
    plan = []  # per agg: (path, payload)
    cnt_index: dict = {}
    mm_fam: dict = {}  # (op, dtype) -> [filled (n,) columns]

    def _want_count(valid_arr, key):
        if key not in cnt_index:
            cnt_index[key] = len(cnt_specs)
            cnt_specs.append(valid_arr)
        return cnt_index[key]

    live_count_i = _want_count(live, ("star",))  # also drives `occupied`
    for ai, (op, v) in enumerate(zip(agg_ops, value_cols)):
        if op == "count_star":
            plan.append(("count", live_count_i))
        elif op == "count":
            plan.append(("count", _want_count(v.validity & live, ("c", ai))))
        elif op == "sum" and not jnp.issubdtype(v.data.dtype, jnp.floating):
            ci = _want_count(v.validity & live, ("c", ai))
            int_specs.append((v.data, v.validity & live))
            plan.append(("isum", (len(int_specs) - 1, ci)))
        elif op == "sum" and (approx_float_sum
                              or reduce_strategy == "PALLAS"):
            # PALLAS forces the order-insensitive kernel path even for
            # exact float sums — a forced-strategy tradeoff the conf doc
            # names (the chooser's AUTO never picks it without the
            # variableFloatAgg opt-in)
            ci = _want_count(v.validity & live, ("c", ai))
            flt_specs.append((v.data, v.validity & live))
            plan.append(("fsum", (len(flt_specs) - 1, ci, v.data.dtype)))
        elif op == "sum" and resolved == "MATMUL":
            # exact float sum beside the int limbs: fixed-point limbs
            ci = _want_count(v.validity & live, ("c", ai))
            fix_specs.append((v.data, v.validity & live))
            plan.append(("fsum_fixed", (len(fix_specs) - 1, ci,
                                        v.data.dtype)))
        elif op == "sum":
            # exact float sum: one scatter op; nullability via matmul count
            ci = _want_count(v.validity & live, ("c", ai))
            plan.append(("fsum_exact", (v, ci)))
        elif op in ("min", "max"):
            # fill dead/invalid rows with the op's identity so they never
            # win, then batch all columns of one (op, dtype) family into a
            # single segment scatter (ops/bucket_reduce.bucket_min_max);
            # semantics mirror segment_reduce exactly, incl. Spark's
            # NaN-is-largest max and NaN-skipping min
            valid = v.validity & live
            data = v.data
            ci = _want_count(valid, ("c", ai))
            nn_ci = None
            if jnp.issubdtype(data.dtype, jnp.floating):
                if op == "max":
                    d = jnp.where(valid, data,
                                  jnp.array(-jnp.inf, data.dtype))
                else:
                    nn_ci = _want_count(valid & ~jnp.isnan(data), ("nn", ai))
                    nan_as_inf = jnp.where(jnp.isnan(data), jnp.inf, data)
                    d = jnp.where(valid, nan_as_inf,
                                  jnp.inf).astype(data.dtype)
            elif data.dtype == jnp.bool_:
                d = jnp.where(valid, data, jnp.array(op == "min", jnp.bool_))
            else:
                lo, hi = _INT_MIN_MAX.get(jnp.dtype(data.dtype), (0, 1))
                d = jnp.where(valid, data,
                              jnp.array(lo if op == "max" else hi,
                                        data.dtype))
            fam = mm_fam.setdefault((op, jnp.dtype(d.dtype)), [])
            plan.append(("minmax", (op, jnp.dtype(d.dtype), len(fam),
                                    ci, nn_ci)))
            fam.append(d)
        elif reduce_strategy == "PALLAS":
            plan.append(("pallas_pos", (op, v)))  # first/last, kernel
        else:
            plan.append(("scatter", (op, v)))  # first/last

    from .bucket_reduce import bucket_min_max

    isums, counts, fsums, (xsums, float_detour) = bucket_reduce(
        seg, B, int_specs, cnt_specs, flt_specs,
        strategy=reduce_strategy, fixed_cols=fix_specs)
    if report is not None:
        kinds = [kind for kind, _ in plan]
        report["lowering"] = tuple(kinds)
        report["float_sums_fixed"] = len(fix_specs)
        report["float_detour"] = float_detour
        # what walks every row outside a cond: the SCATTER lowering's two
        # families, a scatter float sum, a min/max family, a first/last
        report["row_scatters"] = (
            (bool(int_specs) + bool(cnt_specs or flt_specs))
            * (resolved == "SCATTER")
            + kinds.count("fsum_exact") + kinds.count("scatter")
            + len(mm_fam) * (resolved != "PALLAS"))
    mm_results = {
        k: bucket_min_max(seg, B, k[0], cols_, strategy=reduce_strategy)
        for k, cols_ in mm_fam.items()
    }
    occupied = counts[live_count_i] > 0
    ngroups = jnp.sum(occupied.astype(jnp.int32)).astype(jnp.int32)

    # --- group keys + collision status (branch on tier) -----------------
    bucket_ids = jnp.arange(B, dtype=jnp.int64)

    def _direct_branch(_):
        keys_out = []
        for (c, dt), mn, span, stride in zip(
            zip(key_cols, key_dtypes), mns, spans, strides
        ):
            kidx = (bucket_ids // stride) % span  # 0 = null slot
            val = (mn + kidx - 1).astype(c.data.dtype)
            valid = (kidx > 0) & occupied
            keys_out.append((jnp.where(valid, val, jnp.zeros((), val.dtype)), valid))
        return tuple(keys_out), jnp.bool_(True)

    def _hash_branch(_):
        if reduce_strategy == "PALLAS":
            from .pallas_groupby import pallas_bucket_position

            rep0, _found = pallas_bucket_position(seg, B, "min", live)
            rep_row = jnp.clip(rep0, 0, cap - 1)
        else:
            first_row = jax.ops.segment_min(
                jnp.where(live, idx, jnp.int32(cap)), seg, num_segments=B)
            rep_row = jnp.clip(first_row, 0, cap - 1)
        order = SortOrder(True, True)
        words: List[jax.Array] = []
        # one nullpack word per 16 keys: 2-bit null ranks must not alias
        nullpacks = [
            jnp.zeros(cap, jnp.uint32)
            for _ in range((len(key_cols) + 15) // 16)
        ]
        for i, (c, dt) in enumerate(zip(key_cols, key_dtypes)):
            null_rank, vk = fixed_radix_keys(c, dt, order)
            nullpacks[i // 16] = nullpacks[i // 16] | (null_rank << (2 * (i % 16)))
            if vk.dtype == jnp.uint64:
                words.append((vk & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32))
                words.append((vk >> 32).astype(jnp.uint32))
            else:
                words.append(vk.astype(jnp.uint32))
        words.extend(nullpacks)
        ok = jnp.bool_(True)
        for w in words:
            rep_table = jnp.where(
                occupied, jnp.take(w, rep_row, mode="clip"), jnp.uint32(0))
            ok = ok & bucket_equal_check(seg, B, w, rep_table, live)
        keys_out = []
        for c in key_cols:
            kd = jnp.take(c.data, rep_row, mode="clip")
            kv = jnp.take(c.validity, rep_row, mode="clip") & occupied
            keys_out.append((jnp.where(kv, kd, jnp.zeros((), kd.dtype)), kv))
        return tuple(keys_out), ok

    if direct_capable:
        key_tables, collision_free = lax.cond(
            direct_ok, _direct_branch, _hash_branch, operand=None)
    else:
        key_tables, collision_free = _hash_branch(None)

    # --- bucket-compaction: present buckets to the front ----------------
    # All slot work happens at size B (tiny); outputs pad up to the input
    # capacity with plain copies — gathers at cap-size would cost ~100x.
    csum = jnp.cumsum(occupied.astype(jnp.int32))
    if reduce_strategy == "PALLAS":
        # identical slot mapping via one (tiny) B-sized sort, so the
        # PALLAS program carries zero scatter instructions end to end
        _, bucket_of_slot = lax.sort(
            [(~occupied).astype(jnp.uint32),
             jnp.arange(B, dtype=jnp.int32)],
            num_keys=1, is_stable=True)
    else:
        dest = jnp.where(occupied, csum - 1, B)
        bucket_of_slot = (
            jnp.zeros(B, jnp.int32).at[dest].set(
                jnp.arange(B, dtype=jnp.int32), mode="drop")
        )
    slot_live = jnp.arange(B, dtype=jnp.int32) < ngroups
    pad = cap - B

    def to_slots(arr, valid):
        d = jnp.take(arr, bucket_of_slot, mode="clip")
        vv = jnp.take(valid, bucket_of_slot, mode="clip") & slot_live
        d = jnp.where(vv, d, jnp.zeros((), d.dtype))
        if pad > 0:
            d = jnp.concatenate([d, jnp.zeros(pad, d.dtype)])
            vv = jnp.concatenate([vv, jnp.zeros(pad, jnp.bool_)])
        return ColV(d, vv)

    out_keys: List[ColV] = [to_slots(kd, kv) for kd, kv in key_tables]

    out_aggs: List[ColV] = []
    for (kind, payload), (op, v) in zip(plan, zip(agg_ops, value_cols)):
        if kind == "count":
            out_aggs.append(to_slots(counts[payload], jnp.ones(B, jnp.bool_)))
        elif kind == "isum":
            si, ci = payload
            data = isums[si]
            if v.data.dtype != jnp.int64:
                data = data.astype(v.data.dtype)
            out_aggs.append(to_slots(data, counts[ci] > 0))
        elif kind == "fsum":
            si, ci, dt = payload
            out_aggs.append(to_slots(fsums[si].astype(dt), counts[ci] > 0))
        elif kind == "fsum_fixed":
            si, ci, dt = payload
            out_aggs.append(to_slots(xsums[si].astype(dt), counts[ci] > 0))
        elif kind == "fsum_exact":
            sv, ci = payload
            z = jnp.zeros((), sv.data.dtype)
            sm = jax.ops.segment_sum(
                jnp.where(sv.validity & live, sv.data, z), seg, num_segments=B)
            out_aggs.append(to_slots(sm, counts[ci] > 0))
        elif kind == "minmax":
            mop, mdt, fi, ci, nn_ci = payload
            r = mm_results[(mop, mdt)][fi]
            has = counts[ci] > 0
            if nn_ci is not None:
                # all-NaN group: min skips NaN unless nothing else exists
                r = jnp.where((counts[nn_ci] == 0) & has, jnp.nan, r)
            r = jnp.where(has, r, jnp.zeros((), r.dtype))
            out_aggs.append(to_slots(r, has))
        elif kind == "pallas_pos":
            sop, sv = payload
            from .pallas_groupby import pallas_bucket_position

            consider = (sv.validity & live
                        if sop.endswith("ignorenulls") else live)
            wop = "min" if sop.startswith("first") else "max"
            row, found = pallas_bucket_position(seg, B, wop, consider)
            safe = jnp.clip(row, 0, cap - 1)
            vals = jnp.take(sv.data, safe, mode="clip")
            vv = jnp.take(sv.validity, safe, mode="clip") & found
            out_aggs.append(to_slots(
                jnp.where(vv, vals, jnp.zeros((), vals.dtype)), vv))
        else:
            sop, sv = payload
            r = segment_reduce(sop, sv, seg, B, live)
            out_aggs.append(to_slots(r.data, r.validity))
    return out_keys, out_aggs, ngroups, collision_free


def groupby_agg(
    key_cols: Sequence[Val],
    key_dtypes: Sequence[T.DataType],
    value_cols: Sequence[Optional[ColV]],
    agg_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    str_max_lens: Sequence[int] = (),
    approx_float_sum: bool = False,
    num_buckets: int = 8192,
    str_val_max_lens: Sequence[int] = (),
    strategy: Optional[str] = None,
    report: Optional[dict] = None,
) -> Tuple[List[Val], List[Val], jax.Array]:
    """Adaptive groupby: MXU hash-bucket fast path with a traced sort
    fallback.

    Reference analog: cudf's hash groupby with sort-groupby fallback for
    unsupported cases (aggregate.scala:806). Here the choice is a runtime
    ``lax.cond`` on the collision-free check, so low-cardinality aggregates
    (the TPC-DS common case) never pay the bitonic sort.

    ``strategy`` is the plan-level aggregation lowering chosen by the
    exec's strategy chooser (conf spark.rapids.tpu.sql.agg.strategy):
    MATMUL/SCATTER force the hash-bucket tiers' reduction lowering
    (ops/bucket_reduce.py), RADIX skips the hash tiers and reduces over
    the radix-binned order (ops/radix_bin.py). None keeps the backend
    default.
    Plain string keys always take the sort path; DICT-ENCODED string keys
    whose dictionary is unique group directly on their int32 codes (no
    byte-wise hashing or chunk-key sort at all — the cudf-dictionary32
    trick) and rewrap the output codes, so the group keys stay encoded.
    Non-unique dictionaries (post-transform, where distinct codes may
    hold equal strings) materialize and sort like plain strings.

    ``report``: filled by the FIRST hash tier (the one outside every
    ``lax.cond``, which a low-cardinality aggregate takes) with how its
    plan lowers, see :func:`hash_groupby`; left empty where the keys
    bypass the hash tiers.
    """
    key_cols = list(key_cols)
    key_dtypes = list(key_dtypes)
    value_cols = list(value_cols)
    # string min/max reduce over lexicographic RANK columns; winners map
    # back to strings after the (tiered) reduction picked its path
    recover = string_minmax_ranks(
        value_cols, agg_ops, num_rows, str_val_max_lens)
    code_keys = {}  # key index -> DictV template to rewrap from codes
    eff_sml: List[int] = []
    si = 0
    for i, c in enumerate(key_cols):
        if isinstance(c, DictV):
            if si < len(str_max_lens):
                si += 1  # consume this string key's slot either way
            if c.unique:
                key_cols[i] = ColV(c.codes.astype(jnp.int32), c.validity)
                key_dtypes[i] = T.INT
                code_keys[i] = c
            else:
                from ..columnar.column import choose_capacity

                key_cols[i] = materialize_dict(c)
                eff_sml.append(max(4, choose_capacity(max(1, c.max_len), 4)))
        elif isinstance(c, StrV):
            eff_sml.append(str_max_lens[si] if si < len(str_max_lens) else 64)
            si += 1
    str_max_lens = tuple(eff_sml)

    def _rewrap(keys, aggs, n):
        if code_keys:
            from ..columnar.column import choose_capacity

            keys = list(keys)
            for i, t in code_keys.items():
                k = keys[i]
                keys[i] = DictV(
                    k.data, t.dictionary, k.validity,
                    choose_capacity(
                        max(1, int(t.dictionary.chars.shape[0])), 128),
                    t.max_len, True)
        if recover:
            aggs = list(aggs)
            for ai, rec in recover.items():
                aggs[ai] = rec(aggs[ai])
        return keys, aggs, n

    # PALLAS hash tiers cover fixed-width keys; its string/keyless
    # fallback rides the RADIX tiled path so the plan stays scatter-free
    radix = strategy == "RADIX" or strategy == "PALLAS"
    if not key_cols:
        return _rewrap(*sort_groupby(
            key_cols, key_dtypes, value_cols, agg_ops, num_rows,
            str_max_lens, radix_reduce=radix))
    if strategy == "RADIX" or any(
            isinstance(c, StrV) for c in key_cols):
        return _rewrap(*sort_groupby(
            key_cols, key_dtypes, value_cols, agg_ops, num_rows,
            str_max_lens, radix_reduce=radix))
    cap = key_cols[0].validity.shape[0]

    def pow2_floor(x: int) -> int:
        return 1 << (x.bit_length() - 1) if x & (x - 1) else x

    B2 = pow2_floor(min(cap, num_buckets))
    # the one-hot matmul reduction is K-bound on the MXU at ceil(B/128)
    # output tiles x cap contraction cycles: B=128 costs 1/8th of B=1024.
    # Run narrow tiers first (TPC-DS group-bys are usually <100 groups)
    # and escalate to wider tiers — then the bitonic sort — only when the
    # keys don't fit. lax.cond executes just the taken branch, so the
    # common case never pays the wide tiers.
    B1 = min(1024, B2)
    B0 = min(128, B1)

    def pack(keys, aggs, n):
        return (
            tuple((c.data, c.validity) for c in keys),
            tuple((c.data, c.validity) for c in aggs),
            n,
        )

    def use_sort(_):
        return pack(*sort_groupby(
            key_cols, key_dtypes, value_cols, agg_ops, num_rows,
            str_max_lens, radix_reduce=radix))

    def tier(B, below, report=None):
        def run(_):
            hk, ha, hn, ok = hash_groupby(
                list(key_cols), key_dtypes, value_cols, agg_ops, num_rows,
                B, approx_float_sum=approx_float_sum,
                reduce_strategy=strategy, report=report)

            def use_hash(_):
                return pack(hk, ha, hn)

            return lax.cond(ok, use_hash, below, operand=None)

        return run

    chain = use_sort
    if B2 > B1:
        chain = tier(B2, chain)
    if B1 > B0:
        chain = tier(B1, chain)
    keys_t, aggs_t, n = tier(B0, chain, report)(None)
    out_keys = [ColV(d, v) for d, v in keys_t]
    out_aggs = [ColV(d, v) for d, v in aggs_t]
    return _rewrap(out_keys, out_aggs, n)
