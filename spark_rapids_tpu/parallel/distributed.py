"""Distributed SQL operators: shard_map-traceable groupby / sort / join.

These compose the single-chip kernels (ops/groupby.py, ops/sort.py,
ops/join.py) with the collective exchange (parallel/collective.py) into one
XLA program per mesh — the TPU-native expression of the reference's
"PARTIAL aggregate -> shuffle -> FINAL aggregate" / "range partition ->
local sort" / "hash partition both sides -> local join" plans
(GpuShuffleExchangeExec.scala:70, GpuSortExec.scala:51,
GpuShuffleHashJoinExec.scala:23). Where the reference schedules those as
separate Spark stages with an RDMA shuffle between them, here the whole
plan is one jitted SPMD computation: XLA schedules the all_to_all against
compute and nothing touches the host.

All functions run INSIDE shard_map over ``axis_name``; shapes are
per-shard. Fixed-width columns only (matching the collective exchange).
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax import lax

from .. import types as T
from ..expr.eval import ColV
from ..ops import groupby as groupby_ops
from ..ops import hashing
from ..ops import join as join_ops
from ..ops.filter_gather import gather, live_of
from ..ops.sort import SortOrder, sort_with_radix_keys
from ..shuffle.partition import count_bounds_le
from .collective import all_to_all_exchange


def dist_groupby(
    key_cols: Sequence[ColV],
    key_dtypes: Sequence[T.DataType],
    value_cols: Sequence[Optional[ColV]],
    update_ops: Sequence[str],
    merge_ops: Sequence[str],
    num_rows: Union[int, jax.Array],
    axis_name: str,
    n_shards: int,
    str_max_lens: Sequence[int] = (),
    group_cap: int = 0,
    partials: bool = False,
    reports: Optional[dict] = None,
) -> Tuple[List[ColV], List[ColV], jax.Array, jax.Array]:
    """PARTIAL local aggregate -> key-hash all_to_all -> FINAL merge.

    ``update_ops`` aggregate raw inputs into per-shard partials;
    ``merge_ops`` combine partial buffers after the exchange (Spark's
    update/merge split, AggregateFunctions.scala:531). Group keys end up
    shard-disjoint, so results are the concatenation of every shard's
    output (each shard returns its own groups + count).

    ``group_cap`` sizes the exchange to the GROUP cardinality instead of
    the input row capacity: the PARTIAL output (groups compacted to the
    front) is sliced to ``group_cap`` rows per shard before crossing the
    wire, shrinking the all_to_all surface from O(n_shards x cap) to
    O(n_shards x group_cap) — the difference between a mesh aggregate
    that scales and one that drowns in its own receive buffers. A shard
    whose partial produced more than ``group_cap`` groups reports
    ``ok`` = False (results are then truncated garbage; callers retry
    with a doubled cap, the same contract as the join's output-capacity
    retry). 0 disables slicing, ``ok`` is then always True. Fixed-width
    columns only (string group keys keep the full-capacity exchange).

    ``partials``: the inputs are partial rows already (keys and buffers
    of an update the caller ran piece by piece, exec/mesh's chunked
    update); the PARTIAL half is skipped and they cross as they are.

    ``reports``: filled with how each half's aggregate lowers,
    ``reports["update"]`` (unless ``partials``) and ``reports["merge"]``,
    each as ``ops/groupby.hash_groupby`` describes its ``report``.

    Returns (keys, aggs, count, ok) — ``ok`` is globally reduced.
    """
    # PARTIAL: local groupby shrinks rows before they cross the wire.
    # The three phases carry the scope words of exec/base (the one-chip
    # programs' ``agg_update`` / ``agg_merge``, and ``mesh_exchange``):
    # names on the profiler's clock, nothing the program computes
    if partials:
        pkeys, paggs, pn = list(key_cols), list(value_cols), num_rows
    else:
        with jax.named_scope("agg_update"):
            pkeys, paggs, pn = groupby_ops.groupby_agg(
                key_cols, key_dtypes, value_cols, list(update_ops),
                num_rows, str_max_lens, report=_half(reports, "update"))

    all_cols = list(pkeys) + list(paggs)
    cap = all_cols[0].validity.shape[0] if all_cols else 0
    sliceable = (
        0 < group_cap < cap
        and all(type(c) is ColV for c in all_cols))
    ok_local = jnp.bool_(True)
    if sliceable:
        ok_local = pn <= group_cap
        all_cols = [
            ColV(c.data[:group_cap], c.validity[:group_cap])
            for c in all_cols
        ]
        pkeys = all_cols[: len(pkeys)]
        pn = jnp.minimum(pn, group_cap)

    # exchange by key hash (same murmur3+pmod as the single-host exchange);
    # string keys cross via the byte plane of the collective
    with jax.named_scope("mesh_exchange"):
        h = hashing.murmur3(list(pkeys), list(key_dtypes),
                            str_max_lens=str_max_lens)
        pids = hashing.partition_ids(h, n_shards)
        recvd, rn, x_ok = all_to_all_exchange(
            all_cols, pids, pn, axis_name, n_shards)
        ok = x_ok & (
            lax.psum(ok_local.astype(jnp.int32), axis_name) == n_shards)
    rkeys = recvd[: len(pkeys)]
    raggs = recvd[len(pkeys):]

    # FINAL: merge partial buffers locally (keys now shard-disjoint)
    with jax.named_scope("agg_merge"):
        fkeys, faggs, fn_ = groupby_ops.groupby_agg(
            rkeys, key_dtypes, list(raggs), list(merge_ops), rn,
            str_max_lens, report=_half(reports, "merge"))
    return fkeys, faggs, fn_, ok


def _half(reports: Optional[dict], name: str) -> Optional[dict]:
    return None if reports is None else reports.setdefault(name, {})


def _sample_bounds(
    radix_words: Sequence[jax.Array],
    live: jax.Array,
    axis_name: str,
    n_shards: int,
    samples_per_shard: int = 64,
) -> List[jax.Array]:
    """Device-side bound sampling: each shard contributes an evenly-spaced
    sample of its SORTED keys, samples all_gather, and the (n_shards-1)
    quantiles become the range bounds (reference: GpuRangePartitioner
    sketch/determineBounds — but with no driver round-trip)."""
    cap = radix_words[0].shape[0]
    n = jnp.sum(live.astype(jnp.int32))
    # rows are already sorted by key here; sample evenly across live rows
    pos = (
        jnp.arange(samples_per_shard, dtype=jnp.int32)
        * jnp.maximum(n, 1) // samples_per_shard
    )
    pos = jnp.clip(pos, 0, cap - 1)
    has = jnp.arange(samples_per_shard, dtype=jnp.int32) < jnp.minimum(
        n, samples_per_shard)
    samples = [jnp.take(w, pos, mode="clip") for w in radix_words]

    g_samples = [lax.all_gather(s, axis_name, tiled=True) for s in samples]
    g_has = lax.all_gather(has, axis_name, tiled=True)
    total = samples_per_shard * n_shards
    # sort gathered samples (dead samples last via the has-rank key)
    ops_in = [(~g_has).astype(jnp.uint32)] + list(g_samples)
    sorted_ops = lax.sort(ops_in, num_keys=len(ops_in), is_stable=True)
    s_words = sorted_ops[1:]
    g_n = jnp.sum(g_has.astype(jnp.int32))
    bpos = (
        jnp.arange(1, n_shards, dtype=jnp.int32) * jnp.maximum(g_n, 1)
        // n_shards
    )
    bpos = jnp.clip(bpos, 0, total - 1)
    return [jnp.take(w, bpos, mode="clip") for w in s_words]


def dist_sort(
    cols: Sequence[ColV],
    key_indices: Sequence[int],
    key_dtypes: Sequence[T.DataType],
    orders: Sequence[SortOrder],
    num_rows: Union[int, jax.Array],
    axis_name: str,
    n_shards: int,
    str_max_lens: Sequence[int] = (),
    bucket_cap: int = 0,
) -> Tuple[List[ColV], jax.Array, jax.Array]:
    """Sample-range exchange + local sort: shard i's rows all precede
    shard i+1's in the requested order (the global sort contract).

    ``bucket_cap`` is the per-target exchange granule (the receive surface
    is n_shards x bucket_cap per shard): the sampled range bounds spread
    rows roughly evenly, so a granule of ~2x the fair share keeps the
    exchange O(cap) instead of the default O(n_shards x cap). A skewed
    key distribution overflows a block and reports ``ok`` = False
    (callers retry with a bigger granule); 0 keeps the always-fits
    default. Returns (cols, count, ok) — ``ok`` globally reduced."""
    cap = cols[0].validity.shape[0]
    live = live_of(num_rows, cap)
    key_cols = [cols[i] for i in key_indices]

    # local sort FIRST: evenly-spaced positions then sample true quantiles,
    # and the post-exchange sort of mostly-sorted runs is cheap
    perm, sorted_radix = sort_with_radix_keys(
        key_cols, key_dtypes, orders, live, str_max_lens)
    live_sorted = jnp.take(live, perm, mode="clip")
    sorted_cols = gather(cols, perm, live_sorted)

    bounds = _sample_bounds(sorted_radix, live_sorted, axis_name, n_shards)

    # pid = number of bounds <= row (lexicographic over radix words)
    pid = count_bounds_le(sorted_radix, bounds, n_shards - 1)

    recvd, rn, ok = all_to_all_exchange(
        sorted_cols, pid, live_sorted, axis_name, n_shards,
        bucket_cap=bucket_cap)

    rkeys = [recvd[i] for i in key_indices]
    perm2, _ = sort_with_radix_keys(rkeys, key_dtypes, orders, rn,
                                    str_max_lens)
    rcap = recvd[0].validity.shape[0]
    live2 = jnp.arange(rcap, dtype=jnp.int32) < rn
    live2_sorted = jnp.take(live2, perm2, mode="clip")
    return gather(recvd, perm2, live2_sorted), rn, ok


def dist_hash_join(
    left_cols: Sequence[ColV],
    left_keys: Sequence[int],
    right_cols: Sequence[ColV],
    right_keys: Sequence[int],
    key_dtypes: Sequence[T.DataType],
    left_rows: Union[int, jax.Array],
    right_rows: Union[int, jax.Array],
    axis_name: str,
    n_shards: int,
    out_cap: int,
    key_str_max_lens: Sequence[int] = (),
    out_char_caps: Sequence[int] = (),
    exchange_bucket_caps: Tuple[int, int] = (0, 0),
) -> Tuple[List[ColV], jax.Array, jax.Array]:
    """Inner equi-join: hash-exchange both sides, join locally.

    ``out_cap`` is the static per-shard output capacity (callers size it
    from expected selectivity; overflow reports ok=False). String key
    columns compare through the same chunk-key encoding on both sides, so
    ``key_str_max_lens`` must be the SHARED byte bound per string key.
    ``out_char_caps`` sizes the output byte pools per string column of the
    combined (left..right) output; byte overflow also reports ok=False so
    callers can retry with bigger pools. ``exchange_bucket_caps`` are the
    per-side exchange granules (left, right) — hash partitioning spreads
    keys roughly evenly, so ~2x the fair share keeps each side's receive
    surface O(cap) instead of O(n_shards x cap); a skewed key overflows
    the block and ok=False triggers the caller's retry (0 = always-fits
    full granule). Returns (cols = left..right, match count, ok).
    """
    from ..expr.eval import StrV

    def exchange_side(cols, key_ix, rows, bucket_cap):
        kc = [cols[i] for i in key_ix]
        h = hashing.murmur3(
            kc, list(key_dtypes), str_max_lens=list(key_str_max_lens))
        pids = hashing.partition_ids(h, n_shards)
        return all_to_all_exchange(cols, pids, rows, axis_name, n_shards,
                                   bucket_cap=bucket_cap)

    l_cols, ln, ok1 = exchange_side(
        left_cols, left_keys, left_rows, exchange_bucket_caps[0])
    r_cols, rn, ok2 = exchange_side(
        right_cols, right_keys, right_rows, exchange_bucket_caps[1])

    def cap_of(cols):
        c0 = cols[0]
        return (c0.offsets.shape[0] - 1 if isinstance(c0, StrV)
                else c0.validity.shape[0])

    # build = right side: sort by key words, probe with binary search
    rkc = [r_cols[i] for i in right_keys]
    rwords, r_null = join_ops.radix_key_words(
        rkc, key_dtypes, key_str_max_lens)
    rcap = cap_of(r_cols)
    r_live = jnp.arange(rcap, dtype=jnp.int32) < rn
    ok_rows = r_live & ~r_null
    order_rank = jnp.where(ok_rows, 0, 1).astype(jnp.uint32)
    sort_ops = lax.sort(
        [order_rank] + [w for w in rwords]
        + [jnp.arange(rcap, dtype=jnp.int32)],
        num_keys=1 + len(rwords), is_stable=True)
    perm = sort_ops[-1]
    sorted_rwords = [jnp.take(w, perm, mode="clip") for w in rwords]
    sorted_build = gather(r_cols, perm, jnp.take(r_live, perm, mode="clip"))
    build_count = jnp.sum(ok_rows.astype(jnp.int32))

    lkc = [l_cols[i] for i in left_keys]
    lwords, l_null = join_ops.radix_key_words(
        lkc, key_dtypes, key_str_max_lens)
    lcap = cap_of(l_cols)
    l_live = (jnp.arange(lcap, dtype=jnp.int32) < ln) & ~l_null
    lo, hi = join_ops.probe_ranges(sorted_rwords, build_count, lwords, l_live)
    counts = jnp.where(l_live, hi - lo, 0)
    total = jnp.sum(counts.astype(jnp.int64))
    ok = ok1 & ok2 & (total <= out_cap)

    p, build_row, slot_live = join_ops.expansion_plan(counts, lo, out_cap)
    nstr_left = sum(1 for c in l_cols if isinstance(c, StrV))
    lcc = list(out_char_caps[:nstr_left])
    rcc = list(out_char_caps[nstr_left:])
    left_out = gather(l_cols, p, slot_live, char_caps=lcc or None)
    right_out = gather(
        sorted_build, build_row, slot_live, char_caps=rcc or None)
    out = list(left_out) + list(right_out)
    # byte-pool overflow check: gather_string truncates chars but keeps the
    # true cumsum in offsets, so the last offset reveals overflow
    for o in out:
        if isinstance(o, StrV):
            ok = ok & (o.offsets[-1] <= o.chars.shape[0])
    return out, total.astype(jnp.int32), ok
