"""Radix-binned tiled groupby reduction — the RADIX aggregation lowering.

The cost plane (BENCH_r09 + hlo.py) proved the aggregation hot path
touches ~25x its logical working set: the one-hot expansion prices the
reduce in materialized (rows x buckets) bytes, the scatter lowering in
near-serial per-row updates (and, on the CPU dialect, a while-loop whose
full-width accumulator XLA charges per instruction). This module is the
rewrite: rows are ordered once by their radix key digits (the same
order-preserving u32/u64 words the sort machinery builds —
ops/sort.sort_with_radix_keys IS the multi-pass radix binning), then ONE
``lax.fori_loop`` walks the binned order in HBM-resident tiles sized
from the static layout. EVERYTHING per-row beyond the sort happens
inside that loop on tile-sized temporaries: the raw value columns are
gathered one tile at a time and the reduction streams (limb-free sums,
the float stream split, winner words) are BUILT IN THE TILE — no
cap-sized derived array ever materializes, which is precisely where the
first cut of this lowering still paid ~3x the layout bound. Boundary
flags likewise derive per tile from the sliced sorted key words (plus
one carried word per key), and the per-segment results are written
exactly once into the output buffer through a sliding window whose
boundary segment rides the loop carry. No one-hot is ever built and no
scatter instruction is ever emitted.

Reduction families, all scatter-free:

  * sums/counts (AddSpec): per-tile prefix-sum differences at the
    segment boundaries (integer sums wrap mod 2^64 exactly like native
    adds — BIT-identical to the scatter/matmul lowerings);
  * float sums: split per row (IN the tile) into a NORMAL stream (f64
    accumulated by a SEGMENTED scan that resets at every segment
    boundary, so one group's magnitude can never absorb a neighbouring
    group's sum), a BIG stream (|x| > 2^500 scaled down by
    2^-600 — exact power-of-two scaling — so giant magnitudes cannot
    annihilate the prefix's low bits, rescaled after the reduce), and
    per-segment +inf/-inf/NaN presence FLAGS (an OR stream whose
    21-bit-lane tile sums saturate to 3 presence bits in a ONE-BYTE
    output buffer), recombined with IEEE semantics (any NaN or mixed
    infinities -> NaN, else the surviving infinity, else
    normal + big * 2^600). Order-insensitive like the matmul hi/lo
    split, but in native f64 — strictly tighter;
  * min/max (MinMaxSpec): WINNER-ROW streams — the tile-built order
    word is the sort machinery's total-order radix encoding (so Spark's
    NaN-largest / -0.0 == 0.0 rules fall out and all-NaN groups
    naturally win a NaN row), the per-tile winner comes from one
    tile-local secondary sort, and only the winning ROW index is
    materialized — the value is gathered once at the end;
  * first/last and the group-representative row (PosSpec): SORT-FREE.
    The radix sort is stable with dead rows last, so within a segment
    rows appear in ascending ORIGINAL order — first/last considered is
    a per-segment min/max POSITION, computed as one cumulative-max over
    a (segment, position) packing, no order word and no in-tile sort.

The flush tile: the loop runs ceil(cap/tile)+1 trips; the final trip
carries no live rows and exists solely to write the last open segment's
partial through the normal window path, so the body has no conditionals.

Zero new dependencies; everything lowers to sort/slice/cumsum/gather.
"""
from __future__ import annotations

from typing import Callable, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax import lax

#: test hook: force the tile row count (0 = derive from the layout).
#: Lets tests drive multi-tile paths (incl. the flush tile and non-
#: divisible caps) on small inputs. Must stay <= 2^20 so the saturating
#: flag fields below cannot overflow their 21-bit lanes (and the
#: PosSpec position packing its u64).
FORCE_TILE_ROWS = 0

#: |x| above this routes a float row through the scaled BIG stream
F64_BIG = 2.0 ** 500
#: exact power-of-two scaling for the BIG stream (scaling is lossless;
#: the rescale may overflow to inf, which is the mathematically correct
#: sum in that case)
BIG_SCALE_DOWN = 2.0 ** -600
BIG_SCALE_UP = 2.0 ** 600

#: OR-stream field layout: three 21-bit per-tile count lanes (+inf,
#: -inf, NaN). A tile holds < 2^20 rows, so a lane can never carry into
#: its neighbor before the per-tile saturation back to presence bits.
#: Plain python ints (not jnp scalars): the module is lazily imported,
#: possibly inside a jit trace, where a module-scope jnp constant would
#: be born a tracer and leak into every later trace.
_FLAG_LANE = 21
_FLAG_MASK = (1 << _FLAG_LANE) - 1

_U64_MAX = 0xFFFFFFFFFFFFFFFF


def default_tile_rows(cap: int, n_streams: int) -> int:
    """Tile rows sized from the static layout: the loop body's working
    set (streams + the winner sorts' key copies) should sit in fast
    memory (~1 MiB target — VMEM-scale on TPU, L2-scale on the CPU
    fallback), clamped to [2^12, 2^16] and never above the capacity
    bucket."""
    if FORCE_TILE_ROWS:
        return min(FORCE_TILE_ROWS, 1 << 20)
    per_row = max(16, 8 * max(1, n_streams))
    t = max(2, (1 << 20) // per_row)
    t = 1 << max(12, min(16, t.bit_length() - 1))
    while t > cap and t > 8:
        t >>= 1
    return max(8, t)


class TileCtx:
    """Per-tile gather context handed to every stream builder: ``take``
    gathers an ORIGINAL-row-order array at this tile's sorted rows.
    Builders that share a raw column produce syntactically identical
    gathers, which XLA CSE collapses to one — the reason builders close
    over raw columns instead of pre-materializing cap-sized streams."""

    __slots__ = ("p_t",)

    def __init__(self, p_t: jax.Array):
        self.p_t = p_t

    def take(self, arr: jax.Array) -> jax.Array:
        return jnp.take(arr, self.p_t, mode="clip")


class AddSpec(NamedTuple):
    """One additive stream: ``build(ctx)`` returns the (tile,) values
    (already zeroed at rows that must not contribute), ``dtype`` the
    accumulation family (uint64 / uint32 / float64). ``is_or`` marks a
    21-bit-lane flag stream (uint64 build dtype) that combines by
    per-tile saturation + bitwise OR and outputs 3 presence bits."""

    build: Callable[[TileCtx], jax.Array]
    dtype: object
    is_or: bool = False


class MinMaxSpec(NamedTuple):
    """One winner-row reduction ordered by a total-order word:
    ``word(ctx)`` is the (tile,) uint64 key (identity — u64 max for
    min, 0 for max — at non-considered rows), ``cons(ctx)`` the
    considered mask — carried explicitly because a considered value's
    word can legitimately EQUAL the identity (int64.max under min), so
    identity-matching alone cannot distinguish "no considered row" from
    "the extreme value won"."""

    word: Callable[[TileCtx], jax.Array]
    cons: Callable[[TileCtx], jax.Array]
    op: str


class PosSpec(NamedTuple):
    """First ('min') / last ('max') considered row per segment. The
    stable sort makes sorted position order == original row order
    within a segment, so the winner is a positional extremum — no order
    word, no in-tile sort."""

    cons: Callable[[TileCtx], jax.Array]
    op: str


class SegmentedOutputs(NamedTuple):
    u64: List[jax.Array]        # per non-or uint64 AddSpec, (cap,) u64
    u32: List[jax.Array]        # per uint32 AddSpec, (cap,) uint32
    f64: List[jax.Array]        # per float64 AddSpec, (cap,) float64
    flags: List[jax.Array]      # per OR AddSpec, (cap,) uint8 presence
    pos_rows: List[jax.Array]   # per PosSpec, (cap,) i32 (-1 = empty)
    winner_rows: List[jax.Array]  # per MinMaxSpec, (cap,) i32 (-1 = empty)
    nseg: jax.Array             # int32 device scalar


def _tile_diffs(stacked: jax.Array, bounds: jax.Array) -> jax.Array:
    """Per-local-segment sums of a (tile, K) stack over NONDECREASING
    local segment ids, as prefix differences at ``bounds`` (B_local+1,).
    EXACT for the modular integer families (differences of wrapped
    prefixes equal the wrapped segment sum); floats use
    :func:`_tile_segment_sums` instead — a cross-segment float prefix
    lets one segment's magnitude absorb its neighbours' sums."""
    c = jnp.cumsum(stacked, axis=0)
    padded = jnp.concatenate(
        [jnp.zeros((1, stacked.shape[1]), stacked.dtype), c])
    lo, hi = bounds[:-1], bounds[1:]
    return (jnp.take(padded, hi, axis=0, mode="clip")
            - jnp.take(padded, lo, axis=0, mode="clip"))


def _tile_segment_sums(stacked: jax.Array, starts: jax.Array,
                       bounds: jax.Array) -> jax.Array:
    """Per-local-segment FLOAT sums of a (tile, K) stack: a segmented
    associative scan whose running sum RESETS at every segment start
    (``starts``, the per-row boundary flags), read at each segment's
    last row. Accumulation therefore never crosses a segment boundary —
    group A's 1e30 cannot cancel group B's 6.0 the way a tile-wide
    prefix difference would (the rounding class is a per-group tree
    sum, the variableFloatAgg contract)."""
    flags = jnp.broadcast_to(starts[:, None], stacked.shape)

    def comb(a, b):
        af, av = a
        bf, bv = b
        return af | bf, bv + jnp.where(bf, jnp.zeros((), stacked.dtype),
                                       av)

    _, pref = lax.associative_scan(comb, (flags, stacked), axis=0)
    lo, hi = bounds[:-1], bounds[1:]
    out = jnp.take(pref, jnp.maximum(hi - 1, 0), axis=0, mode="clip")
    return jnp.where((hi > lo)[:, None], out,
                     jnp.zeros((), stacked.dtype))


def _saturate_flags(x: jax.Array) -> jax.Array:
    """Collapse the three 21-bit per-tile count lanes of an OR stream
    back to presence bits 0/1/2 (a uint8)."""
    p = (x & _FLAG_MASK) > 0
    m = ((x >> _FLAG_LANE) & _FLAG_MASK) > 0
    q = (x >> (2 * _FLAG_LANE)) > 0
    return (p.astype(jnp.uint8) | (m.astype(jnp.uint8) << 1)
            | (q.astype(jnp.uint8) << 2))


def tiled_segment_groupby(
    perm: jax.Array,
    sorted_words: Sequence[jax.Array],
    live_in: jax.Array,
    adds: Sequence[AddSpec] = (),
    pos: Sequence[PosSpec] = (),
    winners: Sequence[MinMaxSpec] = (),
    tile_rows: int = 0,
) -> SegmentedOutputs:
    """Reduce every stream per segment of the radix-sorted order, one
    HBM-resident tile at a time.

    ``perm``/``sorted_words``: the radix sort's permutation and
    co-sorted key words (dead rows sort LAST — the pad_rank leading key
    contract of ops/sort.sort_with_radix_keys). ``live_in`` is the
    liveness mask in ORIGINAL row order. Stream builders receive a
    :class:`TileCtx` and return tile-local values; additive builders
    must already hold their identity (0) at rows that must not
    contribute — dead rows are dropped structurally.

    Outputs are segment-compacted to the front at the input capacity;
    segment order is the sorted key order (ascending radix words).
    """
    cap = perm.shape[0]
    u64_specs = [s for s in adds if s.dtype == jnp.uint64 and not s.is_or]
    u32_specs = [s for s in adds if s.dtype == jnp.uint32]
    f64_specs = [s for s in adds if s.dtype == jnp.float64]
    or_specs = [s for s in adds if s.is_or]
    n_streams = (len(adds) + len(pos) + 2 * len(winners))
    tile = min(tile_rows or default_tile_rows(cap, n_streams), max(8, cap))
    BL = tile + 1
    trips = -(-cap // tile) + 1  # +1 flush trip writes the final open seg
    w_is_min = [w.op == "min" for w in winners]
    p_is_min = [p.op == "min" for p in pos]

    iota_bl = jnp.arange(BL + 1, dtype=jnp.int32)
    row_ids = jnp.arange(tile, dtype=jnp.int32)
    # PosSpec packing: seg * PACK + payload, payload in [0, BL] — u64 so
    # tile <= 2^20 can never overflow (BL^2 < 2^42)
    PACK = jnp.uint64(BL + 1)

    def body(t, carry):
        (S, prev_ok, prev_w, cu64, cu32, cf64, cflag, cpr, cww, cwr,
         b_u64, b_u32, b_f64, b_flag, b_pos, b_wrow) = carry
        start = t * tile
        pos_ok = (start + row_ids) < cap
        p_t = lax.dynamic_slice(perm, (start,), (tile,))
        ctx = TileCtx(p_t)
        lv_t = jnp.where(pos_ok, ctx.take(live_in), False)
        # boundary flags IN the tile: a live row starts a segment when
        # any sorted key word differs from the previous row's (the
        # previous tile's last word rides the carry; prev_ok is False
        # only on trip 0, where the first live row always starts one)
        w_ts = [jnp.where(
            pos_ok, lax.dynamic_slice(w, (start,), (tile,)),
            jnp.zeros((), w.dtype)) for w in sorted_words]
        diff = jnp.zeros(tile, jnp.bool_)
        for i, w_t in enumerate(w_ts):
            prev_col = jnp.concatenate(
                [prev_w[i][None].astype(w_t.dtype), w_t[:-1]])
            diff = diff | (w_t != prev_col)
        at0 = row_ids == 0
        diff = jnp.where(at0 & ~prev_ok, True, diff)
        f_t = lv_t & diff
        csum = jnp.cumsum(f_t.astype(jnp.int32))
        s_open = (S > 0).astype(jnp.int32)
        seg_local = jnp.where(lv_t, csum - 1 + s_open, BL)
        n_new = csum[-1]
        last_local = jnp.max(jnp.where(lv_t, seg_local, 0))
        w_base = jnp.maximum(S - 1, 0)
        bounds = jnp.searchsorted(seg_local, iota_bl, side="left")
        lo, hi = bounds[:-1], bounds[1:]
        present = hi > lo
        # the last local segment stays open (it may continue into the
        # next tile) and rides the carry instead of being written —
        # except on the flush trip, which exists precisely to write it
        is_flush = t == trips - 1
        keep1 = (jnp.arange(BL, dtype=jnp.int32) != last_local) | is_flush
        keep = keep1[:, None]

        def family(specs, dtype, cprev, buf, saturate):
            if not specs:
                return cprev, buf
            cols = [jnp.where(lv_t, s.build(ctx), jnp.zeros((), dtype))
                    for s in specs]
            stacked = jnp.stack(cols, axis=-1)
            if dtype == jnp.float64:
                # floats must not share a prefix across segments (one
                # group's magnitude would absorb its neighbours');
                # integers wrap mod 2^n, where prefix differences ARE
                # the segment sums
                part = _tile_segment_sums(stacked, f_t, bounds)
            else:
                part = _tile_diffs(stacked, bounds)
            if saturate:
                # flag streams summed per tile in 21-bit lanes: saturate
                # to presence bits, then OR across tile boundaries
                part = _saturate_flags(part)
                comb = cprev | part[0]
            else:
                comb = cprev + part[0]
            row0 = jnp.arange(part.shape[0],
                              dtype=jnp.int32)[:, None] == 0
            part = jnp.where(row0, comb[None, :], part)
            c_out = part[last_local]
            part = jnp.where(keep, part, jnp.zeros((), part.dtype))
            buf = lax.dynamic_update_slice(buf, part,
                                           (w_base, jnp.int32(0)))
            return c_out, buf

        cu64, b_u64 = family(u64_specs, jnp.uint64, cu64, b_u64, False)
        cu32, b_u32 = family(u32_specs, jnp.uint32, cu32, b_u32, False)
        cf64, b_f64 = family(f64_specs, jnp.float64, cf64, b_f64, False)
        cflag, b_flag = family(or_specs, jnp.uint64, cflag, b_flag, True)

        if pos:
            npr = []
            for i, spec in enumerate(pos):
                cons_t = spec.cons(ctx) & lv_t
                if p_is_min[i]:
                    # first considered = smallest position: pack as
                    # BL - position so one cumulative MAX finds it (the
                    # nondecreasing seg prefix makes later segments
                    # dominate earlier ones)
                    pay = jnp.where(cons_t,
                                    jnp.uint64(BL) - row_ids.astype(
                                        jnp.uint64),
                                    jnp.uint64(0))
                else:
                    pay = jnp.where(cons_t,
                                    row_ids.astype(jnp.uint64) + 1,
                                    jnp.uint64(0))
                enc = (jnp.minimum(seg_local, BL).astype(jnp.uint64)
                       * PACK + pay)
                cmax = lax.cummax(enc)
                at_end = jnp.take(cmax, jnp.maximum(hi - 1, 0),
                                  mode="clip")
                pay_end = at_end % PACK
                found = present & (pay_end > 0)
                ppos = jnp.where(
                    p_is_min[i],
                    jnp.uint64(BL) - jnp.maximum(pay_end, 1),
                    jnp.maximum(pay_end, 1) - 1).astype(jnp.int32)
                rw = jnp.where(
                    found,
                    jnp.take(p_t, jnp.clip(ppos, 0, tile - 1),
                             mode="clip"),
                    -1)
                # open-segment carry: for 'first' an earlier tile's hit
                # is earlier in sorted (== original) order and always
                # wins; for 'last' the current tile's hit wins. Masked
                # select on local segment 0, never .at[0].set (a
                # single-element DUS in the body reads as scatter)
                cr = cpr[i]
                take_c = (cr >= 0) & (p_is_min[i] | (rw[0] < 0))
                bl0 = jnp.arange(BL, dtype=jnp.int32) == 0
                rw = jnp.where(bl0 & take_c, cr, rw)
                npr.append(rw[last_local])
                rw = jnp.where(keep1, rw, -1)
                b_pos = lax.dynamic_update_slice(
                    b_pos, rw[:, None], (w_base, jnp.int32(i)))
            cpr = jnp.stack(npr)

        if winners:
            nww, nwr = [], []
            for i, spec in enumerate(winners):
                cons_t = spec.cons(ctx) & lv_t
                ident = jnp.uint64(_U64_MAX if w_is_min[i] else 0)
                word_t = jnp.where(cons_t, spec.word(ctx), ident)
                # one tile-local secondary sort: within each segment the
                # winner sits at the first (min) / last (max) position.
                # Considered rows sort toward the winner position (the
                # crank key) so an identity-word collision — int64.max
                # under min radix-encodes to the identity — can never
                # let a non-considered row shadow a real winner.
                crank = (~cons_t if w_is_min[i] else cons_t).astype(
                    jnp.uint32)
                _, _, sword, sperm, scons = lax.sort(
                    [seg_local, crank, word_t, p_t,
                     cons_t.astype(jnp.uint32)],
                    num_keys=3, is_stable=True)
                wpos = lo if w_is_min[i] else jnp.maximum(hi - 1, 0)
                wd = jnp.where(present,
                               jnp.take(sword, wpos, mode="clip"), ident)
                won = present & (jnp.take(scons, wpos, mode="clip") > 0)
                rw = jnp.where(won, jnp.take(sperm, wpos, mode="clip"),
                               -1)
                # combine the open segment (local 0) with the carry
                # pair; cr < 0 marks "no considered row yet" and never
                # wins, and an empty current winner yields to a carry
                cw, cr = cww[i], cwr[i]
                better = (cw <= wd[0]) if w_is_min[i] else (cw >= wd[0])
                take_c = (cr >= 0) & (better | (rw[0] < 0))
                # masked selects, not .at[0].set — a single-element
                # dynamic-update-slice inside the while body is exactly
                # the CPU scatter-emulation signature the hlo.py
                # classifier hunts, and this loop must never read as one
                bl0 = jnp.arange(BL, dtype=jnp.int32) == 0
                wd = jnp.where(bl0 & take_c, cw, wd)
                rw = jnp.where(bl0 & take_c, cr, rw)
                nww.append(wd[last_local])
                nwr.append(rw[last_local])
                rw = jnp.where(keep1, rw, -1)
                b_wrow = lax.dynamic_update_slice(
                    b_wrow, rw[:, None], (w_base, jnp.int32(i)))
            cww, cwr = jnp.stack(nww), jnp.stack(nwr)

        new_prev_w = tuple(w_t[-1] for w_t in w_ts)
        return (S + n_new, jnp.bool_(True), new_prev_w,
                cu64, cu32, cf64, cflag, cpr, cww, cwr,
                b_u64, b_u32, b_f64, b_flag, b_pos, b_wrow)

    init = (
        jnp.int32(0),
        jnp.bool_(False),
        tuple(jnp.zeros((), w.dtype) for w in sorted_words),
        jnp.zeros(max(1, len(u64_specs)), jnp.uint64),
        jnp.zeros(max(1, len(u32_specs)), jnp.uint32),
        jnp.zeros(max(1, len(f64_specs)), jnp.float64),
        jnp.zeros(max(1, len(or_specs)), jnp.uint8),
        jnp.full(max(1, len(pos)), -1, jnp.int32),
        (jnp.asarray([_U64_MAX if m else 0 for m in w_is_min],
                     jnp.uint64)
         if winners else jnp.zeros(1, jnp.uint64)),
        jnp.full(max(1, len(winners)), -1, jnp.int32),
        jnp.zeros((cap + BL, max(1, len(u64_specs))), jnp.uint64),
        jnp.zeros((cap + BL, max(1, len(u32_specs))), jnp.uint32),
        jnp.zeros((cap + BL, max(1, len(f64_specs))), jnp.float64),
        jnp.zeros((cap + BL, max(1, len(or_specs))), jnp.uint8),
        jnp.full((cap + BL, max(1, len(pos))), -1, jnp.int32),
        jnp.full((cap + BL, max(1, len(winners))), -1, jnp.int32),
    )
    (S, _, _, _, _, _, _, _, _, _,
     b_u64, b_u32, b_f64, b_flag, b_pos, b_wrow) = lax.fori_loop(
        0, trips, body, init)
    return SegmentedOutputs(
        u64=[b_u64[:cap, i] for i in range(len(u64_specs))],
        u32=[b_u32[:cap, i] for i in range(len(u32_specs))],
        f64=[b_f64[:cap, i] for i in range(len(f64_specs))],
        flags=[b_flag[:cap, i] for i in range(len(or_specs))],
        pos_rows=[b_pos[:cap, i] for i in range(len(pos))],
        winner_rows=[b_wrow[:cap, i] for i in range(len(winners))],
        nseg=S,
    )


# ---------------------------------------------------------------------------
# shared segmented-scan helpers (the RADIX join tier's co-sorted merge in
# ops/join.py reuses the same boundary-flag machinery this module's tile
# loop is built from)
# ---------------------------------------------------------------------------
def segment_start_broadcast(flags: jax.Array,
                            values: jax.Array) -> jax.Array:
    """Broadcast ``values`` at segment-start positions (``flags``) to
    every later row of the segment, via one cumulative max — valid
    whenever the flagged values are NONDECREASING across segment starts
    (true for any prefix-sum-derived stream over a sorted order, e.g.
    the join merge's running build counts). Rows before the first flag
    report -1."""
    marked = jnp.where(flags, values.astype(jnp.int32), -1)
    return lax.cummax(marked)


# ---------------------------------------------------------------------------
# tile-local stream pieces (used by the groupby plan builder's closures)
# ---------------------------------------------------------------------------
def float_sum_streams(data, consider):
    """(normal, big, flag_fields) streams for one float column — tile-
    local when called from an AddSpec builder (the intended use), but
    shape-polymorphic.

    normal: plain finite values (|x| <= 2^500), identity elsewhere;
    big: huge finite values scaled by 2^-600 (exact), identity elsewhere;
    flag_fields: 21-bit-lane counts (+inf at bit 0, -inf at bit 21, NaN
    at bit 42) — an OR stream for :func:`tiled_segment_groupby`.
    """
    d = jnp.where(consider, data, 0.0).astype(jnp.float64)
    isnan = d != d
    ispinf = d == jnp.inf
    isninf = d == -jnp.inf
    finite = jnp.isfinite(d)
    big = finite & (jnp.abs(d) > F64_BIG)
    normal = jnp.where(finite & ~big, d, 0.0)
    bigs = jnp.where(big, d * BIG_SCALE_DOWN, 0.0)
    fields = (ispinf.astype(jnp.uint64)
              | (isninf.astype(jnp.uint64) << _FLAG_LANE)
              | (isnan.astype(jnp.uint64) << (2 * _FLAG_LANE)))
    return normal, bigs, fields


def combine_float_sum(normal: jax.Array, big: jax.Array,
                      flags: jax.Array) -> jax.Array:
    """Recombine one float column's per-segment streams with IEEE
    semantics: NaN (or mixed infinities) dominates, then the surviving
    infinity, else normal + big * 2^600 (which may itself overflow to
    the mathematically correct infinity). ``flags`` is the (cap,) uint8
    presence output of the OR stream.

    The rescale is taken only where the BIG stream holds something. The
    f64 a TPU emulates has f32's exponent range: ``2^600`` is ``inf``
    there, no finite value passes ``F64_BIG`` (also ``inf``), so the BIG
    stream is all zeros, and an unguarded ``0 * inf`` made every sum
    NaN on the chip."""
    p = (flags & jnp.uint8(1)) != 0
    m = (flags & jnp.uint8(2)) != 0
    q = (flags & jnp.uint8(4)) != 0
    s = normal + jnp.where(big != 0.0, big * BIG_SCALE_UP, 0.0)
    r = jnp.where(p, jnp.inf, jnp.where(m, -jnp.inf, s))
    return jnp.where(q | (p & m), jnp.nan, r)


def order_word(col_data: jax.Array, consider: jax.Array, dtype,
               op: str) -> jax.Array:
    """Total-order uint64 word for a min/max winner stream: the sort
    machinery's order-preserving radix encoding (Spark NaN-largest,
    -0.0 == 0.0), with the op's identity at non-considered rows.
    Elementwise, so MinMaxSpec builders call it on tile slices."""
    from ..expr.eval import ColV
    from .sort import SortOrder, fixed_radix_keys

    _, vk = fixed_radix_keys(
        ColV(col_data, consider), dtype, SortOrder(True, True))
    w = vk.astype(jnp.uint64)
    ident = jnp.uint64(_U64_MAX if op == "min" else 0)
    return jnp.where(consider, w, ident)
