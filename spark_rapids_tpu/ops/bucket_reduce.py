"""Bucket reductions under interchangeable lowerings: one-hot limb matmul
(MXU) and native segment scatter (the Pallas kernels of
ops/pallas_groupby ride the same entry).

TPU-first design with no reference analog: XLA's scatter (what
``jax.ops.segment_sum`` lowers to) runs near-serially on TPU (~10ns/row),
while the MXU multiplies 256x256 tiles for free. A bucket reduction
``out[b] = sum(x[i] for seg[i]==b)`` is exactly ``one_hot(seg) @ x`` — and
XLA fuses the one-hot generation into the matmul so the (n, B) matrix never
materializes.

The matmul prices the reduction in MXU flops (cap x limbs x B MACs). The
strategy is selected per plan by the aggregate exec's chooser
(``spark.rapids.tpu.sql.agg.strategy``, exec/aggregate.py) and recorded
in the event log so a wrong prediction is visible in tools/tpu_profile.

Exactness: f32 matmuls (precision=HIGHEST) are exact for addends < 2^24.
int64 values split into 8x8-bit limbs reduced in row-blocks of 65536
(block limb sum <= 65536*255 < 2^24), block partials accumulate in int64 —
bit-exact integer sums at matmul speed, including Java wraparound. The
8-bit/65536-row shape keeps the per-block partial tensor (nblocks, L, B)
tiny; 16-bit limbs would force 256-row blocks and a gigabyte-scale
transient. Counts are a ones-limb. Doubles use a hi/lo float split (not
bit-exact, order-insensitive — the reference gates float aggregation the
same way: spark.rapids.sql.variableFloatAgg.enabled). A float sum that
must NOT be approximate rides the same matmul as signed fixed-point limbs
(:func:`_fixed_point_limbs`): integer limb totals, so no scatter and no
order dependence.

Out-of-range segment ids (padding/dead rows) one-hot to a zero row and
drop out of every reduction for free.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

BLOCK_R = 1 << 16  # rows per block: 65536 * 255 < 2^24 keeps f32 exact
N_LIMBS = 8  # 8-bit limbs per int64

#: the fixed-point float sum's window: bits of the grid below the top of
#: the call's largest addend (``N_LIMBS`` sign x magnitude limbs)
FIXED_WINDOW_BITS = 8 * N_LIMBS
#: binary orders of magnitude below the call's largest addend that the
#: window still holds to within 2^-40 of the addend's own size: an addend
#: of f32 exponent ``e`` under the largest ``E`` is cut at 2^(E-e+1-64)
#: of its size (times 1 + 2^-23), so ``E - e <= 22`` keeps the cut under
#: 2^-40. Smaller addends (and every non-finite one) take the detour
FIXED_SPAN = FIXED_WINDOW_BITS - 42
#: the smallest f32 exponent (biased) whose addend the two f32 words hold
#: to 2^-40 even where a backend flushes denormals: under 2^-86 the ``lo``
#: word may be one, and the addend detours
_LO_WORD_SAFE_EXP = 41

_HI = jax.lax.Precision.HIGHEST

#: test hook: force the MXU limb-matmul lowering even on the CPU backend
#: (differential tests diff it against the scatter lowering)
FORCE_MATMUL = False

#: test hook: run every reduction ONE COLUMN AT A TIME instead of fusing
#: all columns into a single limb-matmul / scatter family — the
#: differential baseline the fused path is diffed against (same spirit as
#: FORCE_MATMUL: a lowering switch, never a semantics switch)
FORCE_PER_COLUMN = False


def _resolve_strategy(strategy=None) -> str:
    """Resolve the lowering for one reduction (trace-time static, so each
    jit cache entry is per-strategy and per-backend). ``strategy`` is an
    already-chosen MATMUL/SCATTER/PALLAS from the aggregate exec's
    chooser; None/AUTO falls back to the backend default: the MXU
    tradeoff inverts on XLA CPU, where the one-hot never fuses — it
    materializes (n, B) compare-selects at ~7ns/element (measured:
    1.7-2.3 s for 2M rows x 128 buckets) while scatter runs a tight
    serial loop (~0.2 s for the same shape, 4-10x faster). On TPU
    scatter is the near-serial one (~10ns/row) and the matmul is free.
    ``FORCE_MATMUL`` (test hook) outranks everything so the MXU limb
    path stays differentially covered on the CPU backend."""
    if FORCE_MATMUL:
        return "MATMUL"
    if strategy in ("MATMUL", "SCATTER", "PALLAS"):
        return strategy
    return "SCATTER" if jax.default_backend() == "cpu" else "MATMUL"


def _bucket_reduce_scatter(
    seg: jax.Array,
    B: int,
    int_cols: Sequence[Tuple[jax.Array, jax.Array]],
    count_cols: Sequence[jax.Array],
    float_cols: Sequence[Tuple[jax.Array, jax.Array]],
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array]]:
    """CPU lowering of :func:`bucket_reduce`: native-dtype segment sums,
    one batched scatter per dtype family. No limb splitting — int64 adds
    are native here and wrap mod 2^64 exactly like the limb accumulate;
    float sums run in f64 (at least as accurate as the hi/lo split).
    Counts ride the f64 scatter (exact below 2^53, and row capacities are
    far below that) so the common sum+count aggregate is ONE scatter pass.
    Out-of-range ids drop, matching the one-hot zero row."""
    ints = [
        jnp.where(valid, data.astype(jnp.int64), jnp.int64(0))
        for data, valid in int_cols
    ]
    out_int: List[jax.Array] = []
    if ints:
        s = jax.ops.segment_sum(
            jnp.stack(ints, axis=-1), seg, num_segments=B)
        out_int = [s[:, i] for i in range(len(ints))]
    out_cnt: List[jax.Array] = []
    out_flt: List[jax.Array] = []
    fcols = [valid.astype(jnp.float64) for valid in count_cols] + [
        jnp.where(valid, data, 0.0).astype(jnp.float64)
        for data, valid in float_cols
    ]
    if fcols:
        f = jax.ops.segment_sum(jnp.stack(fcols, axis=-1), seg,
                                num_segments=B)
        out_cnt = [f[:, i].astype(jnp.int64) for i in range(len(count_cols))]
        out_flt = [f[:, len(count_cols) + i]
                   for i in range(len(float_cols))]
    return out_int, out_cnt, out_flt


def bucket_reduce(
    seg: jax.Array,
    B: int,
    int_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
    count_cols: Sequence[jax.Array] = (),
    float_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
    strategy: str = None,
    fixed_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array],
           Tuple[List[jax.Array], jax.Array]]:
    """ALL requested reductions across ALL columns in one fused pass.

    Multi-column fusion is the point: every column's limbs stack into one
    ``(n, L_total)`` operand (8 int limbs + 1 count limb + 2 float limbs
    per column) so a single one-hot matmul per row-block serves the whole
    aggregate plan — the contraction over ``n`` is shared and the MXU sees
    one wide matmul instead of C narrow ones. The CPU lowering fuses the
    same way: one batched scatter per dtype family, not one per column.
    ``FORCE_PER_COLUMN`` is the differential baseline (one pass per
    column) that tests diff this fusion against.

    seg: (n,) int32 bucket ids; ids >= B are dropped.
    int_cols:   [(data int64/int32, valid bool)] -> exact int64 sums (B,)
    count_cols: [valid bool] -> int64 counts (B,)
    float_cols: [(data f64/f32, valid bool)] -> f64 sums (B,) (hi/lo split)
    strategy:   MATMUL / SCATTER / PALLAS, or None for the backend default
                (see :func:`_resolve_strategy`).
    fixed_cols: [(data f64/f32, valid bool)] -> (f64 sums (B,), whether a
                row took the detour): float sums that may not be
                approximate, as fixed-point limbs of the same matmul
                (:func:`_fixed_point_limbs`). The MATMUL lowering only:
                the others have a native f64 sum and no use for it.
    """
    if FORCE_PER_COLUMN:
        out_int: List[jax.Array] = []
        out_cnt: List[jax.Array] = []
        out_flt: List[jax.Array] = []
        out_fix: List[jax.Array] = []
        detoured = jnp.bool_(False)
        for spec in int_cols:
            out_int += _bucket_reduce_pass(seg, B, [spec], (), (),
                                           strategy)[0]
        for valid in count_cols:
            out_cnt += _bucket_reduce_pass(seg, B, (), [valid], (),
                                           strategy)[1]
        for spec in float_cols:
            out_flt += _bucket_reduce_pass(seg, B, (), (), [spec],
                                           strategy)[2]
        for spec in fixed_cols:
            sums, flag = _bucket_reduce_pass(seg, B, (), (), (), strategy,
                                             [spec])[3]
            out_fix += sums
            detoured = detoured | flag
        return out_int, out_cnt, out_flt, (out_fix, detoured)
    return _bucket_reduce_pass(seg, B, int_cols, count_cols, float_cols,
                               strategy, fixed_cols)


def _bucket_reduce_pass(
    seg: jax.Array,
    B: int,
    int_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
    count_cols: Sequence[jax.Array] = (),
    float_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
    strategy: str = None,
    fixed_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array],
           Tuple[List[jax.Array], jax.Array]]:
    resolved = _resolve_strategy(strategy)
    if resolved != "MATMUL":
        assert not fixed_cols, (
            "fixed-point float sums are the MATMUL lowering's", resolved)
        if resolved == "SCATTER":
            out = _bucket_reduce_scatter(
                seg, B, int_cols, count_cols, float_cols)
        else:
            from .pallas_groupby import pallas_bucket_reduce

            out = pallas_bucket_reduce(seg, B, int_cols, count_cols,
                                       float_cols)
        return (*out, ([], jnp.bool_(False)))
    n = seg.shape[0]
    limbs: List[jax.Array] = []
    for data, valid in int_cols:
        # split into u32 halves first: all limb math stays 32-bit (64-bit
        # elementwise ops are emulated on TPU at ~2-4x cost)
        halves = jax.lax.bitcast_convert_type(
            data.astype(jnp.int64), jnp.uint32)  # (n, 2) little-endian
        for half in (halves[..., 0], halves[..., 1]):
            h = jnp.where(valid, half, jnp.uint32(0))
            for i in range(4):
                limbs.append(((h >> (8 * i)) & jnp.uint32(0xFF)).astype(jnp.float32))
    for valid in count_cols:
        limbs.append(valid.astype(jnp.float32))
    nf_start = len(limbs)
    F32_MAX = jnp.float64(3.4028234663852886e38)
    flt_corrections: List[Tuple[jax.Array, jax.Array]] = []
    for data, valid in float_cols:
        d = jnp.where(valid, data, 0.0).astype(jnp.float64)
        # |x| beyond f32 range would make hi=inf and lo=NaN; zero those rows
        # out of the matmul path and scatter-add them separately (cond'd on
        # actually seeing one, so the common case pays no scatter). NaN
        # rows must detour too — abs(NaN) > x is False, and a NaN in the
        # matmul stream poisons EVERY bucket through the one-hot dot
        ovf = ~(jnp.abs(d) <= F32_MAX)
        d_main = jnp.where(ovf, 0.0, d)
        hi = d_main.astype(jnp.float32)
        lo = (d_main - hi.astype(jnp.float64)).astype(jnp.float32)
        limbs.append(hi)
        limbs.append(lo)
        flt_corrections.append((jnp.any(ovf), jnp.where(ovf, d, 0.0)))
    nx_start = len(limbs)
    fixed = [_fixed_point_limbs(data, valid) for data, valid in fixed_cols]
    for cut in fixed:
        limbs.extend(cut[0])
    if not limbs:
        return [], [], [], ([], jnp.bool_(False))
    cols = jnp.stack(limbs, axis=-1)  # (n, L)
    L = cols.shape[1]

    R = min(BLOCK_R, n)
    nb = n // R
    S_parts = []
    if nb:
        oh_src = seg[: nb * R].reshape(nb, R)
        c = cols[: nb * R].reshape(nb, R, L)
        oh = jax.nn.one_hot(oh_src, B, dtype=jnp.float32)
        S_parts.append(jnp.einsum("brl,brB->blB", c, oh, precision=_HI))
    tail = n - nb * R
    if tail:
        oh_t = jax.nn.one_hot(seg[nb * R:], B, dtype=jnp.float32)
        St = jnp.einsum("rl,rB->lB", cols[nb * R:], oh_t, precision=_HI)
        S_parts.append(St[None])
    S = jnp.concatenate(S_parts, axis=0) if len(S_parts) > 1 else S_parts[0]
    acc_i = S[:, :nf_start, :].astype(jnp.int64).sum(axis=0)  # exact
    acc_f = S[:, nf_start:nx_start, :].astype(jnp.float64).sum(axis=0)
    acc_x = S[:, nx_start:, :].astype(jnp.int64).sum(axis=0)  # exact

    out_int: List[jax.Array] = []
    k = 0
    for _ in int_cols:
        total = jnp.zeros(B, jnp.uint64)
        for i in range(N_LIMBS):
            total = total + (acc_i[k].astype(jnp.uint64) << (8 * i))
            k += 1
        out_int.append(total.astype(jnp.int64))
    out_cnt: List[jax.Array] = []
    for _ in count_cols:
        out_cnt.append(acc_i[k])
        k += 1
    out_flt: List[jax.Array] = []
    k = 0
    for (any_ovf, d_ovf) in flt_corrections:
        corr = jax.lax.cond(
            any_ovf,
            lambda d=d_ovf: jax.ops.segment_sum(d, seg, num_segments=B),
            lambda: jnp.zeros(B, jnp.float64),
        )
        out_flt.append(acc_f[k] + acc_f[k + 1] + corr)
        k += 2
    out_fix: List[jax.Array] = []
    detoured = jnp.bool_(False)
    for j, (_, detour, top, d) in enumerate(fixed):
        # highest limb first, each term exact: limb i of the grid weighs
        # 2^(8i - 63) in units of 2^(top - 127), the largest addend's own
        # power of two (a normal f32, so inside what the chip's f64 holds)
        total = jnp.zeros(B, jnp.float64)
        for i in reversed(range(N_LIMBS)):
            total = total + (acc_x[N_LIMBS * j + i].astype(jnp.float64)
                             * (2.0 ** (8 * i - (FIXED_WINDOW_BITS - 1))))
        unit = jax.lax.bitcast_convert_type(
            top.astype(jnp.uint32) << 23, jnp.float32).astype(jnp.float64)
        any_detour = jnp.any(detour)
        corr = jax.lax.cond(
            any_detour,
            lambda d=d, detour=detour: jax.ops.segment_sum(
                jnp.where(detour, d, 0.0), seg, num_segments=B),
            lambda: jnp.zeros(B, jnp.float64),
        )
        out_fix.append(total * unit + corr)
        detoured = detoured | any_detour
    return out_int, out_cnt, out_flt, (out_fix, detoured)


def _place(m: jax.Array, sh: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """The (high, low) u32 words of ``m * 2**sh`` cut to 64 bits, for a
    mantissa ``m < 2**24`` and a shift ``sh <= 40`` (int32, any sign):
    bits that fall below bit 0 are dropped — the cut to the grid."""
    zero = jnp.uint32(0)

    def by(x):  # a shift count in range; the cases out of it select zero
        return jnp.clip(x, 0, 31).astype(jnp.uint32)

    low = jnp.where(sh >= 32, zero,
                    jnp.where(sh >= 0, m << by(sh), m >> by(-sh)))
    high = jnp.where(sh >= 32, m << by(sh - 32),
                     jnp.where(sh > 8, m >> by(32 - sh), zero))
    return high, low


def _fixed_point_limbs(
    data: jax.Array, valid: jax.Array
) -> Tuple[List[jax.Array], jax.Array, jax.Array, jax.Array]:
    """One float column as ``N_LIMBS`` signed 8-bit limbs of a fixed-point
    grid, for the limb matmul: a float sum from exact integer totals.

    One pass finds ``top``, the largest f32 exponent among the finite
    addends of the call. The grid is ``FIXED_WINDOW_BITS`` wide under the
    top of that binade; every addend is cut to it (toward zero) and split
    sign x magnitude into 8-bit limbs, so |limb| <= 255, a block of
    ``BLOCK_R`` rows sums below 2^24 and the f32 one-hot matmul is exact,
    as for the int limbs. Block totals accumulate in int64; the caller
    rebuilds the float from the limb totals, highest first. The only
    roundings are each addend's own cut and that last recombination:
    an addend within ``FIXED_SPAN`` binades of the largest is cut by less
    than 2^-40 of its own size, and nothing at all when the call's range
    of magnitudes times an addend's precision fits the window (two-decimal
    prices of 1 to 100 need 55 bits of the 64). Hence **the sum does not
    depend on the order of the rows** (integer totals), nor — where no
    addend is cut — on how the rows are split into calls, chunks or
    shards, up to the recombination's last-place rounding. A scatter-add
    has neither property.

    The addend is taken as the two f32 words the chip holds an f64 in,
    ``hi = f32(x)`` and ``lo = f32(x - hi)`` (XLA folds the round trip
    there; on a backend with a native f64 this rounds x to 48 bits), and
    everything from the bitcast on is 32-bit integer work: exponent,
    mantissa, shift, borrow. Rows the window cannot serve **detour**, as
    |x| > F32_MAX does in the hi/lo lowering: a non-finite value (NaN,
    an infinity, or beyond f32's range), a nonzero addend more than
    ``FIXED_SPAN`` binades under the largest or under 2^-86 (where the
    ``lo`` word may be a denormal), and a (hi, lo) pair that is not
    normalised (|lo| >= ulp(hi): the limbs' bits would overlap).
    Their limbs are zero and the caller adds them by a ``segment_sum``
    under a ``lax.cond`` on "any such row".

    Returns (limbs, detour mask, ``top``, the masked f64 addends).
    """
    u = jnp.uint32
    d = jnp.where(valid, data, 0.0).astype(jnp.float64)
    hi = d.astype(jnp.float32)
    lo = (d - hi.astype(jnp.float64)).astype(jnp.float32)
    bh = jax.lax.bitcast_convert_type(hi, u)
    bl = jax.lax.bitcast_convert_type(lo, u)
    eh = ((bh >> 23) & u(0xFF)).astype(jnp.int32)
    el = ((bl >> 23) & u(0xFF)).astype(jnp.int32)
    nonfinite = eh == 0xFF
    top = jnp.maximum(jnp.max(jnp.where(nonfinite, 0, eh)), 1)
    # a subnormal has exponent 1 and no implicit bit
    ehe, ele = jnp.maximum(eh, 1), jnp.maximum(el, 1)
    lo_set = (bl & u(0x7FFFFFFF)) != 0
    detour = (nonfinite
              | ((eh < jnp.maximum(top - FIXED_SPAN, _LO_WORD_SAFE_EXP))
                 & (d != 0.0))
              | (lo_set & (ele > ehe - 24)))

    def mantissa(bits, e):
        m = (bits & u(0x7FFFFF)) | jnp.where(e > 0, u(1 << 23), u(0))
        return jnp.where(detour, u(0), m)

    at_top = FIXED_WINDOW_BITS - 24  # the shift of an addend of exponent top
    h1, h0 = _place(mantissa(bh, eh), ehe - top + at_top)
    l1, l0 = _place(mantissa(bl, el), ele - top + at_top)
    # |lo| < ulp(hi) <= |hi|: on the grid the two share no bit, so their
    # sum is an OR and their difference borrows at most across the words
    same = (bh >> 31) == (bl >> 31)
    w0 = jnp.where(same, h0 | l0, h0 - l0)
    w1 = jnp.where(same, h1 | l1, h1 - l1 - (h0 < l0).astype(u))
    negative = (bh >> 31) == 1
    limbs: List[jax.Array] = []
    for w in (w0, w1):
        for i in range(4):
            mag = ((w >> (8 * i)) & u(0xFF)).astype(jnp.float32)
            limbs.append(jnp.where(negative, -mag, mag))
    return limbs, detour, top, d


def bucket_min_max(
    seg: jax.Array, B: int, op: str, cols: Sequence[jax.Array],
    strategy: str = None,
) -> List[jax.Array]:
    """Per-bucket min/max for ALL columns of one (op, dtype) family in ONE
    segment scatter — the scatter-side analog of the fused limb matmul:
    the near-serial walk over ``seg`` (the expensive part on TPU) happens
    once per family instead of once per column. ``cols`` are (n,) arrays
    of one dtype, already masked to the op's identity fill by the caller
    (invalid/dead rows hold +/-inf, dtype extremes, etc. so they never
    win); callers overwrite empty buckets via their count mask. Returns
    (B,) arrays aligned with ``cols``. Under the PALLAS strategy the
    winners reduce in the VMEM-resident word kernel instead of a
    scatter."""
    if _resolve_strategy(strategy) == "PALLAS":
        from .pallas_groupby import pallas_bucket_min_max

        return pallas_bucket_min_max(seg, B, op, cols)
    fn = jax.ops.segment_max if op == "max" else jax.ops.segment_min
    if FORCE_PER_COLUMN or len(cols) == 1:
        return [fn(d, seg, num_segments=B) for d in cols]
    stacked = jnp.stack(cols, axis=-1)  # (n, C)
    r = fn(stacked, seg, num_segments=B)  # (B, C)
    return [r[:, i] for i in range(len(cols))]


def bucket_lookup_u32(
    seg: jax.Array, B: int, table: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Per-row lookup of a u32 table value by bucket id, exactly, via two
    16-bit-limb one-hot matmuls. Returns (lo, hi) f32 per row (each < 2^16,
    exact). Rows with seg >= B read 0."""
    if _resolve_strategy() == "SCATTER":
        # CPU: a plain clipped gather is exact and ~B x cheaper than the
        # materialized one-hot
        t = jnp.where(
            (seg >= 0) & (seg < B),
            jnp.take(table, jnp.clip(seg, 0, B - 1), mode="clip"),
            jnp.uint32(0))
        return ((t & jnp.uint32(0xFFFF)).astype(jnp.float32),
                (t >> 16).astype(jnp.float32))
    n = seg.shape[0]
    lo = (table & jnp.uint32(0xFFFF)).astype(jnp.float32)
    hi = (table >> 16).astype(jnp.float32)
    t2 = jnp.stack([lo, hi], axis=-1)  # (B, 2)
    R = min(4096, n)
    nb = n // R
    parts = []
    if nb:
        head = seg[: nb * R].reshape(nb, R)
        oh = jax.nn.one_hot(head, B, dtype=jnp.float32)
        parts.append(
            jnp.einsum("brB,Bt->brt", oh, t2, precision=_HI).reshape(nb * R, 2))
    tail = n - nb * R
    if tail:
        oh_t = jax.nn.one_hot(seg[nb * R:], B, dtype=jnp.float32)
        parts.append(jnp.einsum("rB,Bt->rt", oh_t, t2, precision=_HI))
    vals = jnp.concatenate(parts, axis=0) if len(parts) > 1 else parts[0]
    return vals[:, 0], vals[:, 1]


def bucket_equal_check(
    seg: jax.Array,
    B: int,
    word: jax.Array,
    rep_table: jax.Array,
    live: jax.Array,
) -> jax.Array:
    """True iff every live row's u32 ``word`` equals its bucket's
    representative (exact collision detection for hash groupby)."""
    lo, hi = bucket_lookup_u32(seg, B, rep_table)
    wlo = (word & jnp.uint32(0xFFFF)).astype(jnp.float32)
    whi = (word >> 16).astype(jnp.float32)
    mismatch = live & ((lo != wlo) | (hi != whi))
    return ~jnp.any(mismatch)
