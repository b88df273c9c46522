"""Hand-written Pallas TPU kernels for the hash-groupby update — the
PALLAS aggregation lowering.

Where the cost plane proves XLA fusion won't cooperate (the one-hot
expansion materializing ~25x the logical working set, BENCH_r09 +
hlo.py), these kernels pin the working set explicitly: each grid step
holds one (rows-block x buckets-block) one-hot mask in VMEM, reduces it
there, and accumulates into a buckets-resident output block — the mask
NEVER exists in HBM, so bytes-accessed is the input stream plus the
(tiny) bucket table. The reference's cuDF hash-groupby kernels own
their shared-memory working set the same way; this is that design
retargeted at the TPU memory hierarchy.

Kernels (all dtypes TPU-valid: u32/i32/f32 only — 64-bit values travel
as u32 half/limb planes built outside the kernel):

  * sums/counts: int64 columns split into 16 4-bit limbs (per-block
    one-hot dot is exact in f32 at <= 2^15 per limb; the cross-block
    int32 accumulator stays exact to capacity 2^27 rows), counts as a
    ones limb — reconstruction outside wraps mod 2^64, BIT-identical to
    every other lowering including Java wraparound;
  * float sums: f32 hi/lo split per column, per-block one-hot dots with
    a Kahan-compensated f32 cross-block accumulator (order-insensitive,
    the variableFloatAgg family); |x| beyond f32 range detours through
    the same rare correction the matmul lowering uses;
  * min/max + first/last + representative row: per-bucket lexicographic
    winner over (hi, lo) u32 total-order word planes (the sort
    machinery's radix encoding, so Spark NaN-largest / -0.0 == 0.0 fall
    out), masked VMEM reductions per block, pair-compare across blocks.

``interpret=True`` off-TPU executes the very same kernels under the
Pallas interpreter — the CPU-CI execution path the differential suite
runs (tests/test_radix_agg.py).
"""
from __future__ import annotations

import functools
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp

#: rows per grid step (the VMEM-resident one-hot's row extent). Kept
#: modest so the interpreter path stays fast in CI.
BLOCK_R = 256
#: buckets per grid step (the one-hot's column extent); B > BLOCK_B
#: tiles the bucket axis through the grid's outer dimension.
BLOCK_B = 256

_U32_MAX = 0xFFFFFFFF


#: what Mosaic (jax 0.9.0 / libtpu 0.0.34, asked for a v5e without the
#: chip, PR 23) said of these kernels at cap 2^21, B=128: the sum/float
#: kernels die on the x64 index maps ("failed to legalize operation
#: 'func.return' ... (i32, i64)"), the winner kernel on its u32 min/max
MOSAIC_REFUSAL = (
    "failed to legalize operation 'func.return' (i32, i64) [sum/float "
    "kernels]; Reductions over unsigned integers not implemented "
    "[winner kernel]")


def _interpret() -> bool:
    from ..envinfo import pallas_interpret

    return pallas_interpret("ops/pallas_groupby", MOSAIC_REFUSAL)


def _pad_rows(arrs: Sequence[jax.Array], n: int, r: int, fill):
    pad = (-n) % r
    if pad == 0:
        return list(arrs)
    return [jnp.concatenate([a, jnp.full((pad,) + a.shape[1:], f, a.dtype)])
            for a, f in zip(arrs, fill)]


def _grid_dims(n: int, B: int) -> Tuple[int, int, int, int]:
    r = min(BLOCK_R, max(8, n))
    bb = min(BLOCK_B, B)
    nbr = -(-max(1, n) // r)
    nbb = -(-B // bb)
    return r, bb, nbr, nbb


# ---------------------------------------------------------------------------
# sums / counts: 4-bit limb accumulation
# ---------------------------------------------------------------------------
def _sum_kernel(seg_ref, limb_ref, out_ref, *, bb):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    seg = seg_ref[...]
    cols = bi * bb + jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1)
    oh = (seg[:, None] == cols).astype(jnp.float32)  # (r, bb) in VMEM only
    partial = jax.lax.dot_general(
        oh, limb_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (bb, L)
    out_ref[...] += partial.astype(jnp.int32)


def _limb_plane(seg: jax.Array, limbs: jax.Array, B: int) -> jax.Array:
    """(B, L) int32 per-bucket limb sums via the Pallas sum kernel."""
    from jax.experimental import pallas as pl

    n, L = limbs.shape
    r, bb, nbr, nbb = _grid_dims(n, B)
    seg_p, limbs_p = _pad_rows([seg, limbs], n, r, [B, 0.0])
    out = pl.pallas_call(
        functools.partial(_sum_kernel, bb=bb),
        out_shape=jax.ShapeDtypeStruct((nbb * bb, L), jnp.int32),
        grid=(nbb, nbr),
        in_specs=[
            pl.BlockSpec((r,), lambda bi, ri: (ri,)),
            pl.BlockSpec((r, L), lambda bi, ri: (ri, 0)),
        ],
        out_specs=pl.BlockSpec((bb, L), lambda bi, ri: (bi, 0)),
        interpret=_interpret(),
    )(seg_p, limbs_p)
    return out[:B]


def _float_kernel(seg_ref, fl_ref, sum_ref, comp_ref, *, bb):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        sum_ref[...] = jnp.zeros_like(sum_ref)
        comp_ref[...] = jnp.zeros_like(comp_ref)

    seg = seg_ref[...]
    cols = bi * bb + jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1)
    oh = (seg[:, None] == cols).astype(jnp.float32)
    partial = jax.lax.dot_general(
        oh, fl_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    # Kahan-compensated f32 accumulation across row blocks
    s = sum_ref[...]
    y = partial - comp_ref[...]
    t = s + y
    comp_ref[...] = (t - s) - y
    sum_ref[...] = t


def _float_plane(seg: jax.Array, fl: jax.Array, B: int
                 ) -> Tuple[jax.Array, jax.Array]:
    from jax.experimental import pallas as pl

    n, L = fl.shape
    r, bb, nbr, nbb = _grid_dims(n, B)
    seg_p, fl_p = _pad_rows([seg, fl], n, r, [B, 0.0])
    s, c = pl.pallas_call(
        functools.partial(_float_kernel, bb=bb),
        out_shape=(jax.ShapeDtypeStruct((nbb * bb, L), jnp.float32),
                   jax.ShapeDtypeStruct((nbb * bb, L), jnp.float32)),
        grid=(nbb, nbr),
        in_specs=[
            pl.BlockSpec((r,), lambda bi, ri: (ri,)),
            pl.BlockSpec((r, L), lambda bi, ri: (ri, 0)),
        ],
        out_specs=(pl.BlockSpec((bb, L), lambda bi, ri: (bi, 0)),
                   pl.BlockSpec((bb, L), lambda bi, ri: (bi, 0))),
        interpret=_interpret(),
    )(seg_p, fl_p)
    return s[:B], c[:B]


def pallas_bucket_reduce(
    seg: jax.Array,
    B: int,
    int_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
    count_cols: Sequence[jax.Array] = (),
    float_cols: Sequence[Tuple[jax.Array, jax.Array]] = (),
) -> Tuple[List[jax.Array], List[jax.Array], List[jax.Array]]:
    """PALLAS lowering of :func:`bucket_reduce`: same contract, same
    bit-exact integer sums/counts (4-bit limbs keep every accumulator
    within exact i32/f32 range to capacity 2^27 rows)."""
    n = seg.shape[0]
    assert n < (1 << 27), "pallas limb accumulators sized for cap < 2^27"
    seg = seg.astype(jnp.int32)
    limbs: List[jax.Array] = []
    for data, valid in int_cols:
        halves = jax.lax.bitcast_convert_type(
            data.astype(jnp.int64), jnp.uint32)  # (n, 2) little-endian
        for half in (halves[..., 0], halves[..., 1]):
            h = jnp.where(valid, half, jnp.uint32(0))
            for i in range(8):
                limbs.append(
                    ((h >> (4 * i)) & jnp.uint32(0xF)).astype(jnp.float32))
    for valid in count_cols:
        limbs.append(valid.astype(jnp.float32))
    out_int: List[jax.Array] = []
    out_cnt: List[jax.Array] = []
    if limbs:
        acc = _limb_plane(seg, jnp.stack(limbs, axis=-1), B)
        k = 0
        for _ in int_cols:
            total = jnp.zeros(B, jnp.uint64)
            for half in range(2):
                for i in range(8):
                    total = total + (acc[:, k].astype(jnp.uint64)
                                     << (32 * half + 4 * i))
                    k += 1
            out_int.append(total.astype(jnp.int64))
        for _ in count_cols:
            out_cnt.append(acc[:, k].astype(jnp.int64))
            k += 1
    out_flt: List[jax.Array] = []
    if float_cols:
        F32_MAX = jnp.float64(3.4028234663852886e38)
        planes: List[jax.Array] = []
        corrections: List[Tuple[jax.Array, jax.Array]] = []
        for data, valid in float_cols:
            d = jnp.where(valid, data, 0.0).astype(jnp.float64)
            # NaN must take the detour too (abs(NaN) > x is False): a
            # NaN left in the matmul stream poisons EVERY bucket through
            # the one-hot dot, not just its own
            ovf = ~(jnp.abs(d) <= F32_MAX)
            d_main = jnp.where(ovf, 0.0, d)
            hi = d_main.astype(jnp.float32)
            lo = (d_main - hi.astype(jnp.float64)).astype(jnp.float32)
            planes.extend([hi, lo])
            corrections.append((jnp.any(ovf), jnp.where(ovf, d, 0.0)))
        s, c = _float_plane(seg, jnp.stack(planes, axis=-1), B)
        for i, (any_ovf, d_ovf) in enumerate(corrections):
            # residual Kahan compensation folds in at f64 width; the
            # rare beyond-f32-range rows take the same cond'd scatter
            # correction as the matmul lowering
            total = (s[:, 2 * i].astype(jnp.float64)
                     - c[:, 2 * i].astype(jnp.float64)
                     + s[:, 2 * i + 1].astype(jnp.float64)
                     - c[:, 2 * i + 1].astype(jnp.float64))
            corr = jax.lax.cond(
                any_ovf,
                lambda d=d_ovf: jax.ops.segment_sum(d, seg, num_segments=B),
                lambda: jnp.zeros(B, jnp.float64),
            )
            out_flt.append(total + corr)
    return out_int, out_cnt, out_flt


# ---------------------------------------------------------------------------
# lexicographic winner over (hi, lo) u32 word planes: min/max, first/last,
# representative row
# ---------------------------------------------------------------------------
def _winner_kernel(seg_ref, hi_ref, lo_ref, whi_ref, wlo_ref, *, bb,
                   is_min):
    from jax.experimental import pallas as pl

    bi = pl.program_id(0)
    ident = jnp.uint32(_U32_MAX if is_min else 0)

    @pl.when(pl.program_id(1) == 0)
    def _():
        whi_ref[...] = jnp.full_like(whi_ref, ident)
        wlo_ref[...] = jnp.full_like(wlo_ref, ident)

    seg = seg_ref[...]
    cols = bi * bb + jax.lax.broadcasted_iota(jnp.int32, (1, bb), 1)
    mask = seg[:, None] == cols  # (r, bb) in VMEM only
    hi = hi_ref[...][:, None]
    lo = lo_ref[...][:, None]
    red = jnp.min if is_min else jnp.max
    cand_hi = red(jnp.where(mask, hi, ident), axis=0)
    tie = mask & (hi == cand_hi[None, :])
    cand_lo = red(jnp.where(tie, lo, ident), axis=0)
    ahi, alo = whi_ref[...], wlo_ref[...]
    if is_min:
        take = (cand_hi < ahi) | ((cand_hi == ahi) & (cand_lo < alo))
    else:
        take = (cand_hi > ahi) | ((cand_hi == ahi) & (cand_lo > alo))
    whi_ref[...] = jnp.where(take, cand_hi, ahi)
    wlo_ref[...] = jnp.where(take, cand_lo, alo)


def pallas_bucket_winner(
    seg: jax.Array, B: int, op: str, hi: jax.Array,
    lo: jax.Array = None,
) -> Tuple[jax.Array, jax.Array]:
    """(winner_hi, winner_lo) u32 per bucket: the lexicographic ``op``
    ('min'/'max') of the (hi, lo) word pair over each bucket's rows.
    Rows excluded from the reduction must carry the op identity
    (u32 max for min, 0 for max) in BOTH planes. Empty buckets return
    the identity; callers mask via their count/found checks."""
    from jax.experimental import pallas as pl

    n = seg.shape[0]
    seg = seg.astype(jnp.int32)
    if lo is None:
        lo = jnp.zeros(n, jnp.uint32)
    r, bb, nbr, nbb = _grid_dims(n, B)
    ident = _U32_MAX if op == "min" else 0
    seg_p, hi_p, lo_p = _pad_rows([seg, hi, lo], n, r, [B, ident, ident])
    whi, wlo = pl.pallas_call(
        functools.partial(_winner_kernel, bb=bb, is_min=op == "min"),
        out_shape=(jax.ShapeDtypeStruct((nbb * bb,), jnp.uint32),
                   jax.ShapeDtypeStruct((nbb * bb,), jnp.uint32)),
        grid=(nbb, nbr),
        in_specs=[
            pl.BlockSpec((r,), lambda bi, ri: (ri,)),
            pl.BlockSpec((r,), lambda bi, ri: (ri,)),
            pl.BlockSpec((r,), lambda bi, ri: (ri,)),
        ],
        out_specs=(pl.BlockSpec((bb,), lambda bi, ri: (bi,)),
                   pl.BlockSpec((bb,), lambda bi, ri: (bi,))),
        interpret=_interpret(),
    )(seg_p, hi_p, lo_p)
    return whi[:B], wlo[:B]


def _order_words(data: jax.Array, fill_excluded: jax.Array, op: str
                 ) -> Tuple[jax.Array, jax.Array]:
    """(hi, lo) u32 order-preserving word planes for one column (the
    sort machinery's radix encoding — NaN canonical-largest, -0.0
    folded), with the op identity at excluded rows."""
    from .sort import _float_radix, _int_radix

    if jnp.issubdtype(data.dtype, jnp.floating):
        w = _float_radix(data)
    elif data.dtype == jnp.bool_:
        w = data.astype(jnp.uint32)
    else:
        w = _int_radix(data)
    if w.dtype == jnp.uint64:
        hi = (w >> 32).astype(jnp.uint32)
        lo = (w & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    else:
        hi = w.astype(jnp.uint32)
        lo = jnp.zeros_like(hi)
    ident = jnp.uint32(_U32_MAX if op == "min" else 0)
    return (jnp.where(fill_excluded, ident, hi),
            jnp.where(fill_excluded, ident, lo))


def _decode_word(whi: jax.Array, wlo: jax.Array, dtype) -> jax.Array:
    """Invert :func:`_order_words` for one winner word pair."""
    from jax import lax

    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating):
        def f32val(k32):
            s = jnp.uint32(1 << 31)
            bits = jnp.where(k32 & s != 0, k32 ^ s, ~k32)
            return lax.bitcast_convert_type(bits, jnp.float32)
        if dtype == jnp.float32:
            return f32val(whi)
        import jax as _jax

        if _jax.default_backend() == "cpu":
            w = (whi.astype(jnp.uint64) << 32) | wlo.astype(jnp.uint64)
            s64 = jnp.uint64(1 << 63)
            bits = jnp.where(w & s64 != 0, w ^ s64, ~w)
            # no 64-bit bitcast under the x64 rewriter: reassemble via
            # the 32-bit halves
            blo = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
            bhi = (bits >> 32).astype(jnp.uint32)
            return _bits64_to_f64(bhi, blo)
        # TPU dialect: the f64 word is the (hi=f32(x), lo=x-hi) pair
        hi = f32val(whi)
        lo = f32val(wlo)
        return hi.astype(jnp.float64) + lo.astype(jnp.float64)
    if dtype == jnp.bool_:
        return whi.astype(jnp.bool_)
    nbits = dtype.itemsize * 8
    if nbits <= 32:
        u = whi ^ jnp.uint32(1 << 31)
        return lax.bitcast_convert_type(u, jnp.int32).astype(dtype)
    w = (whi.astype(jnp.uint64) << 32) | wlo.astype(jnp.uint64)
    u = w ^ jnp.uint64(1 << 63)
    return u.astype(dtype)


def _bits64_to_f64(bhi: jax.Array, blo: jax.Array) -> jax.Array:
    """f64 from raw bit halves via a (n, 2) u32 bitcast (little-endian),
    which the CPU backend supports."""
    from jax import lax

    both = jnp.stack([blo, bhi], axis=-1)
    return lax.bitcast_convert_type(both, jnp.float64)


def pallas_bucket_min_max(
    seg: jax.Array, B: int, op: str, cols: Sequence[jax.Array]
) -> List[jax.Array]:
    """PALLAS lowering of :func:`bucket_reduce.bucket_min_max`: same
    contract (identity-prefilled columns, callers overwrite empty
    buckets via their count mask), per-bucket winners via the
    lexicographic word kernel instead of a segment scatter."""
    out: List[jax.Array] = []
    no = jnp.zeros(seg.shape[0], jnp.bool_)
    for d in cols:
        hi, lo = _order_words(d, no, op)
        whi, wlo = pallas_bucket_winner(seg, B, op, hi, lo)
        out.append(_decode_word(whi, wlo, d.dtype))
    return out


def pallas_bucket_position(
    seg: jax.Array, B: int, op: str, consider: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """(row, found) per bucket: the first ('min') or last ('max')
    considered row — the scatter-free first/last + representative-row
    primitive. Row indices ride +1 so the max identity 0 stays
    distinct."""
    cap = seg.shape[0]
    idx = jnp.arange(cap, dtype=jnp.uint32) + 1
    ident = jnp.uint32(_U32_MAX if op == "min" else 0)
    hi = jnp.where(consider, idx, ident)
    whi, _ = pallas_bucket_winner(seg, B, op, hi)
    found = whi != ident
    row = jnp.where(found, whi.astype(jnp.int32) - 1, -1)
    return row, found
