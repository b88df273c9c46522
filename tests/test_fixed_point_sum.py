"""The MATMUL lowering's non-approximate float sum: addends cut to exact
fixed-point limbs of the one limb matmul (``ops/bucket_reduce.
_fixed_point_limbs``), totals rebuilt from integer limb sums. All under
``FORCE_MATMUL`` on the CPU backend, against float64 references."""
import math

import numpy as np
import pytest

import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.eval import ColV
from spark_rapids_tpu.ops import bucket_reduce as BR
from spark_rapids_tpu.ops import groupby as G

B = 16


@pytest.fixture
def matmul(monkeypatch):
    monkeypatch.setattr(BR, "FORCE_MATMUL", True)


def _fixed(seg, x, valid, buckets=B):
    """(sums, whether the detour ran) of one fixed-point column."""
    out = BR.bucket_reduce(
        jnp.asarray(seg), buckets, [], [jnp.asarray(valid)], [],
        fixed_cols=[(jnp.asarray(x), jnp.asarray(valid))])
    (sums,), detoured = out[3]
    return np.asarray(sums), bool(detoured)


def _reference(seg, x, valid, buckets=B):
    """The correctly rounded float64 sum of every bucket, and the sum of
    its addends' magnitudes (what a float sum's error is measured by)."""
    want, scale = np.zeros(buckets), np.zeros(buckets)
    for b in range(buckets):
        rows = x[(seg == b) & valid]
        want[b], scale[b] = math.fsum(rows), math.fsum(np.abs(rows))
    return want, scale


def _rows(n, seed):
    rng = np.random.default_rng(seed)
    seg = rng.integers(0, B + 3, n).astype(np.int32)  # some rows dropped
    return rng, seg, rng.random(n) > 0.1


# ---------------------------------------------------------------------------
# the cut: 32-bit words of a mantissa on the grid
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("sh", [40, 33, 32, 31, 18, 9, 8, 1, 0, -1, -23,
                                -24, -40])
def test_place_is_the_shifted_mantissa_cut_to_64_bits(sh):
    m = np.array([0xFFFFFF, 0x800000, 0xABCDEF, 1, 0], np.uint32)
    high, low = BR._place(jnp.asarray(m), jnp.full(m.shape, sh, jnp.int32))
    got = [(int(h) << 32) | int(w) for h, w in zip(np.asarray(high),
                                                   np.asarray(low))]
    want = [(int(v) << sh if sh >= 0 else int(v) >> -sh) & (2**64 - 1)
            for v in m]
    assert got == want


def test_the_window_states_its_bound():
    """An addend ``FIXED_SPAN`` binades under the largest keeps 2^-40 of
    its own size; one binade further it would not, and detours."""
    assert BR.FIXED_WINDOW_BITS == 64 and BR.FIXED_SPAN == 22
    x = np.array([1.0 + 2.0**-23 + 2.0**-47,
                  (1.0 + 2.0**-23 + 2.0**-47) * 2.0**-22,
                  (1.0 + 2.0**-23 + 2.0**-47) * 2.0**-23])
    limbs, detour, top, _ = BR._fixed_point_limbs(
        jnp.asarray(x), jnp.ones(3, bool))
    assert int(top) == 127 and list(np.asarray(detour)) == [
        False, False, True]
    q = sum(np.asarray(limb).astype(np.float64) * 2.0**(8 * i - 63)
            for i, limb in enumerate(limbs))
    assert q[0] == x[0] and q[2] == 0.0
    assert abs(q[1] - x[1]) <= 2.0**-40 * x[1]


# ---------------------------------------------------------------------------
# against float64, with and without a tail block
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n", [1000, 2 * BR.BLOCK_R, BR.BLOCK_R + 777])
@pytest.mark.parametrize("signed", [False, True])
def test_sum_matches_float64(matmul, n, signed):
    rng, seg, valid = _rows(n, n + signed)
    x = rng.uniform(-100.0 if signed else 1.0, 100.0, n)
    got, detoured = _fixed(seg, x, valid)
    want, scale = _reference(seg, x, valid)
    assert not detoured
    assert np.max(np.abs(got - want) / scale) < 1e-12
    if not signed:
        np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)


def test_two_decimal_prices_sum_to_the_last_place(matmul):
    """The cell's own addends: prices of 1.00 to 100.00 need 55 of the
    window's 64 bits, so no addend is cut on a backend whose f64 is the
    chip's two words; here each is rounded to 48 bits first."""
    n = BR.BLOCK_R + 4321
    rng, seg, valid = _rows(n, 7)
    x = np.round(rng.uniform(1.0, 100.0, n), 2)
    got, detoured = _fixed(seg, x, valid)
    want, _ = _reference(seg, x, valid)
    assert not detoured
    np.testing.assert_allclose(got, want, rtol=2.0**-46, atol=0.0)


def test_negative_zero_and_cancellation(matmul):
    seg = np.array([0, 0, 1, 1, 2, 2, 3], np.int32)
    x = np.array([-0.0, -0.0, 5.25, -5.25, -3.5, -4.0, 2.0**-10])
    got, detoured = _fixed(seg, x, np.ones(7, bool))
    assert not detoured
    assert list(got[:4]) == [0.0, 0.0, -7.5, 2.0**-10]
    assert not np.signbit(got[0])  # a sum starts from +0.0, as a scatter's


def test_null_rows_and_an_empty_bucket_add_nothing(matmul):
    seg = np.array([0, 0, 1, 1, 5], np.int32)
    x = np.array([1.5, np.nan, 7.0, np.inf, 2.0])  # the nulls hold junk
    valid = np.array([True, False, False, False, True])
    got, detoured = _fixed(seg, x, valid)
    assert not detoured  # a null's NaN never enters
    assert got[0] == 1.5 and got[1] == 0.0 and got[5] == 2.0
    assert not got[2:5].any()


def test_an_all_dead_chunk(matmul):
    n = 4096
    got, detoured = _fixed(np.full(n, B, np.int32), np.full(n, 3.25),
                           np.zeros(n, bool))
    assert not detoured and not got.any()


# ---------------------------------------------------------------------------
# the detour
# ---------------------------------------------------------------------------
def test_non_finite_values_reach_their_own_bucket_and_no_other(matmul):
    n = 5000
    rng, seg, _ = _rows(n, 11)
    x = rng.uniform(1.0, 2.0, n)
    seg[:4] = [0, 1, 2, 3]
    seg[4:] = np.where(seg[4:] < 4, seg[4:] + 4, seg[4:])
    x[:4] = [np.nan, np.inf, -np.inf, 1e300]  # past f32's range as well
    valid = np.ones(n, bool)
    got, detoured = _fixed(seg, x, valid)
    want, _ = _reference(seg, x, valid)
    assert detoured
    assert np.isnan(got[0]) and got[1] == np.inf and got[2] == -np.inf
    assert got[3] == 1e300
    np.testing.assert_allclose(got[4:], want[4:], rtol=1e-12, atol=0.0)


def test_mixed_magnitudes_engage_the_detour_and_still_match(matmul):
    n = 6000
    rng, seg, valid = _rows(n, 13)
    x = rng.uniform(1.0, 2.0, n)
    x[::7] *= 1e20
    x[1::7] *= 1e-20
    got, detoured = _fixed(seg, x, valid)
    want, _ = _reference(seg, x, valid)
    assert detoured
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=0.0)


def test_what_f32_cannot_hold_detours(matmul):
    """Below f32's normal range the two words say nothing of the addend:
    a denormal f32 and a value under f32's smallest both detour."""
    seg = np.array([0, 1, 2, 2], np.int32)
    x = np.array([1e-40, 1e-300, 1e-30, 1e-30])
    got, detoured = _fixed(seg, x, np.ones(4, bool))
    assert detoured and list(got[:3]) == [1e-40, 1e-300, 2e-30]


# ---------------------------------------------------------------------------
# what a scatter never had: the order and the split do not matter
# ---------------------------------------------------------------------------
def _grid_values(rng, n):
    """Addends no window cuts and whose every partial sum float64 holds:
    multiples of 2^-10 under 2^10, either sign."""
    return rng.integers(-(1 << 20), 1 << 20, n).astype(np.float64) / 1024.0


def test_a_permutation_of_the_rows_gives_the_same_bits(matmul):
    n = BR.BLOCK_R + 999
    rng, seg, valid = _rows(n, 17)
    x = rng.uniform(-1e3, 1e3, n)  # cut or not: the totals are integers
    got, _ = _fixed(seg, x, valid)
    perm = rng.permutation(n)
    again, _ = _fixed(seg[perm], x[perm], valid[perm])
    assert got.tobytes() == again.tobytes()


@pytest.mark.parametrize("pieces", [2, 3, 8])
def test_a_different_split_into_chunks_gives_the_same_bits(matmul, pieces):
    n = 3 * 8 * 1024
    rng, seg, valid = _rows(n, 19)
    x = _grid_values(rng, n)
    whole, _ = _fixed(seg, x, valid)
    want, _ = _reference(seg, x, valid)
    assert whole.tobytes() == want.tobytes()
    parts = [_fixed(s, v, m)[0] for s, v, m in zip(
        np.split(seg, pieces), np.split(x, pieces), np.split(valid, pieces))]
    # the partials merged as the mesh aggregate's FINAL half merges them
    merged, _ = _fixed(np.tile(np.arange(B, dtype=np.int32), pieces),
                       np.concatenate(parts), np.ones(B * pieces, bool))
    assert merged.tobytes() == whole.tobytes()


# ---------------------------------------------------------------------------
# the plan: who takes the fixed-point form
# ---------------------------------------------------------------------------
def _groupby(n, approx, report, strategy=None, rows=None, seed=23):
    rng = np.random.default_rng(seed)
    key = rng.integers(1, 11, n).astype(np.int32)
    x = np.round(rng.uniform(1.0, 100.0, n), 2)
    xv = rng.random(n) > 0.05
    q = rng.integers(1, 100, n).astype(np.int32)
    live = n if rows is None else rows
    keys, aggs, ngroups = G.groupby_agg(
        [ColV(jnp.asarray(key), jnp.ones(n, bool))], [T.INT],
        [ColV(jnp.asarray(x), jnp.asarray(xv)),
         ColV(jnp.asarray(q), jnp.ones(n, bool)), None],
        ["sum", "sum", "count_star"], live, (), approx_float_sum=approx,
        strategy=strategy, report=report)
    ng = int(ngroups)
    got = {int(k): (float(s), int(i), int(c)) for k, s, i, c in zip(
        np.asarray(keys[0].data)[:ng], np.asarray(aggs[0].data)[:ng],
        np.asarray(aggs[1].data)[:ng], np.asarray(aggs[2].data)[:ng])}
    want = {}
    for k in np.unique(key[:live]):
        rows_k = (key == k) & (np.arange(n) < live)
        want[int(k)] = (math.fsum(x[rows_k & xv]), int(q[rows_k].sum()),
                        int(rows_k.sum()))
    return got, want


def test_an_exact_float_sum_is_planned_fixed_under_matmul(matmul):
    # the first forced-MATMUL groupby of the file: its seconds are the limb
    # program's one compile, which the size (3000 rows) does not move
    report = {}
    got, want = _groupby(3000, False, report)
    assert report["lowering"] == ("fsum_fixed", "isum", "count")
    assert report["float_sums_fixed"] == 1 and report["row_scatters"] == 0
    assert not bool(report["float_detour"])
    assert got.keys() == want.keys()
    for k in want:
        assert got[k][1:] == want[k][1:]
        assert abs(got[k][0] - want[k][0]) <= 1e-12 * want[k][0]


def test_an_approximate_float_sum_keeps_its_hi_lo_limbs(matmul):
    report = {}
    got, want = _groupby(3000, True, report)
    assert report["lowering"] == ("fsum", "isum", "count")
    assert report["float_sums_fixed"] == 0 and report["row_scatters"] == 0
    for k in want:
        assert abs(got[k][0] - want[k][0]) <= 1e-6 * want[k][0]


@pytest.mark.parametrize("strategy", [None, "SCATTER"])
def test_the_scatter_lowering_keeps_its_segment_sum(strategy):
    """Not under FORCE_MATMUL: the CPU backend's default, and SCATTER
    when the chooser names it."""
    report = {}
    got, want = _groupby(3000, False, report, strategy=strategy)
    assert report["lowering"] == ("fsum_exact", "isum", "count")
    assert report["float_sums_fixed"] == 0
    # the ints' scatter, the counts' scatter and the float sum's own
    assert report["row_scatters"] == 3
    for k in want:
        assert got[k][1:] == want[k][1:]
        assert abs(got[k][0] - want[k][0]) <= 1e-13 * want[k][0]


def test_min_max_families_count_as_row_scatters(matmul):
    n = 512
    rng = np.random.default_rng(29)
    key = jnp.asarray(rng.integers(0, 5, n).astype(np.int32))
    x = ColV(jnp.asarray(rng.uniform(0, 1, n)), jnp.ones(n, bool))
    report = {}
    G.groupby_agg([ColV(key, jnp.ones(n, bool))], [T.INT], [x, x, x],
                  ["sum", "min", "max"], n, (), report=report)
    assert report["lowering"] == ("fsum_fixed", "minmax", "minmax")
    assert report["row_scatters"] == 2 and report["float_sums_fixed"] == 1


def test_a_live_mask_with_no_live_row(matmul):
    """The mesh aggregate's all-padding chunk: nothing in, no group out."""
    report = {}
    got, want = _groupby(2048, False, report, rows=0)
    assert got == want == {}
    assert not bool(report["float_detour"])


def test_fixed_columns_are_the_matmul_lowerings():
    seg = jnp.zeros(8, jnp.int32)
    col = (jnp.ones(8), jnp.ones(8, bool))
    with pytest.raises(AssertionError, match="MATMUL"):
        BR.bucket_reduce(seg, 4, [], [], [], strategy="SCATTER",
                         fixed_cols=[col])
    # without one, the lowering answers with an empty fourth part
    out = BR.bucket_reduce(seg, 4, [], [col[1]], [col], strategy="SCATTER")
    assert out[3][0] == [] and not bool(out[3][1])


def test_per_column_baseline_agrees(matmul, monkeypatch):
    n = 3000
    rng, seg, valid = _rows(n, 31)
    cols = [(jnp.asarray(_grid_values(rng, n)), jnp.asarray(valid))
            for _ in range(2)]
    fused = BR.bucket_reduce(jnp.asarray(seg), B, [], [], [],
                             fixed_cols=cols)
    monkeypatch.setattr(BR, "FORCE_PER_COLUMN", True)
    apart = BR.bucket_reduce(jnp.asarray(seg), B, [], [], [],
                             fixed_cols=cols)
    for a, b in zip(fused[3][0], apart[3][0]):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    assert not bool(fused[3][1]) and not bool(apart[3][1])
