"""Differential + tiling tests for the RADIX and PALLAS aggregation
lowerings (round 12: kill the 25x byte amplification).

Coverage, per the issue checklist:
  * the four-strategy differential matrix — MATMUL / SCATTER / RADIX
    (+ PALLAS via interpret mode off-TPU) — over the torture set:
    int64 wraparound, all-null columns, the float hi/lo + NORMAL/BIG
    stream splits (incl. inf/NaN/huge magnitudes), dead and negative
    segment ids;
  * radix tiling edge cases: empty batches, multi-tile + flush-tile
    paths on non-divisor tile sizes (FORCE_TILE_ROWS), and the hash-tier
    overflow escalation (cardinality past the first tier) retrying into
    the scatter-free fallback;
  * the recompile guard: forced RADIX/PALLAS plans compile ONCE across
    batches and a rerun compiles nothing (AUTO's guard lives in
    tests/test_metrics.py);
  * the Pallas hash-join probe kernel vs the binary-search baseline, at
    ops level and through the conf-gated exec path.

Integer sums and counts must be BIT-identical across every lowering
(limb/prefix accumulation wraps mod 2^64 like native adds). Float sums
are order-insensitive decompositions under MATMUL/PALLAS (f32 hi/lo)
and RADIX (f64 NORMAL/BIG streams): MATMUL/PALLAS compare at the
approx-float-agg tolerance, RADIX at f64 rounding tightness.
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64 enable)
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch, schema_of
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import (
    InMemoryScanExec,
    TpuHashAggregateExec,
    TpuProjectExec,
)
from spark_rapids_tpu.exec import base as exec_base
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.eval import ColV
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.ops import groupby as G
from spark_rapids_tpu.ops import radix_bin as RBX
from spark_rapids_tpu.sql import TpuSession

from harness import assert_tpu_and_cpu_equal

STRATEGIES = ("SCATTER", "MATMUL", "RADIX", "PALLAS")
#: strategies whose float sums are exact f64 accumulations (vs the
#: order-insensitive f32 hi/lo decompositions of MATMUL/PALLAS)
_TIGHT_FLOAT = {"SCATTER", "RADIX"}


# ---------------------------------------------------------------------------
# ops-level four-strategy matrix over groupby_agg
# ---------------------------------------------------------------------------
def _groups_of(keys, aggs, nseg):
    """{key tuple -> ((value, valid), ...)} over the live segments, so
    strategies with different output orders (hash-bucket compaction vs
    sorted-key order) compare directly."""
    n = int(nseg)
    kcols = [np.asarray(k.data)[:n] for k in keys]
    out = {}
    for i in range(n):
        key = tuple(c[i] for c in kcols)
        row = []
        for a in aggs:
            valid = bool(np.asarray(a.validity)[i])
            row.append((np.asarray(a.data)[i] if valid else None, valid))
        out[key] = tuple(row)
    return out


def _run_strategy(strategy, key_np, vals, num_rows, ops, dtypes=None):
    keys = [ColV(jnp.asarray(key_np), jnp.ones(key_np.shape[0], jnp.bool_))]
    cols = [None if v is None else ColV(jnp.asarray(v[0]), jnp.asarray(v[1]))
            for v in vals]
    return G.groupby_agg(keys, dtypes or [T.LONG], cols, list(ops),
                         num_rows, strategy=strategy)


def _assert_matrix_agrees(key_np, vals, num_rows, ops, float_ops=()):
    """Run every strategy over one torture input and diff against the
    SCATTER baseline: bit-identical on ints/counts/winner families,
    tolerance-matched on float sums per the strategy's decomposition."""
    base = _groups_of(*_run_strategy("SCATTER", key_np, vals, num_rows, ops))
    for strategy in STRATEGIES[1:]:
        got = _groups_of(*_run_strategy(strategy, key_np, vals, num_rows,
                                        ops))
        assert set(got) == set(base), (strategy, set(got) ^ set(base))
        for k in base:
            for ai, ((bv, bok), (gv, gok)) in enumerate(zip(base[k],
                                                            got[k])):
                assert bok == gok, (strategy, k, ai)
                if not bok:
                    continue
                if ai in float_ops:
                    bf, gf = float(bv), float(gv)
                    if np.isnan(bf) or np.isnan(gf):
                        assert np.isnan(bf) and np.isnan(gf), \
                            (strategy, k, ai, bf, gf)
                    elif strategy in _TIGHT_FLOAT:
                        np.testing.assert_allclose(gf, bf, rtol=1e-12,
                                                   atol=0.0,
                                                   err_msg=str((strategy,
                                                                k, ai)))
                    else:
                        np.testing.assert_allclose(gf, bf, rtol=1e-4,
                                                   atol=1e-6,
                                                   err_msg=str((strategy,
                                                                k, ai)))
                else:
                    assert bv == gv, (strategy, k, ai, bv, gv)


def test_matrix_int64_wraparound_and_counts():
    n, cap = 700, 1024
    rng = np.random.default_rng(5)
    key = np.zeros(cap, np.int64)
    key[:n] = rng.integers(0, 23, n)
    big = np.zeros(cap, np.int64)
    big[:n] = (1 << 62) + rng.integers(0, 1 << 40, n)  # wraps per group
    valid = np.zeros(cap, bool)
    valid[:n] = rng.random(n) > 0.15
    _assert_matrix_agrees(
        key, [(big, valid), (big, valid), None], n,
        ["sum", "count", "count_star"])


def test_matrix_all_null_and_minmax_first_last():
    n, cap = 500, 1024
    rng = np.random.default_rng(6)
    key = np.zeros(cap, np.int64)
    key[:n] = rng.integers(0, 11, n)
    data = np.zeros(cap, np.int64)
    data[:n] = rng.integers(-(2 ** 62), 2 ** 62, n)
    none = np.zeros(cap, bool)
    some = np.zeros(cap, bool)
    some[:n] = rng.random(n) > 0.5
    _assert_matrix_agrees(
        key,
        [(data, none), (data, some), (data, some), (data, some),
         (data, none)],
        n, ["sum", "min", "max", "first", "count"])


def test_matrix_float_streams_inf_nan_huge():
    """The float-sum decompositions (MATMUL/PALLAS f32 hi/lo + overflow
    correction, RADIX NORMAL/BIG/flags) must agree with the plain f64
    scatter sum on normals, huge magnitudes (>2^500), infinities of one
    sign, mixed infinities (-> NaN), and NaN poisoning."""
    # 22 rows in the smallest bucket (256): the seconds are five lowerings'
    # compiles of a float sum (PALLAS interpreted), not the size
    cases = {
        0: [1.5, -2.25, 3e8],                      # plain normals
        1: [1e300, 1e300, -2.5e299],               # BIG stream only
        2: [np.inf, 1.0, 2.0],                     # +inf survives
        3: [-np.inf, -1.0],                        # -inf survives
        4: [np.inf, -np.inf, 5.0],                 # mixed -> NaN
        5: [np.nan, 1.0],                          # NaN poisons
        6: [1e308, 1e308],                         # overflow -> +inf
        7: [2.0 ** 501, -(2.0 ** 501), 7.0],       # BIG cancels to normal
    }
    rows = [(k, v) for k, vs in cases.items() for v in vs]
    n, cap = len(rows), 256
    key = np.zeros(cap, np.int64)
    fval = np.zeros(cap)
    key[:n] = [k for k, _ in rows]
    fval[:n] = [v for _, v in rows]
    valid = np.zeros(cap, bool)
    valid[:n] = True
    _assert_matrix_agrees(key, [(fval, valid), (fval, valid)], n,
                          ["sum", "count"], float_ops={0})


def test_matrix_float_magnitude_disparity_across_groups():
    """One group's 1e30 must not corrupt a NEIGHBOURING group's small
    sum: a tile-wide float prefix difference would cancel group 1's
    1+2+3 to 0.0 against group 0's 1e30 — the RADIX float family
    reduces by a segmented scan that resets at every boundary, so
    cross-group contamination is structurally impossible (regression
    for the round-12 review finding)."""
    cap = 256
    key = np.zeros(cap, np.int64)
    fval = np.zeros(cap)
    rows = [(0, 1e30), (1, 1.0), (1, 2.0), (1, 3.0), (2, -4.5),
            (0, 2.5e30), (3, 1e-20), (3, 2e-20)]
    n = len(rows)
    key[:n] = [k for k, _ in rows]
    fval[:n] = [v for _, v in rows]
    valid = np.zeros(cap, bool)
    valid[:n] = True
    _assert_matrix_agrees(key, [(fval, valid), (fval, valid)], n,
                          ["sum", "count"], float_ops={0})
    # and explicitly against the exact per-group answer
    keys, aggs, nseg = _run_strategy(
        "RADIX", key, [(fval, valid)], n, ["sum"])
    got = {int(np.asarray(keys[0].data)[i]):
           float(np.asarray(aggs[0].data)[i]) for i in range(int(nseg))}
    assert got[1] == 6.0 and got[2] == -4.5, got
    np.testing.assert_allclose(got[0], 3.5e30, rtol=1e-12)
    np.testing.assert_allclose(got[3], 3e-20, rtol=1e-12)


#: the BIG stream's constants as a TPU reads them: the f64 it emulates
#: has f32's exponent range, so 2^500 and 2^600 are inf and 2^-600 is 0
AS_THE_CHIP_READS = {"F64_BIG": float("inf"), "BIG_SCALE_DOWN": 0.0,
                     "BIG_SCALE_UP": float("inf")}
COMBINE_CASES = {
    # (normal, big, flags) -> the sum with f64's constants, and with the
    # chip's where that differs (a BIG stream it could never have filled)
    "nothing_big": ([1.0, -2.5, 0.0, 3.0e30], [0.0] * 4, [0] * 4,
                    [1.0, -2.5, 0.0, 3.0e30], None),
    "a_big_addend": ([1.0, 2.0], [2.0 ** -50, 0.0], [0, 0],
                     [2.0 ** 550 + 1.0, 2.0], [float("inf"), 2.0]),
    "the_flags_outrank_the_sum": (
        [1.0] * 5, [0.0] * 5, [1, 2, 4, 3, 0],
        [float("inf"), float("-inf"), float("nan"), float("nan"), 1.0],
        None),
}


@pytest.mark.parametrize("chip", [False, True], ids=["f64", "as_the_chip"])
@pytest.mark.parametrize("case", sorted(COMBINE_CASES))
def test_combine_float_sum_rescales_only_what_the_big_stream_holds(
        case, chip, monkeypatch):
    """PR 25's NaN: ``normal + big * 2^600`` was ``x + 0 * inf`` on the
    chip, for every group of every RADIX float sum."""
    if chip:
        for name, value in AS_THE_CHIP_READS.items():
            monkeypatch.setattr(RBX, name, value)
    normal, big, flags, want, want_chip = COMBINE_CASES[case]
    got = np.asarray(RBX.combine_float_sum(
        jnp.asarray(normal), jnp.asarray(big),
        jnp.asarray(flags, jnp.uint8)))
    np.testing.assert_array_equal(
        got, np.asarray(want_chip if chip and want_chip else want))


def test_radix_float_sums_are_no_nan_with_the_chips_constants(monkeypatch):
    """The whole RADIX groupby with the constants as the chip reads them
    (the merge of cell 4's partials at capacity 2048: 100 groups)."""
    for name, value in AS_THE_CHIP_READS.items():
        monkeypatch.setattr(RBX, name, value)
    cap = 2048
    rng = np.random.default_rng(1)
    key = rng.integers(1, 101, cap)
    val = np.round(rng.random(cap) * 100, 2)
    ok = np.ones(cap, bool)
    got = _groups_of(*_run_strategy("RADIX", key, [(val, ok)], cap, ["sum"]))
    want = np.bincount(key, weights=val, minlength=101)
    assert sorted(k for (k,) in got) == list(range(1, 101))
    for (k,), ((s, valid),) in got.items():
        assert valid and not np.isnan(s)
        np.testing.assert_allclose(s, want[k], rtol=1e-12)


def test_matrix_dead_rows_never_contribute():
    """Rows past num_rows carry arbitrary garbage (incl. extreme values
    that would win any min/max) and must drop from every lowering."""
    # four fifths of the slots dead is the point; the seconds are five
    # lowerings' compiles of sum/min/max, not the 512 slots
    n, cap = 100, 512
    rng = np.random.default_rng(8)
    key = rng.integers(0, 7, cap)  # garbage keys on dead rows too
    data = rng.integers(-(2 ** 62), 2 ** 62, cap)
    data[n:] = np.int64(-(2 ** 63))  # would win every min
    valid = np.ones(cap, bool)
    _assert_matrix_agrees(
        key, [(data, valid), (data, valid), (data, valid), None], n,
        ["sum", "min", "max", "count_star"])


def test_matrix_empty_batch():
    cap = 256
    key = np.zeros(cap, np.int64)
    data = np.zeros(cap, np.int64)
    valid = np.zeros(cap, bool)
    for strategy in STRATEGIES:
        keys, aggs, nseg = _run_strategy(
            strategy, key, [(data, valid), None], 0, ["sum", "count_star"])
        assert int(nseg) == 0, strategy


def test_matrix_tier_overflow_escalates_scatter_free():
    """Cardinality past the first hash tier (128 buckets) forces the
    tier-escalation retry; under RADIX/PALLAS the escalation (and the
    final sort fallback) must still produce the baseline's groups."""
    # the size is the point: 2048 is the smallest capacity whose chain has
    # all three hash tiers (128, 1024, 2048) above the sort fallback
    n, cap = 1500, 2048
    rng = np.random.default_rng(9)
    key = np.zeros(cap, np.int64)
    key[:n] = rng.integers(0, 600, n)  # > 128: first tier overflows
    data = np.zeros(cap, np.int64)
    data[:n] = rng.integers(-(2 ** 62), 2 ** 62, n)
    valid = np.zeros(cap, bool)
    valid[:n] = rng.random(n) > 0.1
    _assert_matrix_agrees(
        key, [(data, valid), (data, valid), None], n,
        ["sum", "max", "count_star"])


# ---------------------------------------------------------------------------
# radix tiling: multi-tile, flush tile, non-divisor tiles
# ---------------------------------------------------------------------------
@pytest.fixture
def force_tile():
    prev = RBX.FORCE_TILE_ROWS

    def set_tile(t):
        RBX.FORCE_TILE_ROWS = t

    try:
        yield set_tile
    finally:
        RBX.FORCE_TILE_ROWS = prev


@pytest.mark.parametrize("tile", [32, 48, 100])
def test_radix_tiling_multi_tile_and_flush(force_tile, tile):
    """Small forced tiles drive segments ACROSS tile boundaries (the
    open-segment carry) and the final flush trip; 48/100 do not divide
    the capacity, covering the ragged last tile. Results must match the
    untiled scatter baseline exactly."""
    n, cap = 900, 1024
    rng = np.random.default_rng(tile)
    key = np.zeros(cap, np.int64)
    key[:n] = np.sort(rng.integers(0, 9, n))  # few groups: long runs
    data = np.zeros(cap, np.int64)
    data[:n] = rng.integers(-(2 ** 62), 2 ** 62, n)
    fval = np.zeros(cap)
    fval[:n] = rng.normal(size=n) * 1e6
    valid = np.zeros(cap, bool)
    valid[:n] = rng.random(n) > 0.2
    base = _groups_of(*_run_strategy(
        "SCATTER", key,
        [(data, valid), (fval, valid), (data, valid), None], n,
        ["sum", "sum", "min", "count_star"]))
    force_tile(tile)
    got = _groups_of(*_run_strategy(
        "RADIX", key,
        [(data, valid), (fval, valid), (data, valid), None], n,
        ["sum", "sum", "min", "count_star"]))
    assert set(got) == set(base)
    for k in base:
        (bs, _), (bf, bfok), (bm, bmok), (bc, _) = base[k]
        (gs, _), (gf, gfok), (gm, gmok), (gc, _) = got[k]
        assert bs == gs and bc == gc and bmok == gmok
        if bmok:
            assert bm == gm
        if bfok:
            np.testing.assert_allclose(float(gf), float(bf), rtol=1e-12)


def test_radix_single_group_spanning_every_tile(force_tile):
    """One group across ALL tiles: the open-segment carry chains through
    every trip and only the flush tile finally writes it."""
    n, cap = 1000, 1024
    force_tile(64)
    key = np.zeros(cap, np.int64)
    data = np.ones(cap, np.int64)
    valid = np.zeros(cap, bool)
    valid[:n] = True
    keys, aggs, nseg = _run_strategy(
        "RADIX", key, [(data, valid), None], n, ["sum", "count_star"])
    assert int(nseg) == 1
    assert int(np.asarray(aggs[0].data)[0]) == n
    assert int(np.asarray(aggs[1].data)[0]) == n


# ---------------------------------------------------------------------------
# PALLAS bucket kernels vs the scatter baseline (negative/dead ids)
# ---------------------------------------------------------------------------
def test_pallas_bucket_reduce_negative_and_dead_ids():
    from spark_rapids_tpu.ops import bucket_reduce as BR
    from spark_rapids_tpu.ops.pallas_groupby import pallas_bucket_reduce

    n, B = 777, 48
    rng = np.random.default_rng(12)
    seg = rng.integers(-3, B + 4, n).astype(np.int32)  # both tails
    ival = rng.integers(-(2 ** 62), 2 ** 62, n)
    fval = rng.uniform(-1e6, 1e6, n)
    valid = rng.random(n) < 0.8
    args = (jnp.asarray(seg), B,
            [(jnp.asarray(ival), jnp.asarray(valid))],
            [jnp.asarray(valid)],
            [(jnp.asarray(fval), jnp.asarray(valid))])
    base = BR.bucket_reduce(*args, strategy="SCATTER")
    got = pallas_bucket_reduce(jnp.asarray(seg), B,
                               [(jnp.asarray(ival), jnp.asarray(valid))],
                               [jnp.asarray(valid)],
                               [(jnp.asarray(fval), jnp.asarray(valid))])
    np.testing.assert_array_equal(np.asarray(got[0][0]),
                                  np.asarray(base[0][0]))
    np.testing.assert_array_equal(np.asarray(got[1][0]),
                                  np.asarray(base[1][0]))
    np.testing.assert_allclose(np.asarray(got[2][0]),
                               np.asarray(base[2][0]),
                               rtol=1e-4, atol=1e-6)


def test_pallas_bucket_min_max_and_position():
    import jax

    from spark_rapids_tpu.ops.pallas_groupby import (
        pallas_bucket_min_max, pallas_bucket_position)

    n, B = 600, 32
    rng = np.random.default_rng(13)
    seg = jnp.asarray(rng.integers(0, B, n).astype(np.int32))
    consider = jnp.asarray(rng.random(n) < 0.7)
    for dt, fill in ((np.int64, (2 ** 63 - 1, -(2 ** 63))),
                     (np.float64, (np.inf, -np.inf))):
        data = (rng.integers(-(2 ** 62), 2 ** 62, n).astype(dt)
                if dt is np.int64 else
                (rng.normal(size=n) * 1e9).astype(dt))
        for op, ident in zip(("min", "max"), fill):
            masked = jnp.where(consider, jnp.asarray(data),
                               jnp.asarray(dt(ident)))
            fn = jax.ops.segment_min if op == "min" else jax.ops.segment_max
            want = np.asarray(fn(masked, seg, num_segments=B))
            got = np.asarray(pallas_bucket_min_max(
                seg, B, op, [masked])[0])
            have = np.asarray(jax.ops.segment_sum(
                consider.astype(jnp.int32), seg, num_segments=B)) > 0
            np.testing.assert_array_equal(got[have], want[have],
                                          err_msg=f"{dt} {op}")
    # first/last considered row per bucket
    idx = jnp.arange(n, dtype=jnp.int32)
    for op, red in (("min", jax.ops.segment_min),
                    ("max", jax.ops.segment_max)):
        fillv = n + 1 if op == "min" else -1
        want = np.asarray(red(jnp.where(consider, idx, jnp.int32(fillv)),
                              seg, num_segments=B))
        row, found = pallas_bucket_position(seg, B, op, consider)
        have = np.asarray(found)
        np.testing.assert_array_equal(np.asarray(row)[have],
                                      want[have], err_msg=op)


# ---------------------------------------------------------------------------
# exec-level: the conf-selected strategies against the CPU oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", list(STRATEGIES))
def test_exec_strategy_matrix_vs_cpu_oracle(strategy):
    n = 160
    data = {
        "k": [i % 7 if i % 11 else None for i in range(n)],
        "a": [(i * 13) % 400 - 200 for i in range(n)],
        "b": [None if i % 9 == 0 else (i / 7.0 - 10.0) for i in range(n)],
    }
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)

    # the f32 hi/lo decompositions (MATMUL/PALLAS) sit outside the
    # harness's 1e-9 oracle tolerance; their float-sum correctness is
    # pinned by the ops-level matrix at the documented 1e-4 tolerance
    fsum = ([A.agg(A.Sum(col("b")), "sb")]
            if strategy in _TIGHT_FLOAT else [])

    def build(s):
        return (s.create_dataframe(data, schema).group_by("k")
                .agg(A.agg(A.Sum(col("a")), "sa"),
                     *fsum,
                     A.agg(A.Min(col("a")), "mn"),
                     A.agg(A.Max(col("b")), "mx"),
                     A.agg(A.Count(col("b")), "cb"),
                     A.agg(A.Count(None), "cs")))

    assert_tpu_and_cpu_equal(
        build,
        conf={"spark.rapids.tpu.sql.agg.strategy": strategy,
              # float sums need the variableFloatAgg opt-in to replace;
              # the ops-level matrix above pins per-strategy tightness
              "spark.rapids.tpu.sql.variableFloatAgg.enabled": True},
        approx_float=True)


# ---------------------------------------------------------------------------
# recompile guard: forced RADIX / PALLAS compile once, rerun nothing
# ---------------------------------------------------------------------------
def _plan(conf, batches, schema):
    scan = InMemoryScanExec(conf, [batches], schema)
    proj = TpuProjectExec(
        conf, [col("k"), E.Alias(E.Multiply(col("a"), lit(3)), "a3")], scan)
    return TpuHashAggregateExec(
        conf, [col("k")],
        [A.agg(A.Sum(col("a3")), "s"), A.agg(A.Count(None), "c"),
         A.agg(A.Min(col("a3")), "mn")], proj)


@pytest.mark.parametrize("strategy", ["RADIX", "PALLAS"])
def test_forced_strategy_compiles_once(strategy):
    rng = np.random.default_rng(14)
    schema = schema_of(k=T.INT, a=T.LONG)
    nb, n = 3, 330 if strategy == "RADIX" else 350  # distinct cap buckets
    batches = [ColumnarBatch.from_pydict({
        "k": [int(x) for x in rng.integers(0, 6, n)],
        "a": [int(x) for x in rng.integers(-100, 100, n)],
    }, schema) for _ in range(nb)]
    conf = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "ON",
                       "spark.rapids.tpu.sql.agg.strategy": strategy})
    agg = _plan(conf, batches, schema)
    before = exec_base.compile_miss_count()
    rows1 = agg.collect()
    assert exec_base.compile_miss_count() - before == 1, \
        exec_base.COMPILE_COUNTER.by_site
    again = _plan(conf, batches, schema)
    before2 = exec_base.compile_miss_count()
    rows2 = again.collect()
    assert exec_base.compile_miss_count() == before2
    assert sorted(rows1) == sorted(rows2)
    # and the baseline cross-check: same groups as the scatter program
    base = _plan(RapidsConf({
        "spark.rapids.tpu.sql.agg.fusedPlan": "ON",
        "spark.rapids.tpu.sql.agg.strategy": "SCATTER"}), batches, schema)
    assert sorted(base.collect()) == sorted(rows1)


# ---------------------------------------------------------------------------
# Pallas join probe kernel
# ---------------------------------------------------------------------------
def test_pallas_probe_ranges_matches_binary_search():
    from spark_rapids_tpu.ops import join as J

    rng = np.random.default_rng(15)
    nb, m = 300, 517
    build = np.sort(rng.integers(0, 90, nb).astype(np.uint32))
    bcount = 211  # rows past the count are non-joinable padding
    build[bcount:] = np.uint32(0xFFFFFFFF)
    probe = rng.integers(0, 120, m).astype(np.uint32)
    live = rng.random(m) < 0.85
    args = ([jnp.asarray(build)], jnp.int32(bcount),
            [jnp.asarray(probe)], jnp.asarray(live))
    lo0, hi0 = J.probe_ranges(*args, pallas=False)
    lo1, hi1 = J.probe_ranges(*args, pallas=True)
    np.testing.assert_array_equal(np.asarray(hi0 - lo0),
                                  np.asarray(hi1 - lo1))
    has = np.asarray(hi1 - lo1) > 0
    np.testing.assert_array_equal(np.asarray(lo0)[has],
                                  np.asarray(lo1)[has])


@pytest.mark.parametrize("how", ["inner", "left", "semi", "anti"])
def test_exec_join_with_pallas_probe(how):
    ln, rn = 90, 31
    ldata = {"k": [i % 9 if i % 11 else None for i in range(ln)],
             "a": [(i * 7) % 50 - 25 for i in range(ln)]}
    rdata = {"k2": [i % 12 if i % 7 else None for i in range(rn)],
             "b": [i / 3.0 for i in range(rn)]}
    lsch = schema_of(k=T.INT, a=T.LONG)
    rsch = schema_of(k2=T.INT, b=T.DOUBLE)

    def build(s):
        return s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")], how=how)

    assert_tpu_and_cpu_equal(
        build,
        conf={"spark.rapids.tpu.sql.join.pallasProbe.enabled": True},
        approx_float=True)
