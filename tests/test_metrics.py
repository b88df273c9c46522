"""Profiler + fused aggregate hot-path tests.

Covers the round-6 tentpole:
  * differential tests diffing the FUSED multi-column bucket reduce
    against the per-column baseline (FORCE_PER_COLUMN) on BOTH lowerings
    (scatter on CPU, FORCE_MATMUL for the MXU limb path) — int64
    wraparound, all-null columns, the float hi/lo split, and mixed
    sum/count/min/max plans;
  * device-sync timing + bytes-touched accounting via
    TpuSession.explain_metrics() for aggregate and project execs;
  * the recompile-regression guard: a multi-batch fused aggregate plan
    compiles ONCE (compile cache-miss counter == expected) and re-running
    the same plan shape compiles nothing.
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
    TpuFilterExec,
    TpuHashAggregateExec,
    TpuProjectExec,
)
from spark_rapids_tpu.exec import base as exec_base
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.ops import bucket_reduce as BR
from spark_rapids_tpu.sql import TpuSession


# ---------------------------------------------------------------------------
# fused vs per-column bucket reduce (both lowerings)
# ---------------------------------------------------------------------------
@pytest.fixture(params=["scatter", "matmul"])
def lowering(request):
    """Run the differential against both lowerings: the CPU scatter
    family and the forced MXU limb-matmul path."""
    prev = BR.FORCE_MATMUL
    BR.FORCE_MATMUL = request.param == "matmul"
    try:
        yield request.param
    finally:
        BR.FORCE_MATMUL = prev


def _diff_bucket_reduce(seg, B, int_cols, count_cols, float_cols,
                        strategy=None):
    fused = BR.bucket_reduce(seg, B, int_cols, count_cols, float_cols,
                             strategy=strategy)
    prev = BR.FORCE_PER_COLUMN
    BR.FORCE_PER_COLUMN = True
    try:
        percol = BR.bucket_reduce(seg, B, int_cols, count_cols, float_cols,
                                  strategy=strategy)
    finally:
        BR.FORCE_PER_COLUMN = prev
    for fi, pi in zip(fused[0], percol[0]):
        np.testing.assert_array_equal(np.asarray(fi), np.asarray(pi))
    for fc, pc in zip(fused[1], percol[1]):
        np.testing.assert_array_equal(np.asarray(fc), np.asarray(pc))
    for ff, pf in zip(fused[2], percol[2]):
        np.testing.assert_allclose(
            np.asarray(ff), np.asarray(pf), rtol=1e-12, atol=0.0)
    return fused


def test_fused_reduce_int64_wraparound(lowering):
    """Java-wraparound int64 sums must survive the multi-column fusion
    bit-exactly (limb accumulation wraps mod 2^64 like native adds)."""
    n = 512
    rng = np.random.default_rng(3)
    seg = jnp.asarray((rng.integers(0, 7, n)).astype(np.int32))
    big = np.full(n, (1 << 62) + 12345, np.int64)
    mixed = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    valid = jnp.ones(n, jnp.bool_)
    out = _diff_bucket_reduce(
        seg, 8,
        [(jnp.asarray(big), valid), (jnp.asarray(mixed), valid)],
        [valid], [])
    # cross-check column 0 against numpy's wrapping sum per bucket
    segs = np.asarray(seg)
    for b in range(7):
        want = np.int64(0)
        with np.errstate(over="ignore"):
            for v in big[segs == b]:
                want = np.int64(want + v)  # wraps
        assert int(np.asarray(out[0][0])[b]) == int(want)


def test_fused_reduce_all_null_columns(lowering):
    n = 256
    seg = jnp.asarray(np.arange(n, dtype=np.int32) % 5)
    none_valid = jnp.zeros(n, jnp.bool_)
    some_valid = jnp.asarray(np.arange(n) % 3 == 0)
    data_i = jnp.asarray(np.arange(n, dtype=np.int64) * 7 - 100)
    data_f = jnp.asarray(np.linspace(-4.0, 9.0, n))
    out = _diff_bucket_reduce(
        seg, 8,
        [(data_i, none_valid), (data_i, some_valid)],
        [none_valid, some_valid],
        [(data_f, none_valid), (data_f, some_valid)])
    assert np.all(np.asarray(out[0][0]) == 0)  # all-null sums to 0
    assert np.all(np.asarray(out[1][0]) == 0)  # all-null counts to 0
    assert np.all(np.asarray(out[2][0]) == 0.0)


def test_fused_reduce_float_hilo_split(lowering):
    """Doubles whose mantissa exceeds f32 need the hi/lo split; values
    beyond f32 range take the overflow correction. Both must be identical
    fused vs per-column."""
    n = 384
    rng = np.random.default_rng(11)
    seg = jnp.asarray((rng.integers(0, 4, n)).astype(np.int32))
    precise = rng.normal(size=n) * 1e9 + rng.normal(size=n) * 1e-9
    huge = np.where(np.arange(n) % 97 == 0, 1e300, precise)
    valid = jnp.asarray(rng.random(n) < 0.9)
    _diff_bucket_reduce(
        seg, 4, [], [],
        [(jnp.asarray(precise), valid), (jnp.asarray(huge), valid)])


def test_fused_minmax_family_matches_per_column(lowering):
    n = 300
    rng = np.random.default_rng(23)
    seg = jnp.asarray((rng.integers(0, 6, n)).astype(np.int32))
    cols = [jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int64))
            for _ in range(3)]
    for op in ("min", "max"):
        fused = BR.bucket_min_max(seg, 6, op, cols)
        prev = BR.FORCE_PER_COLUMN
        BR.FORCE_PER_COLUMN = True
        try:
            percol = BR.bucket_min_max(seg, 6, op, cols)
        finally:
            BR.FORCE_PER_COLUMN = prev
        for f, p in zip(fused, percol):
            np.testing.assert_array_equal(np.asarray(f), np.asarray(p))


def _mixed_plan_exec(conf, batches, schema):
    scan = InMemoryScanExec(conf, [batches], schema)
    filt = TpuFilterExec(conf, E.GreaterThanOrEqual(col("a"), lit(-80)), scan)
    proj = TpuProjectExec(
        conf, [col("k"), E.Alias(E.Multiply(col("a"), lit(3)), "a3"),
               col("b")], filt)
    return TpuHashAggregateExec(
        conf, [col("k")],
        [A.agg(A.Sum(col("a3")), "s"), A.agg(A.Count(col("b")), "c"),
         A.agg(A.Min(col("a3")), "mn"), A.agg(A.Max(col("a3")), "mx"),
         A.agg(A.Min(col("b")), "fmn"), A.agg(A.Max(col("b")), "fmx"),
         A.agg(A.Count(None), "cs")], proj)


def _mk_batches(schema, nb=3, n=50):
    rng = np.random.default_rng(7)
    out = []
    for i in range(nb):
        out.append(ColumnarBatch.from_pydict({
            "k": [int(x) for x in rng.integers(0, 6, n)],
            "a": [int(x) for x in rng.integers(-100, 100, n)],
            "b": [None if rng.random() < 0.15 else float(rng.normal())
                  for _ in range(n)],
        }, schema))
    return out


def _cmp_rows(lhs, rhs):
    assert len(lhs) == len(rhs)
    for a, b in zip(sorted(lhs), sorted(rhs)):
        assert len(a) == len(b)
        for x, y in zip(a, b):
            if isinstance(x, float) and x == x and y == y:
                assert abs(x - y) < 1e-9, (a, b)
            else:
                assert x == y or (x != x and y != y), (a, b)


def test_mixed_plan_fused_vs_per_column(lowering):
    """Exec-level differential for a mixed sum/count/min/max plan: the
    fused multi-column kernel vs the per-column baseline, same results on
    both lowerings (and fused single-program plan vs per-batch paths)."""
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)
    batches = _mk_batches(schema)
    on = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "ON"})
    off = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "OFF"})
    fused_rows = _mixed_plan_exec(on, batches, schema).collect()
    prev = BR.FORCE_PER_COLUMN
    BR.FORCE_PER_COLUMN = True
    try:
        percol_rows = _mixed_plan_exec(off, batches, schema).collect()
    finally:
        BR.FORCE_PER_COLUMN = prev
    _cmp_rows(fused_rows, percol_rows)


def test_dead_and_out_of_range_rows_drop_out(lowering):
    """Out-of-range segment ids — padding rows at id B, dead rows past it,
    and NEGATIVE ids — must drop out of every reduction under both
    lowerings."""
    n = 257  # off the block/tile sizes on purpose
    rng = np.random.default_rng(31)
    seg_np = rng.integers(-3, 12, n).astype(np.int32)  # B=8: both tails
    seg = jnp.asarray(seg_np)
    data = rng.integers(-(2**62), 2**62, n).astype(np.int64)
    valid = jnp.asarray(rng.random(n) < 0.7)
    out = _diff_bucket_reduce(
        seg, 8, [(jnp.asarray(data), valid)], [valid], [])
    v = np.asarray(valid)
    for b in range(8):
        m = (seg_np == b) & v
        want = np.int64(0)
        with np.errstate(over="ignore"):
            for x in data[m]:
                want = np.int64(want + x)
        assert int(np.asarray(out[0][0])[b]) == int(want)
        assert int(np.asarray(out[1][0])[b]) == int(m.sum())


def test_both_lowerings_bit_identical_int_sums():
    """Acceptance pin: MATMUL and SCATTER produce BIT-identical integer
    sums and counts over the same inputs (incl. wraparound)."""
    n = 600
    rng = np.random.default_rng(43)
    seg = jnp.asarray(rng.integers(0, 16, n).astype(np.int32))
    cols = [(jnp.asarray(rng.integers(-(2**62), 2**62, n).astype(np.int64)),
             jnp.asarray(rng.random(n) < 0.8)) for _ in range(3)]
    cnts = [v for _, v in cols]
    outs = {}
    outs["SCATTER"] = BR.bucket_reduce(seg, 16, cols, cnts, [],
                                       strategy="SCATTER")
    prev = BR.FORCE_MATMUL
    BR.FORCE_MATMUL = True
    try:
        outs["MATMUL"] = BR.bucket_reduce(seg, 16, cols, cnts, [])
    finally:
        BR.FORCE_MATMUL = prev
    for i in range(3):
        np.testing.assert_array_equal(
            np.asarray(outs["SCATTER"][0][i]),
            np.asarray(outs["MATMUL"][0][i]))
        np.testing.assert_array_equal(
            np.asarray(outs["SCATTER"][1][i]),
            np.asarray(outs["MATMUL"][1][i]))


# ---------------------------------------------------------------------------
# strategy chooser: conf plumbing, visibility, cost-model branches
# ---------------------------------------------------------------------------
def test_strategy_chooser_forced_and_auto_branches():
    from spark_rapids_tpu.exec.aggregate import choose_agg_strategy

    ops = ("sum", "count", "count_star")
    exprs = (E.BoundReference(1, T.LONG, True),
             E.BoundReference(1, T.LONG, True), None)
    forced = RapidsConf({"spark.rapids.tpu.sql.agg.strategy": "MATMUL"})
    s, why = choose_agg_strategy(forced, 1 << 20, ops, exprs)
    assert s == "MATMUL" and "forced" in why
    # a lowering that went with the cost model is no value of the conf
    with pytest.raises(ValueError, match="not in allowed values"):
        RapidsConf({"spark.rapids.tpu.sql.agg.strategy": "SORT"})
    auto = RapidsConf({})
    s, why = choose_agg_strategy(auto, 1 << 20, ops, exprs, backend="cpu")
    assert s == "SCATTER" and "CPU backend" in why
    # on an accelerator AUTO resolves MATMUL whatever the shape: the one
    # lowering the v5e compiler takes at every capacity (RADIX is refused
    # at small ones and sums floats to NaN where it compiles)
    wide_ops = tuple(["sum"] * 40)
    wide_exprs = tuple(E.BoundReference(i, T.LONG, True) for i in range(40))
    fwide_exprs = tuple(E.BoundReference(i, T.DOUBLE, True)
                        for i in range(40))
    for cap in (1 << 7, 1 << 11, 1 << 24):
        for shape in ((wide_ops, wide_exprs), (wide_ops, fwide_exprs),
                      (("count_star",), (None,))):
            s_tpu, why_tpu = choose_agg_strategy(
                auto, cap, *shape, backend="tpu")
            assert s_tpu == "MATMUL" and "tpu backend" in why_tpu
    # CPU AUTO flips to RADIX at the byte-amplification capacity
    # threshold (the merge gate is XLA bytes, not shared-box wall clock)
    s_big, why_big = choose_agg_strategy(
        auto, 1 << 24, ops, exprs, backend="cpu")
    assert s_big == "RADIX" and "amplif" in why_big
    # ... unless a float sum has to stay exact (variableFloatAgg off):
    # RADIX's stream split is order-insensitive
    s_exact, _ = choose_agg_strategy(
        auto, 1 << 24, wide_ops, fwide_exprs, backend="cpu")
    assert s_exact == "SCATTER"
    s_approx, _ = choose_agg_strategy(
        RapidsConf(
            {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}),
        1 << 24, wide_ops, fwide_exprs, backend="cpu")
    assert s_approx == "RADIX"


def test_strategy_visible_in_events_and_explain_metrics():
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": True,
                       "spark.rapids.tpu.sql.agg.strategy": "RADIX"})
    n = 64
    data = {"k": [i % 4 for i in range(n)], "v": list(range(n))}
    schema = schema_of(k=T.INT, v=T.LONG)
    rows = sess.create_dataframe(data, schema).group_by("k").agg(
        A.agg(A.Sum(col("v")), "s")).collect()
    assert sorted(rows) == sorted(
        (k, sum(v for i, v in enumerate(range(n)) if i % 4 == k))
        for k in range(4))
    evs = [r for r in sess.events.records()
           if r["event"] == "agg_strategy"]
    assert evs and evs[0]["strategy"] == "RADIX"
    assert "forced" in evs[0]["reason"]
    assert "strategy=RADIX" in sess.explain_metrics()
    # the analyzer's forecast note names the same strategy (explain)
    df = sess.create_dataframe(data, schema).group_by("k").agg(
        A.agg(A.Sum(col("v")), "s"))
    assert "agg strategy: RADIX" in df.explain()
    sess.close()


def test_auto_strategy_resolution_does_not_double_compile():
    """Recompile guard for the chooser: AUTO resolves to ONE fixed
    strategy per plan shape, so the fused aggregate still compiles
    exactly once across batches and a rerun compiles nothing — the
    strategy is memoized per capacity, part of the cache key, and never
    data-dependent."""
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)
    # a capacity bucket (256) no other test's plan uses: the guard below
    # must observe ITS OWN compile, not another test's warm cache
    batches = _mk_batches(schema, nb=4, n=200)
    conf = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "ON",
                       "spark.rapids.tpu.sql.agg.strategy": "AUTO"})
    agg = _mixed_plan_exec(conf, batches, schema)
    before = exec_base.compile_miss_count()
    rows1 = agg.collect()
    assert exec_base.compile_miss_count() - before == 1
    again = _mixed_plan_exec(conf, batches, schema)
    before2 = exec_base.compile_miss_count()
    rows2 = again.collect()
    assert exec_base.compile_miss_count() == before2
    _cmp_rows(rows1, rows2)
    # and a RADIX-forced plan is a DIFFERENT program (one fresh compile),
    # not a silent reuse of the scatter executable
    forced = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "ON",
                         "spark.rapids.tpu.sql.agg.strategy": "RADIX"})
    sorted_agg = _mixed_plan_exec(forced, batches, schema)
    before3 = exec_base.compile_miss_count()
    rows3 = sorted_agg.collect()
    assert exec_base.compile_miss_count() - before3 == 1
    _cmp_rows(rows1, rows3)


# ---------------------------------------------------------------------------
# explain_metrics: device-sync timing + bytes accounting
# ---------------------------------------------------------------------------
def _find_exec(plan, cls):
    if isinstance(plan, cls):
        return plan
    for c in getattr(plan, "children", ()):
        r = _find_exec(c, cls)
        if r is not None:
            return r
    return None


def test_explain_metrics_device_sync_and_bytes():
    sess = TpuSession({
        "spark.rapids.tpu.metrics.deviceSync.enabled": True,
    })
    n = 64
    data = {"k": [i % 4 for i in range(n)], "v": list(range(n))}
    schema = schema_of(k=T.INT, v=T.LONG)

    # a project-topped plan: the project exec runs (and records) itself
    sess.create_dataframe(data, schema).select(
        col("k"), E.Alias(E.Multiply(col("v"), lit(2)), "v2")).collect()
    proj = _find_exec(sess.last_executed_plan.tpu_child, TpuProjectExec)
    assert proj is not None

    # an aggregate-topped plan (a project below would fuse INTO the agg
    # program and record nothing of its own — by design)
    sess.create_dataframe(data, schema).group_by("k").agg(
        A.agg(A.Sum(col("v")), "s")).collect()
    agg = _find_exec(sess.last_executed_plan.tpu_child,
                     TpuHashAggregateExec)
    assert agg is not None

    for node in (agg, proj):
        m = node.metrics
        # device-accurate timing recorded (fence ran and waited)
        assert exec_base.OP_TIME_DEVICE in m, node
        assert m[exec_base.OP_TIME_DEVICE].kind == "ns"
        assert m[exec_base.OP_TIME_DEVICE].value > 0
        assert m[exec_base.BYTES_TOUCHED].value > 0
    # bytes accounting is rows x row-bytes of the OUTPUT batch:
    # project emits n rows of (int32 k + int64 v2) + 2 validity bytes
    assert proj.metrics[exec_base.BYTES_TOUCHED].value == n * (4 + 1 + 8 + 1)
    # aggregate emits 4 groups of (int32 k + int64 s) + 2 validity bytes
    assert agg.metrics[exec_base.BYTES_TOUCHED].value == 4 * (4 + 1 + 8 + 1)
    report = sess.explain_metrics()
    assert "opTimeDevice" in report
    assert "bytesTouched" in report
    assert "compile cache misses" in report
    # the footer is PER-RUN: re-running the (cache-warm) query reports 0
    sess.create_dataframe(data, schema).group_by("k").agg(
        A.agg(A.Sum(col("v")), "s")).collect()
    assert "compile cache misses: 0" in sess.explain_metrics()


def test_explain_metrics_without_sync_has_no_device_time():
    sess = TpuSession()
    df = sess.create_dataframe(
        {"k": [1, 2], "v": [3, 4]}, schema_of(k=T.INT, v=T.LONG))
    df.select(col("k"), col("v")).collect()
    report = sess.explain_metrics()
    assert "opTimeDevice" not in report
    assert "bytesTouched" in report


# ---------------------------------------------------------------------------
# recompile-regression guard: the fused aggregate compiles once per plan
# ---------------------------------------------------------------------------
def test_fused_agg_compiles_once_across_batches():
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)
    batches = _mk_batches(schema, nb=4, n=40)  # same shape bucket
    conf = RapidsConf({"spark.rapids.tpu.sql.agg.fusedPlan": "ON"})
    agg = _mixed_plan_exec(conf, batches, schema)
    before = exec_base.compile_miss_count()
    site_before = dict(exec_base.COMPILE_COUNTER.by_site)
    rows1 = agg.collect()
    added = exec_base.compile_miss_count() - before
    # ONE program for the whole update+merge+eval across 4 batches (the
    # child chain fuses into it; nothing else may compile)
    assert exec_base.COMPILE_COUNTER.by_site.get("agg_plan", 0) \
        == site_before.get("agg_plan", 0) + 1
    assert added == 1, exec_base.COMPILE_COUNTER.by_site
    # an identical plan over the same batch shapes recompiles NOTHING
    again = _mixed_plan_exec(conf, batches, schema)
    before2 = exec_base.compile_miss_count()
    rows2 = again.collect()
    assert exec_base.compile_miss_count() == before2
    _cmp_rows(rows1, rows2)
