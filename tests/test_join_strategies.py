"""Tiered join lowerings (round 14: kill the 29.8x join byte
amplification).

Coverage, per the issue checklist:
  * the five-tier differential matrix — AUTO / SEARCH / DIRECT / RADIX
    (+ PALLAS via interpret mode off-TPU) — over every join type and the
    torture inputs: all-null keys, NaN keys (NaN==NaN, -0.0==0.0),
    duplicate-heavy builds (the RADIX fused fast path must decline its
    uniqueness precondition and fall to the general co-sort), empty
    build/probe sides, and non-pow2 radix-agg tiles over a join output
    (FORCE_TILE_ROWS);
  * ops-level bit-identity: radix_probe_ranges' [lo, hi) — including
    insertion points for unmatched rows — and the matched-build mask
    equal the binary-search baseline everywhere, and
    radix_expansion_plan's pair list equals the repeat-based plan on
    every live slot;
  * ZERO scatter instructions in every RADIX-tier program (the compiled
    probe, the matched variant, the fused lo/matched variant, and the
    expansion), pinned through the hlo.py classifier;
  * forced-strategy recompile guards: a rerun of a RADIX join compiles
    nothing;
  * splits under fault injection (faults.py oom channel) for the new
    tiers, row-exact vs the CPU oracle;
  * the chooser: forced values, the CPU AUTO flip at build cap 2^16,
    the accelerator cost model against conf-declared roofline peaks,
    the legacy pallasProbe toggle, and the 'join_strategy' event +
    describe() visibility.
"""
import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401  (x64 enable)
import jax
import jax.numpy as jnp

from spark_rapids_tpu import events as EV
from spark_rapids_tpu import faults
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import schema_of
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import base as exec_base
from spark_rapids_tpu.exec.join import (
    TpuShuffledHashJoinExec,
    choose_join_strategy,
)
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.hlo import summarize_hlo
from spark_rapids_tpu.ops import join as J
from spark_rapids_tpu.ops import radix_bin as RBX
from spark_rapids_tpu.sql import TpuSession

from harness import compare_rows

STRATEGIES = ("AUTO", "SEARCH", "DIRECT", "RADIX", "PALLAS")
JOIN_TYPES = ("inner", "left", "right", "full", "semi", "anti")


# ---------------------------------------------------------------------------
# ops-level bit-identity: co-sorted merge vs binary search
# ---------------------------------------------------------------------------
def _sorted_build(rng, nb, bcount, nwords, lo_card=50):
    """Build words with a lexicographically sorted joinable prefix and
    garbage beyond it (the exec sorts exactly like this)."""
    ws = [rng.integers(0, lo_card, nb).astype(np.uint32)]
    for _ in range(nwords - 1):
        ws.append(rng.integers(0, 3, nb).astype(np.uint32))
    order = np.lexsort(tuple(w[:bcount] for w in reversed(ws)))
    for w in ws:
        w[:bcount] = w[:bcount][order]
    return ws


#: (build rows, probe rows) by key words: a shape compiles three programs,
#: so the trials vary the data, the joinable prefix and the dead rows over
#: three fixed shapes and not the shapes themselves
_RANGE_SHAPES = {1: (399, 130), 2: (64, 599), 3: (7, 257)}


@pytest.mark.parametrize("seed", range(8))
def test_ops_radix_ranges_bitidentical_vs_search(seed):
    rng = np.random.default_rng(seed)
    nwords = 1 + seed % 3
    nb, m = _RANGE_SHAPES[nwords]
    bcount = int(rng.integers(0, nb + 1))
    bws = _sorted_build(rng, nb, bcount, nwords)
    pws = [rng.integers(0, 70, m).astype(np.uint32)] + [
        rng.integers(0, 3, m).astype(np.uint32)
        for _ in range(nwords - 1)
    ]
    live = rng.random(m) < 0.8
    args = ([jnp.asarray(w) for w in bws], jnp.int32(bcount),
            [jnp.asarray(w) for w in pws], jnp.asarray(live))
    lo0, hi0 = J._probe_binary_search(*args)
    lo1, hi1, matched = J.radix_probe_ranges(*args, want_matched=True)
    np.testing.assert_array_equal(np.asarray(lo0), np.asarray(lo1))
    np.testing.assert_array_equal(np.asarray(hi0), np.asarray(hi1))
    want_m = np.asarray(J.matched_build_mask(
        lo0, hi0, jnp.asarray(live), nb))
    np.testing.assert_array_equal(want_m, np.asarray(matched))
    # the fused lo/matched variant: same lo, matched == (hi > lo)
    lo2, hi2, _ = J.radix_probe_ranges(*args, lo_matched_only=True)
    has = np.asarray(hi0 > lo0)
    np.testing.assert_array_equal(np.asarray(lo2)[has],
                                  np.asarray(lo0)[has])
    np.testing.assert_array_equal(np.asarray(hi2 > lo2), has)


def test_ops_radix_ranges_dead_probe_and_empty_sides():
    one = jnp.asarray(np.array([7], np.uint32))
    # empty joinable build: every probe reports [0, 0)
    lo, hi, m = J.radix_probe_ranges(
        [one], jnp.int32(0), [jnp.asarray(np.array([7, 9], np.uint32))],
        jnp.asarray(np.array([True, True])), want_matched=True)
    assert np.asarray(lo).tolist() == [0, 0]
    assert np.asarray(hi).tolist() == [0, 0]
    assert not np.asarray(m).any()
    # dead probe rows always report [0, 0), whatever their words
    lo, hi, _ = J.radix_probe_ranges(
        [one], jnp.int32(1), [one], jnp.asarray(np.array([False])))
    assert np.asarray(lo).tolist() == [0] and np.asarray(hi).tolist() == [0]


def test_ops_radix_expansion_identical_on_live_slots():
    rng = np.random.default_rng(9)
    counts = jnp.asarray(rng.integers(0, 4, 300).astype(np.int32))
    lo = jnp.asarray(np.cumsum(rng.integers(0, 3, 300)).astype(np.int32))
    out_cap = 1024
    p0, b0, s0 = J.expansion_plan(counts, lo, out_cap)
    p1, b1, s1 = J.radix_expansion_plan(counts, lo, out_cap)
    live = np.asarray(s0)
    np.testing.assert_array_equal(live, np.asarray(s1))
    np.testing.assert_array_equal(np.asarray(p0)[live], np.asarray(p1)[live])
    np.testing.assert_array_equal(np.asarray(b0)[live], np.asarray(b1)[live])


def test_ops_radix_programs_have_zero_scatters():
    rng = np.random.default_rng(1)
    nb, m = 256, 512
    bws = [jnp.asarray(np.sort(rng.integers(0, 99, nb).astype(np.uint32)))]
    pws = [jnp.asarray(rng.integers(0, 99, m).astype(np.uint32))]
    live = jnp.ones(m, bool)
    variants = {
        "ranges": lambda: jax.jit(
            lambda *a: J.radix_probe_ranges(*a)).lower(
                bws, jnp.int32(nb), pws, live).compile(),
        "matched": lambda: jax.jit(
            lambda *a: J.radix_probe_ranges(*a, want_matched=True)).lower(
                bws, jnp.int32(nb), pws, live).compile(),
        "fused": lambda: jax.jit(
            lambda *a: J.radix_probe_ranges(
                *a, lo_matched_only=True)).lower(
                bws, jnp.int32(nb), pws, live).compile(),
        "expansion": lambda: jax.jit(
            lambda c, l: J.radix_expansion_plan(c, l, 1024)).lower(
                jnp.zeros(m, jnp.int32), jnp.zeros(m, jnp.int32)).compile(),
    }
    for name, build in variants.items():
        s = summarize_hlo(build().as_text(), top_k=64)
        assert s["scatter_count"] == 0, (name, s["top_fusions"])


# ---------------------------------------------------------------------------
# exec-level five-tier matrix vs the CPU oracle
# ---------------------------------------------------------------------------
def _torture_datasets():
    """(name, left data+schema, right data+schema) torture inputs. Small
    on purpose: the CPU oracle join is O(n^2)."""
    ln, rn = 72, 29
    lsch = schema_of(k=T.INT, a=T.LONG)
    rsch = schema_of(k2=T.INT, b=T.LONG)
    fsch_l = schema_of(k=T.DOUBLE, a=T.LONG)
    fsch_r = schema_of(k2=T.DOUBLE, b=T.LONG)
    unique = ({"k": [i % 40 if i % 11 else None for i in range(ln)],
               "a": [(i * 7) % 50 - 25 for i in range(ln)]}, lsch,
              {"k2": [i if i % 7 else None for i in range(rn)],
               "b": [i * 3 for i in range(rn)]}, rsch)
    dup = (unique[0], lsch,
           {"k2": [i % 5 if i % 7 else None for i in range(rn)],
            "b": [i * 3 for i in range(rn)]}, rsch)
    allnull = (unique[0], lsch,
               {"k2": [None] * rn, "b": [i for i in range(rn)]}, rsch)
    nan = ({"k": [float("nan") if i % 5 == 0 else
                  (-0.0 if i % 5 == 1 else float(i % 9))
                  for i in range(ln)],
            "a": [i for i in range(ln)]}, fsch_l,
           {"k2": [float("nan") if i % 4 == 0 else
                   (0.0 if i % 4 == 1 else float(i % 12))
                   for i in range(rn)],
            "b": [i * 3 for i in range(rn)]}, fsch_r)
    empty_build = (unique[0], lsch, {"k2": [], "b": []}, rsch)
    empty_probe = ({"k": [], "a": []}, lsch, unique[2], rsch)
    return [("unique", *unique), ("dup", *dup), ("allnull", *allnull),
            ("nan", *nan), ("empty_build", *empty_build),
            ("empty_probe", *empty_probe)]


@pytest.mark.parametrize("strategy", [
    # RADIX (the new tier) and DIRECT (the fused incumbent) run in the
    # budgeted tier-1 sweep; the rest ride the CI pallas job, which runs
    # this file unfiltered
    "RADIX", "DIRECT",
    pytest.param("AUTO", marks=pytest.mark.slow),
    pytest.param("SEARCH", marks=pytest.mark.slow),
    pytest.param("PALLAS", marks=pytest.mark.slow),
])
def test_exec_join_matrix_vs_cpu_oracle(strategy):
    datasets = _torture_datasets()
    cpu_sess = TpuSession({"spark.rapids.tpu.sql.enabled": False})
    tpu_sess = TpuSession(
        {"spark.rapids.tpu.sql.join.strategy": strategy})

    def build(s, ds, how):
        _, ld, lsch, rd, rsch = ds
        return s.create_dataframe(ld, lsch).join(
            s.create_dataframe(rd, rsch), on=[("k", "k2")], how=how)

    for ds in datasets:
        for how in JOIN_TYPES:
            want = build(cpu_sess, ds, how).collect()
            got = build(tpu_sess, ds, how).collect()
            compare_rows(want, got, ignore_order=True,
                         approx_float=True)


def test_join_feeding_radix_agg_non_pow2_tiles():
    """Join output through a forced-RADIX aggregate on non-divisor tile
    sizes (FORCE_TILE_ROWS): the radix-binned agg must reduce the join's
    masked/fused output exactly, multi-tile + flush paths included."""
    n, d = 700, 37
    rng = np.random.default_rng(21)
    ldata = {"k": [int(x) for x in rng.integers(0, d, n)],
             "v": [int(x) for x in rng.integers(-100, 100, n)]}
    rdata = {"k2": list(range(d)),
             "g": [i % 6 for i in range(d)]}
    lsch = schema_of(k=T.INT, v=T.LONG)
    rsch = schema_of(k2=T.INT, g=T.INT)
    from spark_rapids_tpu.expr import aggregates as A

    def build(s):
        j = s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")], how="inner")
        return j.group_by("g").agg(A.agg(A.Sum(col("v")), "sv"),
                                   A.agg(A.Count(None), "c"))

    want = build(TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    prev = RBX.FORCE_TILE_ROWS
    try:
        for tile in (96, 160):
            RBX.FORCE_TILE_ROWS = tile
            got = build(TpuSession({
                "spark.rapids.tpu.sql.join.strategy": "RADIX",
                "spark.rapids.tpu.sql.agg.strategy": "RADIX"})).collect()
            compare_rows(want, got, ignore_order=True)
    finally:
        RBX.FORCE_TILE_ROWS = prev


# ---------------------------------------------------------------------------
# fused fast path + recompile guards
# ---------------------------------------------------------------------------
def _exec_join(conf_dict, ldata, lsch, rdata, rsch, how="inner"):
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.exec import InMemoryScanExec

    conf = RapidsConf(conf_dict)
    lb = ColumnarBatch.from_pydict(ldata, lsch)
    rb = ColumnarBatch.from_pydict(rdata, rsch)
    return TpuShuffledHashJoinExec(
        conf, InMemoryScanExec(conf, [[lb]], lsch),
        InMemoryScanExec(conf, [[rb]], rsch),
        [col("k")], [col("k2")], how)


_L = {"k": [i % 29 for i in range(120)], "a": list(range(120))}
_LS = schema_of(k=T.INT, a=T.LONG)
_RU = {"k2": list(range(29)), "b": [i * 2 for i in range(29)]}
_RD = {"k2": [i % 4 for i in range(29)], "b": [i * 2 for i in range(29)]}
_RS = schema_of(k2=T.INT, b=T.LONG)


def test_radix_unique_build_takes_fused_fast_path():
    j = _exec_join({"spark.rapids.tpu.sql.join.strategy": "RADIX"},
                   _L, _LS, _RU, _RS)
    rows = j.collect()
    st = j._fast_built
    assert isinstance(st, dict) and st["kind"] == "radix", st
    assert j._join_strategy_choice[0] == "RADIX"
    assert "strategy=RADIX" in j.describe()
    assert len(rows) == 120  # every probe row matches its unique key


def test_radix_duplicate_build_declines_fusion_general_path():
    j = _exec_join({"spark.rapids.tpu.sql.join.strategy": "RADIX"},
                   _L, _LS, _RD, _RS)
    rows = j.collect()
    assert j._fast_built is False  # uniqueness sync said no
    # 120 probe rows x 29/4-ish dup matches, vs the oracle
    o = _exec_join({"spark.rapids.tpu.sql.join.strategy": "SEARCH"},
                   _L, _LS, _RD, _RS)
    compare_rows(o.collect(), rows, ignore_order=True)


def test_forced_radix_join_compiles_once():
    j = _exec_join({"spark.rapids.tpu.sql.join.strategy": "RADIX"},
                   _L, _LS, _RU, _RS)
    rows1 = sorted(j.collect())
    before = exec_base.compile_miss_count()
    rows2 = sorted(j.collect())  # same exec, same shapes: zero compiles
    assert exec_base.compile_miss_count() == before, \
        exec_base.COMPILE_COUNTER.by_site
    assert rows1 == rows2
    # and the memoized choice never flips mid-plan
    assert j._strategy_by_cap == {32: "RADIX"} or len(
        j._strategy_by_cap) == 1


def test_fused_radix_probe_program_has_zero_scatters():
    """Harvest the compiled programs of a RADIX join feeding a RADIX
    aggregate (the bench join-shape topology) and pin ZERO
    scatter-classified instructions across all of them — the acceptance
    criterion of the rewrite."""
    from spark_rapids_tpu import hlo, xla_cost
    from spark_rapids_tpu.exec import TpuHashAggregateExec
    from spark_rapids_tpu.expr import aggregates as A

    prev = xla_cost.FORCE_HARVEST
    xla_cost.FORCE_HARVEST = True
    try:
        seq = hlo.snapshot()
        j = _exec_join({"spark.rapids.tpu.sql.join.strategy": "RADIX",
                        "spark.rapids.tpu.sql.agg.strategy": "RADIX"},
                       {"k": [i % 13 for i in range(500)],
                        "a": list(range(500))}, _LS,
                       {"k2": list(range(13)),
                        "b": [i * 7 for i in range(13)]}, _RS)
        agg = TpuHashAggregateExec(
            j.conf, [col("b")],
            [A.agg(A.Sum(col("a")), "s"), A.agg(A.Count(None), "c")], j)
        agg.collect()
        recs = hlo.records_since(seq)
        assert recs, "no programs harvested"
        assert sum(r.get("scatter_count") or 0 for r in recs) == 0, [
            (r["digest"], r["top_fusions"]) for r in recs
            if r.get("scatter_count")]
    finally:
        xla_cost.FORCE_HARVEST = prev


# ---------------------------------------------------------------------------
# splits under fault injection for the new tiers
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("strategy", ["RADIX", "PALLAS"])
def test_split_and_retry_under_injected_oom(strategy):
    n = 600
    ldata = {"k": [i % 23 for i in range(n)],
             "a": [None if i % 17 == 0 else i for i in range(n)]}
    rdata = {"k2": [i % 9 for i in range(23)],
             "b": [i * 10 for i in range(23)]}
    lsch = schema_of(k=T.INT, a=T.LONG)
    rsch = schema_of(k2=T.INT, b=T.LONG)

    def build(s):
        return s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")], how="inner")

    want = build(TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    sess = TpuSession({
        "spark.rapids.tpu.sql.join.strategy": strategy,
        "spark.rapids.tpu.test.faults.oom": "TpuShuffledHashJoinExec*>256",
        "spark.rapids.tpu.memory.oomRetry.backoffMs": 0,
    })
    try:
        got = build(sess).collect()
        compare_rows(want, got, ignore_order=True)
        inj = faults.active()
        assert inj is not None and inj.fired(), strategy
    finally:
        faults.uninstall()


# ---------------------------------------------------------------------------
# the chooser + visibility surfaces
# ---------------------------------------------------------------------------
def test_chooser_forced_and_auto_branches():
    keys = (T.LONG,)
    forced = RapidsConf({"spark.rapids.tpu.sql.join.strategy": "SEARCH"})
    s, why = choose_join_strategy(forced, 1 << 17, keys, "inner")
    assert s == "SEARCH" and "forced" in why
    auto = RapidsConf({})
    # CPU AUTO: small single-key build -> DIRECT (fusable table), big
    # build -> RADIX (the scatter dialect's charged-byte amplification)
    s, why = choose_join_strategy(auto, 1 << 12, keys, "inner",
                                  backend="cpu")
    assert s == "DIRECT", why
    s, why = choose_join_strategy(auto, 1 << 17, keys, "inner",
                                  backend="cpu")
    assert s == "RADIX" and "29.8x" in why
    # multi-word keys have no direct-address table at any size
    s, _ = choose_join_strategy(auto, 1 << 12, (T.LONG, T.LONG), "inner",
                                backend="cpu")
    assert s == "RADIX"
    # accelerator AUTO: single-key builds keep the fusable direct
    # table; multi-word keys are costed against the conf-declared
    # roofline peaks, with the search's gather chain priced at the
    # chip's near-serial random-access rate
    s, why = choose_join_strategy(auto, 1 << 17, keys, "inner",
                                  backend="tpu")
    assert s == "DIRECT", why
    s_wide, why_wide = choose_join_strategy(
        auto, 1 << 22, (T.LONG, T.LONG, T.LONG), "inner", backend="tpu")
    assert s_wide == "RADIX", why_wide
    assert "est radix" in why_wide and "GB/s" in why_wide
    # a tiny declared HBM peak makes the sort passes expensive enough
    # that the gather chain wins the same shape
    slow_hbm = RapidsConf(
        {"spark.rapids.tpu.roofline.peakHbmGBps": 0.05})
    s_slow, why_slow = choose_join_strategy(
        slow_hbm, 1 << 22, (T.LONG, T.LONG, T.LONG), "inner",
        backend="tpu")
    assert s_slow == "SEARCH", why_slow
    # legacy toggle: pallasProbe forces the PALLAS tier under AUTO
    legacy = RapidsConf(
        {"spark.rapids.tpu.sql.join.pallasProbe.enabled": True})
    s, why = choose_join_strategy(legacy, 1 << 12, keys, "inner")
    assert s == "PALLAS" and "legacy" in why


def test_strategy_visible_in_events_and_explain():
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": True,
                       "spark.rapids.tpu.sql.join.strategy": "RADIX"})
    ldf = sess.create_dataframe(_L, _LS)
    rdf = sess.create_dataframe(_RU, _RS)
    try:
        rows = ldf.join(rdf, on=[("k", "k2")], how="inner").collect()
    finally:
        # the sink is process-wide: left installed, every later file of
        # this worker runs with a cost consumer on
        EV.uninstall()
    assert len(rows) == 120
    evs = [r for r in sess.events.records()
           if r.get("event") == "join_strategy"]
    assert evs, "join_strategy event not emitted"
    assert evs[0]["strategy"] == "RADIX"
    assert evs[0]["build_cap"] >= 29
    assert "forced" in evs[0]["reason"]


def test_plananalysis_forecasts_join_strategy():
    sess = TpuSession({"spark.rapids.tpu.sql.join.strategy": "RADIX"})
    ldf = sess.create_dataframe(_L, _LS)
    rdf = sess.create_dataframe(_RU, _RS)
    text = ldf.join(rdf, on=[("k", "k2")], how="inner").explain()
    assert "join strategy: RADIX" in text, text


def test_profiler_join_strategy_section():
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpu_profile", os.path.join(
            os.path.dirname(__file__), "..", "tools", "tpu_profile.py"))
    tp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tp)
    events = [
        {"event": "join_strategy", "ts": 0, "op": "TpuShuffledHashJoinExec",
         "strategy": "RADIX", "reason": "forced by conf",
         "build_cap": 1 << 15},
    ]
    text = tp.build_report(events)
    if isinstance(text, tuple):  # (report, violation count)
        text = text[0]
    assert "== join strategy ==" in text
    assert "TpuShuffledHashJoinExec[build_cap=32768]: RADIX" in text


def test_string_key_join_mismatched_length_buckets():
    """String join keys derive their chunk-word counts from EACH side's
    own max-length bucket; pad_key_words zero-extends the shorter side
    (exact — beyond-bucket chunks are all zero), so a probe key equal
    to a build key's PREFIX must not match it. CPU AUTO routes string
    keys to RADIX, which crashed (or truncation-matched) before the
    round-14 review fix; SEARCH silently compared only the common
    prefix."""
    ldata = {"k": ["abcd", "abcdXYZw", "ab", None, "abcd"],
             "a": [1, 2, 3, 4, 5]}
    rdata = {"k2": ["abcd", "abcdXYZwLONGTAIL", "zz", None],
             "b": [10, 20, 30, 40]}
    lsch = schema_of(k=T.STRING, a=T.LONG)
    rsch = schema_of(k2=T.STRING, b=T.LONG)

    def build(s):
        return s.create_dataframe(ldata, lsch).join(
            s.create_dataframe(rdata, rsch), on=[("k", "k2")], how="left")

    want = build(TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    for strategy in ("AUTO", "SEARCH", "RADIX"):
        got = build(TpuSession({
            "spark.rapids.tpu.sql.join.strategy": strategy})).collect()
        compare_rows(want, got, ignore_order=True)
    # ops-level: the padded word lists reconstruct the longer encoding
    from spark_rapids_tpu.ops.join import pad_key_words

    bw = [jnp.zeros(8, jnp.uint32)] * 3
    pw = [jnp.ones(4, jnp.uint32)]
    b2, p2 = pad_key_words(bw, pw)
    assert len(b2) == len(p2) == 3
    assert p2[1].shape == (4,) and not np.asarray(p2[1]).any()


def test_legacy_pallas_toggle_keeps_direct_fused_fast_path():
    """sql.join.pallasProbe.enabled predates the strategy conf and only
    ever governed the GENERAL probe path — the DIRECT fused fast path
    pre-empted it. The AUTO resolution must preserve that (the conf's
    keep-their-behavior contract), while a FORCED strategy=PALLAS does
    disable the fast path."""
    legacy = {"spark.rapids.tpu.sql.join.pallasProbe.enabled": True}
    j = _exec_join(legacy, _L, _LS, _RU, _RS)
    rows = j.collect()
    assert isinstance(j._fast_built, dict) and \
        j._fast_built["kind"] == "direct", j._fast_built
    o = _exec_join({"spark.rapids.tpu.sql.join.strategy": "SEARCH"},
                   _L, _LS, _RU, _RS)
    compare_rows(o.collect(), rows, ignore_order=True)
    forced = {"spark.rapids.tpu.sql.join.strategy": "PALLAS"}
    j2 = _exec_join(forced, _L, _LS, _RU, _RS)
    rows2 = j2.collect()
    assert j2._fast_built is False
    compare_rows(rows, rows2, ignore_order=True)
