"""Per-fusion HLO attribution plane (spark_rapids_tpu/hlo.py) + the
environment-provenance helper (envinfo.py) riding the same PR.

Pins the contracts ISSUE 11 introduced:
  1. golden HLO-text fixtures — a CPU-dialect module (scatter +
     transpose fusion), a TPU-dialect module (tiled layouts, one-hot
     expansion feeding a dot), and a malformed/unknown-op module — pin
     the parser's byte totals, idiom classifications, and the
     coverage-fraction degradation (never an exception);
  2. exactness anchor: a plain jitted dot's attribution equals the
     compiler's own ``cost_analysis()['bytes accessed']``;
  3. live harvest: a cold query emits exactly one ``hlo_summary`` per
     ``program_cost`` twin (same site+digest), with accounted_frac /
     coverage reported whenever the attribution explains less than the
     compiler's figure — the shortfall is named, never silent;
  4. zero overhead: with events AND obs off (FORCE_HARVEST unset) the
     HLO text is never fetched or parsed (spy on harvest_hlo — the only
     as_text caller — matching the xla_cost contract);
  5. obs twins: scatter-program counter + top-fusion-bytes gauge;
  6. tpu_profile: the '== hlo ==' section names the amplification
     culprit per site with its share of the site's XLA bytes, and
     --diff gates per-site fusion-byte growth / scatter appearance in
     both event-log and bench-JSON form (scatter gated only when the
     agg strategy did not change);
  7. env provenance: envinfo.environment_info shape, the
     environments_differ rule, its duplicated-by-design twin in the
     offline tool, and the loud ENVIRONMENTS DIFFER banner in --diff.
"""
import importlib.util
import json
import os

import pytest

from spark_rapids_tpu import envinfo
from spark_rapids_tpu import events as EV
from spark_rapids_tpu import hlo
from spark_rapids_tpu import obs
from spark_rapids_tpu import xla_cost as XC
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.obs.registry import MetricsRegistry
from spark_rapids_tpu.sql import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "tpu_profile", os.path.join(REPO, "tools", "tpu_profile.py"))
tpu_profile = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpu_profile)


@pytest.fixture(autouse=True)
def clean_planes():
    EV.uninstall()
    obs.uninstall()
    prev = XC.FORCE_HARVEST
    XC.FORCE_HARVEST = False
    yield
    XC.FORCE_HARVEST = prev
    EV.uninstall()
    obs.uninstall()


# ---------------------------------------------------------------------------
# 1. golden fixtures
# ---------------------------------------------------------------------------
# CPU dialect: plain layouts, a kLoop transpose fusion, a real scatter
# with an add combiner. Hand-computed attribution (output + operand
# shape bytes; parameters/tuple cost zero):
#   fusion:  32768 out + 32768 operand           =  65536  transpose/copy
#   scatter: 32768 out + 32768 + 128 + 8192      =  73856  scatter-add
#   total                                        = 139392
CPU_HLO = """\
HloModule jit_step, entry_computation_layout={(f32[128,64]{1,0})->f32[64,128]{1,0}}

%add_comp (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}

%fused_computation (p0: f32[128,64]) -> f32[64,128] {
  %p0 = f32[128,64]{1,0} parameter(0)
  ROOT %t = f32[64,128]{1,0} transpose(f32[128,64]{1,0} %p0), dimensions={1,0}
}

ENTRY %main (x: f32[128,64], idx: s32[32,1], upd: f32[32,64]) -> (f32[64,128], f32[128,64]) {
  %x = f32[128,64]{1,0} parameter(0)
  %idx = s32[32,1]{1,0} parameter(1)
  %upd = f32[32,64]{1,0} parameter(2)
  %fusion = f32[64,128]{1,0} fusion(f32[128,64]{1,0} %x), kind=kLoop, calls=%fused_computation
  %scatter = f32[128,64]{1,0} scatter(f32[128,64]{1,0} %x, s32[32,1]{1,0} %idx, f32[32,64]{1,0} %upd), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add_comp
  ROOT %out = (f32[64,128]{1,0}, f32[128,64]{1,0}) tuple(f32[64,128]{1,0} %fusion, f32[128,64]{1,0} %scatter)
}
"""

# TPU dialect: tiled layout suffixes {1,0:T(8,128)}, a one-hot
# expansion fusion (iota+broadcast+compare) feeding a dot — the
# bucket_reduce matmul signature. Attribution:
#   onehot fusion: 65536 out + 4096 operand           =  69632  one-hot expand
#   dot:           256 out + 65536 + 16384 operands   =  82176  one-hot dot
#   total                                             = 151808
TPU_HLO = """\
HloModule jit_agg, is_scheduled=true

%region_0.11 (Arg_0.12: f32[], Arg_1.13: f32[]) -> f32[] {
  %Arg_0.12 = f32[] parameter(0)
  %Arg_1.13 = f32[] parameter(1)
  ROOT %add.14 = f32[] add(f32[] %Arg_0.12, f32[] %Arg_1.13)
}

%fused_onehot (param_0.1: s32[1024]) -> f32[1024,16] {
  %param_0.1 = s32[1024]{0:T(1024)} parameter(0)
  %iota.3 = s32[1024,16]{1,0:T(8,128)} iota(), iota_dimension=1
  %broadcast.4 = s32[1024,16]{1,0:T(8,128)} broadcast(s32[1024]{0:T(1024)} %param_0.1), dimensions={0}
  %compare.5 = pred[1024,16]{1,0:T(8,128)(4,1)} compare(s32[1024,16]{1,0:T(8,128)} %broadcast.4, s32[1024,16]{1,0:T(8,128)} %iota.3), direction=EQ
  ROOT %convert.6 = f32[1024,16]{1,0:T(8,128)} convert(pred[1024,16]{1,0:T(8,128)(4,1)} %compare.5)
}

ENTRY %main.42 (p0: s32[1024], p1: f32[1024,4]) -> f32[16,4] {
  %p0 = s32[1024]{0:T(1024)} parameter(0)
  %p1 = f32[1024,4]{1,0:T(8,128)} parameter(1)
  %onehot = f32[1024,16]{1,0:T(8,128)} fusion(s32[1024]{0:T(1024)} %p0), kind=kLoop, calls=%fused_onehot
  ROOT %dot.9 = f32[16,4]{1,0:T(8,128)} dot(f32[1024,16]{1,0:T(8,128)} %onehot, f32[1024,4]{1,0:T(8,128)} %p1), lhs_contracting_dims={0}, rhs_contracting_dims={0}
}
"""

# malformed: an unknown dtype (q77), a line that is not an instruction,
# and a healthy ROOT — 2 of 4 entry lines fully parse -> coverage 0.5,
# and only the healthy add contributes bytes (32 out + 2x32 operands)
BAD_HLO = """\
HloModule weird

ENTRY %e (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %mys = q77[8] mystery-op(f32[8]{0} %p)
  this line is not an instruction at all
  ROOT %r = f32[8]{0} add(f32[8]{0} %p, f32[8]{0} %p)
}
"""


def test_cpu_dialect_golden_bytes_and_classes():
    s = hlo.summarize_hlo(CPU_HLO)
    assert s["coverage"] == 1.0
    assert s["instructions"] == 6
    assert s["total_bytes"] == 139392
    assert s["scatter_count"] == 1
    by_name = {r["name"]: r for r in s["top_fusions"]}
    assert by_name["scatter"]["class"] == "scatter-add"
    assert by_name["scatter"]["bytes"] == 73856
    assert by_name["fusion"]["class"] == "transpose/copy"
    assert by_name["fusion"]["bytes"] == 65536
    # ranked by attributed bytes: the scatter owns the module
    assert s["top_fusions"][0]["name"] == "scatter"
    assert s["largest_output"]["bytes"] == 32768


def test_tpu_dialect_tiled_layouts_and_one_hot():
    s = hlo.summarize_hlo(TPU_HLO)
    assert s["coverage"] == 1.0
    assert s["total_bytes"] == 151808
    assert s["scatter_count"] == 0
    by_name = {r["name"]: r for r in s["top_fusions"]}
    # the dot sees THROUGH its fusion operand to the broadcast-compare
    # expansion: classified as the one-hot dot idiom, not a plain dot
    assert by_name["dot.9"]["class"] == "one-hot dot"
    assert by_name["dot.9"]["bytes"] == 82176
    # the expansion itself is named even without an in-fusion dot
    assert by_name["onehot"]["class"] == "one-hot expand"
    assert by_name["onehot"]["bytes"] == 69632


def test_malformed_degrades_coverage_never_raises():
    s = hlo.summarize_hlo(BAD_HLO)
    assert s["coverage"] == 0.5
    assert s["total_bytes"] == 96
    assert s["scatter_count"] == 0
    # pure garbage and empty text both yield the zero summary
    for text in ("", "not hlo at all\n{}{}", "HloModule x\n"):
        z = hlo.summarize_hlo(text)
        assert z["coverage"] == 0.0 and z["total_bytes"] == 0


def test_dot_consuming_scatter_output_is_not_a_scatter():
    """The one-hot look-through must not leak producer opcodes into the
    idiom decision: a dot that merely CONSUMES a scatter's output stays
    a plain dot, and the module counts ONE scatter, not two (else any
    refactor fusing/unfusing a scatter's consumer flips scatter_count
    and fires the --diff appearance gate on a no-op change)."""
    text = """\
HloModule consume
%add_c (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %r = f32[] add(f32[] %a, f32[] %b)
}
ENTRY %e (x: f32[16,8], idx: s32[4,1], upd: f32[4,8], w: f32[8,4]) -> f32[16,4] {
  %x = f32[16,8]{1,0} parameter(0)
  %idx = s32[4,1]{1,0} parameter(1)
  %upd = f32[4,8]{1,0} parameter(2)
  %w = f32[8,4]{1,0} parameter(3)
  %sc = f32[16,8]{1,0} scatter(f32[16,8]{1,0} %x, s32[4,1]{1,0} %idx, f32[4,8]{1,0} %upd), update_window_dims={1}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add_c
  ROOT %d = f32[16,4]{1,0} dot(f32[16,8]{1,0} %sc, f32[8,4]{1,0} %w), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    s = hlo.summarize_hlo(text)
    assert s["coverage"] == 1.0
    assert s["scatter_count"] == 1, s["top_fusions"]
    by_name = {r["name"]: r for r in s["top_fusions"]}
    assert by_name["sc"]["class"] == "scatter-add"
    assert by_name["d"]["class"] == "dot"


def test_radix_bin_loop_not_misclassified_as_scatter():
    """The radix-bin lowering compiles to a while loop whose body writes
    MULTI-ELEMENT tiles through dynamic-update-slice (the sliding output
    window of ops/radix_bin.py). The classifier must read it as
    'radix-bin' — calling it scatter would trip the --diff
    scatter-appearance gate on the byte-amplification fix itself — while
    the CPU scatter emulation (one element updated per trip against a
    full-size accumulator) must STILL read as scatter-add."""
    text = """\
HloModule jit_radix

%tile_cond (cp: (s32[], f32[4096,2], f32[64,2])) -> pred[] {
  %cp = (s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) parameter(0)
  %ci = s32[] get-tuple-element((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %cp), index=0
  %cn = s32[] constant(64)
  ROOT %lt = pred[] compare(s32[] %ci, s32[] %cn), direction=LT
}

%tile_body (p: (s32[], f32[4096,2], f32[64,2])) -> (s32[], f32[4096,2], f32[64,2]) {
  %p = (s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) parameter(0)
  %i = s32[] get-tuple-element((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %p), index=0
  %buf = f32[4096,2]{1,0} get-tuple-element((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %p), index=1
  %tile = f32[64,2]{1,0} get-tuple-element((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %p), index=2
  %zero = s32[] constant(0)
  %win = f32[4096,2]{1,0} dynamic-update-slice(f32[4096,2]{1,0} %buf, f32[64,2]{1,0} %tile, s32[] %i, s32[] %zero)
  %one = s32[] constant(1)
  %ni = s32[] add(s32[] %i, s32[] %one)
  ROOT %t = (s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) tuple(s32[] %ni, f32[4096,2]{1,0} %win, f32[64,2]{1,0} %tile)
}

%em_cond (ep: (s32[], f32[4096], f32[1])) -> pred[] {
  %ep = (s32[], f32[4096]{0}, f32[1]{0}) parameter(0)
  %ei = s32[] get-tuple-element((s32[], f32[4096]{0}, f32[1]{0}) %ep), index=0
  %en = s32[] constant(4096)
  ROOT %elt = pred[] compare(s32[] %ei, s32[] %en), direction=LT
}

%em_body (q: (s32[], f32[4096], f32[1])) -> (s32[], f32[4096], f32[1]) {
  %q = (s32[], f32[4096]{0}, f32[1]{0}) parameter(0)
  %j = s32[] get-tuple-element((s32[], f32[4096]{0}, f32[1]{0}) %q), index=0
  %acc = f32[4096]{0} get-tuple-element((s32[], f32[4096]{0}, f32[1]{0}) %q), index=1
  %el = f32[1]{0} get-tuple-element((s32[], f32[4096]{0}, f32[1]{0}) %q), index=2
  %wr = f32[4096]{0} dynamic-update-slice(f32[4096]{0} %acc, f32[1]{0} %el, s32[] %j)
  %one2 = s32[] constant(1)
  %nj = s32[] add(s32[] %j, s32[] %one2)
  ROOT %t2 = (s32[], f32[4096]{0}, f32[1]{0}) tuple(s32[] %nj, f32[4096]{0} %wr, f32[1]{0} %el)
}

ENTRY %main (init: (s32[], f32[4096,2], f32[64,2]), einit: (s32[], f32[4096], f32[1])) -> f32[4096,2] {
  %init = (s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) parameter(0)
  %einit = (s32[], f32[4096]{0}, f32[1]{0}) parameter(1)
  %radix = (s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) while((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %init), condition=%tile_cond, body=%tile_body
  %emul = (s32[], f32[4096]{0}, f32[1]{0}) while((s32[], f32[4096]{0}, f32[1]{0}) %einit), condition=%em_cond, body=%em_body
  ROOT %out = f32[4096,2]{1,0} get-tuple-element((s32[], f32[4096,2]{1,0}, f32[64,2]{1,0}) %radix), index=1
}
"""
    s = hlo.summarize_hlo(text)
    assert s["coverage"] == 1.0
    by_name = {r["name"]: r for r in s["top_fusions"]}
    assert by_name["radix"]["class"] == "radix-bin", by_name
    assert by_name["emul"]["class"] == "scatter-add", by_name
    # only the per-element emulation counts against the scatter gate
    assert s["scatter_count"] == 1, s["top_fusions"]


def test_pallas_custom_call_classified_not_scatter():
    """A hand-written Pallas/Mosaic kernel surfaces as a custom-call
    whose target names the Mosaic pipeline; it owns its working set in
    VMEM and must classify as 'pallas', never as the scatter/one-hot it
    replaced (and never inflate scatter_count)."""
    text = """\
HloModule jit_pallas

ENTRY %main (p0: s32[1024], p1: f32[1024,16]) -> s32[256,17] {
  %p0 = s32[1024]{0} parameter(0)
  %p1 = f32[1024,16]{1,0} parameter(1)
  ROOT %cc = s32[256,17]{1,0} custom-call(s32[1024]{0} %p0, f32[1024,16]{1,0} %p1), custom_call_target="tpu_custom_call", api_version=API_VERSION_STATUS_RETURNING
}
"""
    s = hlo.summarize_hlo(text)
    assert s["coverage"] == 1.0
    assert s["scatter_count"] == 0
    by_name = {r["name"]: r for r in s["top_fusions"]}
    assert by_name["cc"]["class"] == "pallas"
    # bytes still attribute normally: output + operand shapes
    assert by_name["cc"]["bytes"] == 256 * 17 * 4 + 1024 * 4 + 1024 * 16 * 4


def test_compiled_radix_program_has_zero_scatter_classified():
    """End to end on the REAL compiled program: lower a RADIX-strategy
    groupby (sums, float sum, min, count, first — every reduction
    family), parse its optimized HLO, and require ZERO scatter-classified
    entry instructions with full parse coverage — the merge gate of the
    byte-amplification fix, pinned against compiler drift."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.eval import ColV
    from spark_rapids_tpu.ops import groupby as G

    cap = 1 << 10
    rng = np.random.default_rng(0)
    keys = ColV(jnp.asarray(rng.integers(0, 50, cap).astype(np.int64)),
                jnp.ones(cap, jnp.bool_))
    vals = ColV(jnp.asarray(rng.integers(-100, 100, cap).astype(np.int64)),
                jnp.ones(cap, jnp.bool_))
    fvals = ColV(jnp.asarray(rng.normal(size=cap)),
                 jnp.ones(cap, jnp.bool_))

    def run(k, v, f, n):
        return G.groupby_agg(
            [k], [T.LONG], [v, f, v, None, v],
            ["sum", "sum", "min", "count_star", "first"],
            n, strategy="RADIX")

    txt = (jax.jit(run)
           .lower(keys, vals, fvals, jnp.int32(cap)).compile().as_text())
    s = hlo.summarize_hlo(txt, top_k=64)
    assert s["coverage"] == 1.0
    assert s["scatter_count"] == 0, [
        r for r in s["top_fusions"]
        if r["class"] in ("scatter", "scatter-add")]
    mod = hlo.parse_hlo_module(txt)
    classes = {hlo.classify(mod, ins) for ins in mod.instrs(mod.entry)}
    assert "radix-bin" in classes, classes
    assert not classes & {"scatter", "scatter-add"}, classes


def test_top_k_truncates_ranked_list():
    s = hlo.summarize_hlo(CPU_HLO, top_k=1)
    assert len(s["top_fusions"]) == 1
    assert s["top_fusions"][0]["name"] == "scatter"
    # truncation changes the reported list, not the totals
    assert s["total_bytes"] == 139392


def test_shape_parser_tuples_dynamic_dims_and_comments():
    # tuple with /*index=N*/ filler, bounded-dynamic dim, token
    b, e, _ = hlo._parse_shape(
        "(f32[2,3]{1,0}, /*index=1*/ s32[<=10]{0}, token[])", 0)
    assert b == 2 * 3 * 4 + 10 * 4  # token costs 0 bytes
    assert e == 6 + 10 + 1
    with pytest.raises(ValueError):
        hlo._parse_shape("f32[2,", 0)


# ---------------------------------------------------------------------------
# 2. exactness anchor vs the compiler's own figure
# ---------------------------------------------------------------------------
def test_plain_dot_matches_cost_analysis_exactly():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a @ b)
    a = jnp.zeros((64, 64), jnp.float32)
    compiled = f.lower(a, a).compile()
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0] if ca else {}
    xla_bytes = ca.get("bytes accessed")
    s = hlo.summarize_hlo(compiled.as_text())
    assert s["coverage"] == 1.0
    if xla_bytes:  # backend reported one: the anchor must hold
        assert abs(s["total_bytes"] - xla_bytes) <= 0.1 * xla_bytes


# ---------------------------------------------------------------------------
# 3. live harvest: one hlo_summary per program_cost, shortfall named
# ---------------------------------------------------------------------------
def _query(sess, hi=4096, mult=301):
    """Cold compiles need a (hi, mult) pair no other suite has run —
    the pipeline caches are process-global (test_program_cost idiom)."""
    df = (sess.range(0, hi)
          .where(E.GreaterThanOrEqual(col("id"), lit(100)))
          .select(col("id"),
                  E.Alias(E.Multiply(col("id"), lit(mult)), "v"))
          .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))
    return df.collect()


def test_live_harvest_one_summary_per_program(tmp_path):
    sess = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    _query(sess, mult=301)
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    costs = [r for r in recs if r["event"] == "program_cost"]
    sums = [r for r in recs if r["event"] == "hlo_summary"]
    assert costs and sums
    # exactly one summary per harvested program, same (site, digest)
    assert ({(r["site"], r["digest"]) for r in costs}
            == {(r["site"], r["digest"]) for r in sums})
    for r in sums:
        for field in EV.EVENT_TYPES["hlo_summary"]:
            assert field in r, f"hlo_summary missing {field}: {r}"
        assert 0.0 <= r["coverage"] <= 1.0
        assert r["total_bytes"] >= 0
        # the acceptance contract: bytes within 10% of the compiler's
        # figure, OR the shortfall is REPORTED via accounted_frac +
        # coverage (XLA utilization-weights bytes inside fused loop
        # bodies; the ratio and coverage explain the divergence)
        af = r.get("accounted_frac")
        if af is not None and not (0.9 <= af <= 1.1):
            assert r["coverage"] is not None
    # warm rerun harvests nothing new (rides the xla_cost once-guard)
    n = len(sums)
    _query(sess, mult=301)
    with open(sess.events.path) as f:
        recs2 = [json.loads(line) for line in f]
    assert len([r for r in recs2 if r["event"] == "hlo_summary"]) == n


def test_agg_summaries_carry_scatter_attribution():
    """The headline shape: a grouped aggregate on the SCATTER strategy
    must name its scatter instructions (this is the instrument the
    item-1 kernel rewrite is judged by)."""
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.sql.agg.strategy": "SCATTER",
    })
    df = (sess.range(0, 3000)
          .select(col("id"),
                  E.Alias(E.Multiply(col("id"), lit(302)), "v"))
          .group_by("v")
          .agg(A.agg(A.Sum(col("id")), "s")))
    df.collect()
    sums = [r for r in sess.events.records()
            if r["event"] == "hlo_summary"]
    assert sums
    assert any(r["scatter_count"] > 0 for r in sums), \
        "SCATTER-strategy agg harvested no scatter-classified fusions"
    clsset = {f["class"] for r in sums for f in r["top_fusions"]}
    assert clsset & {"scatter", "scatter-add"}, clsset


def test_harvest_hlo_tolerates_broken_compiled():
    class NoText:
        pass

    class RaisingText:
        def as_text(self):
            raise RuntimeError("backend refuses")

    class NotHlo:
        def as_text(self):
            return "definitely not an hlo dump"

    for compiled in (NoText(), RaisingText(), NotHlo()):
        assert hlo.harvest_hlo(compiled, "site", "d00d") is None


# ---------------------------------------------------------------------------
# 4. zero overhead when events + obs are both off
# ---------------------------------------------------------------------------
def test_zero_overhead_no_hlo_text_fetched_when_off(monkeypatch):
    fetched = []
    monkeypatch.setattr(
        hlo, "harvest_hlo",
        lambda *a, **k: fetched.append(a) or None)
    parsed = []
    monkeypatch.setattr(
        hlo, "summarize_hlo",
        lambda *a, **k: parsed.append(a) or {})
    sess = TpuSession({})  # defaults: everything off
    rows = _query(sess, hi=8192, mult=303)
    assert rows[0][1] == 8092
    assert fetched == [], "HLO text fetched while planes off"
    assert parsed == [], "HLO parsed while planes off"


# ---------------------------------------------------------------------------
# 5. obs twins
# ---------------------------------------------------------------------------
def test_hlo_summary_has_live_twin_declared():
    from spark_rapids_tpu.obs.registry import EVENT_BACKED_METRICS, METRICS

    fam = EVENT_BACKED_METRICS["hlo_summary"]
    assert fam in METRICS
    assert "tpu_hlo_top_fusion_bytes" in METRICS


def test_obs_twins_scatter_counter_and_fusion_gauge():
    reg = MetricsRegistry()
    obs.install(reg)
    try:
        obs.note_hlo_summary("agg_update", 3, 1 << 20)
        obs.note_hlo_summary("agg_update", 0, 1 << 10)  # smaller: no drop
        assert reg.value("tpu_hlo_scatter_programs",
                         site="agg_update") == 1
        assert reg.value("tpu_hlo_top_fusion_bytes",
                         site="agg_update") == 1 << 20
    finally:
        obs.uninstall()


def test_live_query_sets_obs_twins():
    reg = MetricsRegistry()
    obs.install(reg)
    try:
        sess = TpuSession({"spark.rapids.tpu.sql.agg.strategy": "SCATTER"})
        df = (sess.range(0, 2500)
              .select(col("id"),
                      E.Alias(E.Multiply(col("id"), lit(304)), "v"))
              .group_by("v")
              .agg(A.agg(A.Sum(col("id")), "s")))
        df.collect()
        snap = reg.snapshot()
        assert snap.get("tpu_hlo_scatter_programs"), snap.keys()
        assert snap.get("tpu_hlo_top_fusion_bytes"), snap.keys()
    finally:
        obs.uninstall()


# ---------------------------------------------------------------------------
# 6. tpu_profile: == hlo == section + --diff gates
# ---------------------------------------------------------------------------
def _sum_ev(site, digest, top, total, scatters=0, cls="scatter-add",
            ts=1):
    return {"ts": ts, "event": "hlo_summary", "site": site,
            "digest": digest, "backend": "cpu", "instructions": 10,
            "coverage": 1.0, "total_bytes": total,
            "scatter_count": scatters,
            "top_fusions": [{"name": "fusion.7", "op": "fusion",
                             "class": cls, "bytes": top,
                             "out_bytes": top // 2}],
            "largest_output": {"name": "fusion.7", "bytes": top // 2}}


def _cost_ev(site, digest, bytes_, ts=1):
    return {"ts": ts, "event": "program_cost", "site": site,
            "digest": digest, "backend": "cpu", "trace_ms": 1.0,
            "compile_ms": 1.0, "flops": 1.0, "bytes_accessed": bytes_,
            "temp_bytes": None, "argument_bytes": None,
            "output_bytes": None, "op": "TpuHashAggregateExec"}


def test_hlo_section_names_the_culprit():
    events = [
        _cost_ev("agg_update", "aaa", 19.4e9),
        _sum_ev("agg_update", "aaa", top=12_100_000_000,
                total=15_000_000_000, scatters=2),
    ]
    text = "\n".join(tpu_profile.hlo_section(events))
    assert "== hlo ==" in text
    assert "site=agg_update" in text and "scatters=2" in text
    # the culprit line joins the fusion to the compiler's own figure
    assert ("agg_update: fusion.7 [scatter-add] accounts for "
            "12100.00MB of 19400.00MB (62% of site XLA bytes)" in text)
    assert "largest single fusion" in text
    # no summaries: a placeholder, not an error
    assert "no hlo_summary events" in "\n".join(
        tpu_profile.hlo_section([]))


def test_report_includes_hlo_from_live_log():
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": True})
    _query(sess, mult=305)
    text, violations = tpu_profile.build_report(sess.events.records())
    assert violations == 0
    assert "== hlo ==" in text
    sect = text.split("== hlo ==")[1].split("==")[0]
    assert "site=" in sect, "hlo section empty on a cold run:\n" + text


def test_diff_logs_gates_fusion_bytes_and_scatter_appearance():
    old = [_sum_ev("agg_update", "a", top=1 << 20, total=4 << 20)]
    # 10x growth in the top fusion: REGRESSION
    new = [_sum_ev("agg_update", "a", top=10 << 20, total=40 << 20)]
    text, n = tpu_profile.diff_logs(old, new, threshold=0.2)
    assert n >= 1 and "agg_update.top_fusion_bytes: REGRESSION" in text
    assert "agg_update.hlo_bytes: REGRESSION" in text
    # a scatter lowering APPEARING is structural, gated at any size
    news = [_sum_ev("agg_update", "a", top=1 << 20, total=4 << 20,
                    scatters=1)]
    text, n = tpu_profile.diff_logs(old, news, threshold=0.2)
    assert n == 1 and "agg_update.scatter_count: REGRESSION" in text
    # self-diff is clean
    text, n = tpu_profile.diff_logs(old, list(old), threshold=0.2)
    assert n == 0, text
    # the appearance gate covers a site the OLD log never harvested —
    # the rewrite-introduces-a-new-compile-site scenario must not evade
    # the structural gate via the site intersection
    newsite = [_sum_ev("pallas_update", "p", top=1 << 16, total=1 << 18,
                       scatters=1)]
    text, n = tpu_profile.diff_logs(old, old + newsite, threshold=0.2)
    assert n == 1 and "pallas_update.scatter_count: REGRESSION" in text
    # a scatter-free new site is not a regression
    clean = [_sum_ev("pallas_update", "p", top=1 << 16, total=1 << 18,
                     scatters=0, cls="dot")]
    text, n = tpu_profile.diff_logs(old, old + clean, threshold=0.2)
    assert n == 0, text


# ---------------------------------------------------------------------------
# 7. environment provenance
# ---------------------------------------------------------------------------
def test_environment_info_shape_and_memoization():
    env = envinfo.environment_info()
    for key in ("backend", "device_kind", "device_count", "jax_version",
                "host_cores"):
        assert key in env, key
    assert env["device_count"] >= 1
    # memoized: same content, and the returned dict is a copy (a caller
    # mutating it cannot poison later events)
    env["backend"] = "poisoned"
    assert envinfo.environment_info()["backend"] != "poisoned"
    assert "backend=" in envinfo.describe(env)
    assert envinfo.describe(None) == "backend=?"


_ENV_CASES = [
    # (a, b, differ)
    ({"backend": "cpu", "device_kind": "cpu"},
     {"backend": "cpu", "device_kind": "cpu"}, False),
    ({"backend": "cpu", "device_kind": "cpu"},
     {"backend": "tpu", "device_kind": "TPU v5p"}, True),
    ({"backend": "tpu", "device_kind": "TPU v4"},
     {"backend": "tpu", "device_kind": "TPU v5p"}, True),
    # missing blocks (pre-provenance logs) never differ
    (None, {"backend": "tpu", "device_kind": "TPU v5p"}, False),
    ({"backend": "cpu", "device_kind": "cpu"}, None, False),
    (None, None, False),
]


def test_environments_differ_rule_and_profiler_twin_agree():
    for a, b, want in _ENV_CASES:
        assert envinfo.environments_differ(a, b) is want, (a, b)
        # the offline tool's duplicated-by-design copy must agree
        assert tpu_profile._envs_differ(a, b) is want, (a, b)


def test_diff_warns_loudly_on_environment_mismatch():
    cpu_env = {"backend": "cpu", "device_kind": "cpu",
               "device_count": 1, "jax_version": "0.4.37"}
    tpu_env = {"backend": "tpu", "device_kind": "TPU v5p",
               "device_count": 8, "jax_version": "0.4.37"}

    def qstart(env):
        return {"ts": 1, "event": "query_start", "query_id": 1,
                "plan_digest": "d", "sql_hash": "h", "env": env}

    text, n = tpu_profile.diff_logs([qstart(cpu_env)], [qstart(tpu_env)],
                                    threshold=0.2)
    assert "ENVIRONMENTS DIFFER" in text
    assert n == 0, "env mismatch is a warning, not a regression"
    # same env: silent
    text, _ = tpu_profile.diff_logs([qstart(cpu_env)],
                                    [qstart(dict(cpu_env))], threshold=0.2)
    assert "ENVIRONMENTS DIFFER" not in text


def test_query_start_rides_env_and_status_serves_it(tmp_path):
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.metrics.http.enabled": True,
    })
    try:
        _query(sess, mult=306)
        qs = [r for r in sess.events.records()
              if r["event"] == "query_start"]
        assert qs and qs[0].get("env"), "query_start lost its env block"
        assert qs[0]["env"]["backend"] == envinfo.environment_info()[
            "backend"]
        # /status serves the same block; tpu_top renders it
        import urllib.request

        st = json.loads(urllib.request.urlopen(
            sess.obs_address + "/status").read())
        assert st.get("env", {}).get("backend") == qs[0]["env"]["backend"]
        _tspec = importlib.util.spec_from_file_location(
            "tpu_top", os.path.join(REPO, "tools", "tpu_top.py"))
        tpu_top = importlib.util.module_from_spec(_tspec)
        _tspec.loader.exec_module(tpu_top)
        screen = tpu_top.render_status(st)
        assert "env  backend=" in screen
    finally:
        obs.shutdown()


# ---------------------------------------------------------------------------
# 8. conf-declared top-K reaches the harvest
# ---------------------------------------------------------------------------
def test_conf_top_k_controls_summary_width():
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.hlo.topK": 1,
    })
    _query(sess, mult=307)
    sums = [r for r in sess.events.records()
            if r["event"] == "hlo_summary"]
    assert sums
    assert all(len(r["top_fusions"]) <= 1 for r in sums)
    hlo._TOP_K = None  # don't leak the narrowed width into later tests


# ---------------------------------------------------------------------------
# 9. direct-address join-table idiom (round 14): its own class
# ---------------------------------------------------------------------------
def test_join_table_build_classified_distinct_from_scatter():
    """The DIRECT join tier builds its (first, count) tables with a
    scatter-MIN of an IOTA (row indices) plus a scatter-ADD of ones over
    the same table shape. Both must classify 'join-table' — a
    deliberately chosen DIRECT join is not the scatter-add aggregation
    amplifier, and must contribute ZERO to scatter_count (the --diff
    appearance gate's subject)."""
    text = """\
HloModule jit_fastbuild

%min_s32 (a: s32[], b: s32[]) -> s32[] {
  %a = s32[] parameter(0)
  %b = s32[] parameter(1)
  ROOT %m = s32[] minimum(s32[] %a, s32[] %b)
}

%add_s32 (a2: s32[], b2: s32[]) -> s32[] {
  %a2 = s32[] parameter(0)
  %b2 = s32[] parameter(1)
  ROOT %s = s32[] add(s32[] %a2, s32[] %b2)
}

ENTRY %main (off: s64[4096,1], finit: s32[16384], cinit: s32[16384], ones: s32[4096]) -> (s32[16384], s32[16384]) {
  %off = s64[4096,1]{1,0} parameter(0)
  %finit = s32[16384]{0} parameter(1)
  %cinit = s32[16384]{0} parameter(2)
  %ones = s32[4096]{0} parameter(3)
  %bidx = s32[4096]{0} iota(), iota_dimension=0
  %first = s32[16384]{0} scatter(s32[16384]{0} %finit, s64[4096,1]{1,0} %off, s32[4096]{0} %bidx), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%min_s32
  %cnt = s32[16384]{0} scatter(s32[16384]{0} %cinit, s64[4096,1]{1,0} %off, s32[4096]{0} %ones), update_window_dims={}, inserted_window_dims={0}, scatter_dims_to_operand_dims={0}, index_vector_dim=1, to_apply=%add_s32
  ROOT %out = (s32[16384]{0}, s32[16384]{0}) tuple(s32[16384]{0} %first, s32[16384]{0} %cnt)
}
"""
    s = hlo.summarize_hlo(text)
    assert s["coverage"] == 1.0
    by_name = {r["name"]: r for r in s["top_fusions"]}
    assert by_name["first"]["class"] == "join-table", by_name
    assert by_name["cnt"]["class"] == "join-table", by_name
    assert s["scatter_count"] == 0, s["top_fusions"]


def test_compiled_direct_join_build_classifies_join_table():
    """The REAL compiled direct-address build (this backend's dialect —
    on CPU, jax 0.9.0, a pair of `wrapped_scatter` fusions whose update
    stream is an operand-less `wrapped_iota` fusion) must classify
    join-table end to
    end, and a min+count scatter AGGREGATION over data values must NOT
    (the iota update stream is the discriminator)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    def build_tables(key64, ok):
        nb = key64.shape[0]
        tbl = 4 * nb
        kmin = jnp.min(jnp.where(ok, key64, jnp.uint64(2 ** 64 - 1)))
        diffu = key64 - kmin
        off = jnp.where(ok & (diffu < jnp.uint64(tbl)), diffu,
                        jnp.uint64(tbl)).astype(jnp.int64)
        bidx = jnp.arange(nb, dtype=jnp.int32)
        first = jnp.full(tbl, nb, jnp.int32).at[off].min(bidx, mode="drop")
        cnt = jnp.zeros(tbl, jnp.int32).at[off].add(1, mode="drop")
        return first, cnt

    k = jnp.asarray(np.arange(2048, dtype=np.uint64))
    ok = jnp.ones(2048, bool)
    c = jax.jit(build_tables).lower(k, ok).compile()
    s = hlo.summarize_hlo(c.as_text(), top_k=16)
    assert s["scatter_count"] == 0, s["top_fusions"]
    assert any(r["class"] == "join-table" for r in s["top_fusions"])

    def agg_scatters(seg, vals):
        B = 128
        mn = jnp.full(B, 2 ** 31 - 1, jnp.int32).at[seg].min(
            vals, mode="drop")
        cnt = jnp.zeros(B, jnp.int32).at[seg].add(1, mode="drop")
        return mn, cnt

    seg = jnp.asarray((np.arange(2048) % 128).astype(np.int32))
    vals = jnp.asarray((np.arange(2048) * 7 % 999).astype(np.int32))
    c2 = jax.jit(agg_scatters).lower(seg, vals).compile()
    s2 = hlo.summarize_hlo(c2.as_text(), top_k=16)
    assert s2["scatter_count"] == 2, s2["top_fusions"]
    assert not any(r["class"] == "join-table" for r in s2["top_fusions"])
