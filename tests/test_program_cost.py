"""Compiled-program cost plane (spark_rapids_tpu/xla_cost.py) + the
roofline observability riding on it.

Pins the contracts ISSUE 10 introduced:
  1. ``program_cost`` round-trips the JSONL sink with its full schema
     and is emitted EXACTLY ONCE per compile miss — a warm rerun
     (recompile-guard style) emits nothing;
  2. missing-cost-key tolerance: a backend reporting no cost/memory
     analysis degrades every consumer (event, roofline report,
     explain_metrics, bench block) to partial rows, never an error;
  3. the tpu_profile '== roofline ==' section renders achieved GB/s /
     FLOP/s vs peaks, a limiter classification, the
     furthest-below-roofline program, and the analyzer-vs-XLA byte
     delta;
  4. the analyzer-bound vs XLA-bytes cross-check runs on a bounded plan
     (harness records it; XLA above the bound is a lead, not a failure);
  5. zero overhead: with events AND obs off (and FORCE_HARVEST unset)
     cost_analysis is never called and nothing is wrapped;
  6. obs twins: compile-seconds-by-site counter + largest-temp gauge;
  7. Perfetto: program_cost renders as a real duration span on the
     compile track plus a cumulative compile-seconds counter;
  8. --diff: grown XLA bytes / peak temp flag a regression, compile-time
     jitter below the 1ms floor never does, and bench JSONs compare
     hbm_frac_xla only when both runs carry it.
"""
import importlib.util
import json
import os

import pytest

from spark_rapids_tpu import events as EV
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
    """Every test starts and ends with events/obs uninstalled and the
    harvest hook off (other suites set FORCE_HARVEST via the harness)."""
    EV.uninstall()
    obs.uninstall()
    prev = XC.FORCE_HARVEST
    XC.FORCE_HARVEST = False
    yield
    XC.FORCE_HARVEST = prev
    EV.uninstall()
    obs.uninstall()


def _query(sess, hi=2048, mult=2):
    """The pipeline caches are PROCESS-global: a test that needs a cold
    compile must use a (hi, mult) pair no other test (or suite) has run,
    or it inherits warm programs and harvests nothing."""
    df = (sess.range(0, hi)
          .where(E.GreaterThanOrEqual(col("id"), lit(100)))
          .select(col("id"),
                  E.Alias(E.Multiply(col("id"), lit(mult)), "v"))
          .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))
    return df.collect()


# ---------------------------------------------------------------------------
# 1. schema + exactly-one-per-miss
# ---------------------------------------------------------------------------
def test_program_cost_schema_roundtrip(tmp_path):
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.metrics.deviceSync.enabled": True,
    })
    _query(sess, mult=101)
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    costs = [r for r in recs if r["event"] == "program_cost"]
    assert costs, "no program_cost events from a cold session"
    for r in costs:
        # every REQUIRED field present (None allowed — backends differ)
        for field in EV.EVENT_TYPES["program_cost"]:
            assert field in r, f"program_cost missing {field}: {r}"
        assert r["site"] and r["digest"]
        assert r["trace_ms"] >= 0 and r["compile_ms"] >= 0
        # the CPU backend DOES report these two; assert one real harvest
    assert any(r.get("bytes_accessed") for r in costs)
    assert any(r.get("op") for r in costs), "no op attribution"


def test_exactly_one_cost_event_per_compile_miss():
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": True})
    _query(sess, mult=102)
    recs = sess.events.records()
    costs = [r for r in recs if r["event"] == "program_cost"]
    misses = [r for r in recs if r["event"] == "compile_miss"]
    assert costs
    # at most one cost event per miss, and no two costs share a digest
    assert len(costs) <= len(misses)
    digests = [r["digest"] for r in costs]
    assert len(digests) == len(set(digests))
    # recompile-guard style: the warm rerun emits NOTHING new
    n = len(costs)
    _query(sess, mult=102)
    costs2 = [r for r in sess.events.records()
              if r["event"] == "program_cost"]
    assert len(costs2) == n, "warm rerun harvested again"


# ---------------------------------------------------------------------------
# 2. missing-key tolerance (the CPU-fallback / exotic-backend contract)
# ---------------------------------------------------------------------------
class _NoCostCompiled:
    def cost_analysis(self):
        raise NotImplementedError("backend reports no cost analysis")

    def memory_analysis(self):
        return None


class _WeirdListCompiled:
    def cost_analysis(self):
        return []  # empty list: some backends return one dict per module

    def memory_analysis(self):
        raise RuntimeError("unsupported")


def test_harvest_tolerates_missing_cost_keys():
    for compiled in (_NoCostCompiled(), _WeirdListCompiled()):
        cost = XC.harvest_compiled(compiled)
        for field in XC.COST_FIELDS:
            assert cost[field] is None
    # a record built from the degraded harvest still emits + reports
    logger = EV.EventLogger(ring_size=64, path=None)
    logger.enabled = True
    EV.install(logger)
    XC.note_program_cost("degraded_site", "d00d", 1_000_000, 2_000_000,
                         XC.harvest_compiled(_NoCostCompiled()), op="OpX")
    (rec,) = [r for r in logger.records() if r["event"] == "program_cost"]
    assert rec["bytes_accessed"] is None and rec["temp_bytes"] is None
    # the roofline section degrades to a partial row, not an error
    lines = tpu_profile.roofline_section([rec], [])
    text = "\n".join(lines)
    assert "degraded_site" in text
    assert "no byte/flop cost keys" in text


# ---------------------------------------------------------------------------
# 3. roofline golden render
# ---------------------------------------------------------------------------
def _mk(event, **kw):
    kw.setdefault("ts", _mk.ts)
    _mk.ts += 1000
    kw["event"] = event
    return kw


_mk.ts = 1_000_000


def test_roofline_section_golden():
    events = [
        _mk("program_cost", site="fused_chain", digest="aaa", backend="cpu",
            trace_ms=10.0, compile_ms=20.0, flops=4.0e6,
            bytes_accessed=8.0e6, temp_bytes=1 << 20,
            argument_bytes=1 << 10, output_bytes=1 << 10,
            op="TpuProjectExec"),
        _mk("program_cost", site="agg_plan", digest="bbb", backend="cpu",
            trace_ms=5.0, compile_ms=15.0, flops=2.0e9,
            bytes_accessed=1.0e6, temp_bytes=2 << 20,
            argument_bytes=1 << 10, output_bytes=1 << 10,
            op="TpuHashAggregateExec"),
        # device lanes: project 8ms, aggregate 2ms
        _mk("op_span", op="TpuProjectExec", section="", start=0,
            dur=8_000_000, lane="device"),
        _mk("op_span", op="TpuHashAggregateExec", section="", start=0,
            dur=2_000_000, lane="device"),
    ]
    queries = [{"analysis": {"bytes_by_op": {"TpuProjectExec": 4_000_000}},
                "events": events, "query_id": 1}]
    lines = tpu_profile.roofline_section(
        events, queries, peak_gbps=100.0, peak_tflops=1.0)
    text = "\n".join(lines)
    assert "== roofline ==" in text
    # project: 8e6 bytes / 8e6 ns = 1 GB/s = 1% of 100 GB/s peak;
    # flops 4e6/8e6ns = 0.5 GFLOP/s = 0.05% of 1 TFLOP/s -> bandwidth
    assert ("site=fused_chain op=TpuProjectExec programs=1 "
            "compile=30.0ms" in text)
    assert "achieved[device]=1.000GB/s (1.00% of peak)" in text
    assert "-> bandwidth-limited" in text
    # aggregate: 2e9 flops / 2e6 ns = 1000 GFLOP/s = 100% of 1 TFLOP/s;
    # bytes 1e6/2e6ns = 0.5GB/s = 0.5% -> compute
    assert "-> compute-limited" in text
    # analyzer delta: XLA 8MB > bound 4MB names the lead
    assert ("TpuProjectExec: XLA touches 8.00MB > analyzer bound 4.00MB"
            in text)
    assert "materialized intermediates" in text
    # project is furthest below roofline (1% < 100%)
    assert "furthest below roofline: fused_chain at 1.00% of peak" in text


def test_roofline_peaks_stay_in_sync_with_engine():
    # the offline tool duplicates BACKEND_PEAKS to avoid importing jax;
    # the engine's table is the source of truth
    assert tpu_profile.BACKEND_PEAKS == XC.BACKEND_PEAKS


def test_report_includes_roofline_from_live_log():
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.metrics.deviceSync.enabled": True,
    })
    _query(sess, mult=103)
    text, violations = tpu_profile.build_report(sess.events.records())
    assert violations == 0
    assert "== roofline ==" in text
    assert "site=" in text.split("== roofline ==")[1].split("==")[0], (
        "roofline section empty on a cold run:\n" + text)


# ---------------------------------------------------------------------------
# 4. analyzer-bound vs XLA-bytes cross-check on a bounded plan
# ---------------------------------------------------------------------------
def test_bounded_plan_cross_check_records_xla_vs_analyzer():
    from tests.harness import assert_tpu_and_cpu_equal

    captured = []

    def build(sess):
        captured.append(sess)
        return (sess.range(0, 777)
                .select(col("id"),
                        E.Alias(E.Multiply(col("id"), lit(37)), "w")))

    assert_tpu_and_cpu_equal(build)
    # build runs for the CPU session, THE TPU SESSION, and possibly an
    # elision-off differential session — the cross-check lands on #2
    sess = captured[1]
    comp = getattr(sess, "last_xla_vs_analyzer", None)
    assert comp, "harness did not record the XLA-vs-analyzer comparison"
    for op, (xla_bytes, bound) in comp.items():
        assert xla_bytes > 0
        # bounds exist for the fully-modeled ops of this bounded plan
        if bound is not None:
            assert bound > 0


# ---------------------------------------------------------------------------
# 5. zero overhead when events + obs are both off
# ---------------------------------------------------------------------------
def test_zero_overhead_no_cost_analysis_when_off(monkeypatch):
    calls = []

    def spy(compiled):
        calls.append(compiled)
        return {k: None for k in XC.COST_FIELDS}

    monkeypatch.setattr(XC, "harvest_compiled", spy)
    wrapped = []
    orig_wrap = XC.wrap

    def wrap_spy(built, site, key):
        out = orig_wrap(built, site, key)
        if out is not built:
            wrapped.append(site)
        return out

    monkeypatch.setattr(XC, "wrap", wrap_spy)
    sess = TpuSession({})  # defaults: everything off
    rows = _query(sess, hi=4096, mult=104)
    assert rows[0][1] == 3996
    assert calls == [], "cost_analysis harvested while planes off"
    assert wrapped == [], f"CostProbe wrapped while planes off: {wrapped}"


# ---------------------------------------------------------------------------
# 6. obs twins
# ---------------------------------------------------------------------------
def test_obs_twins_compile_seconds_and_temp_gauge():
    reg = MetricsRegistry()
    obs.install(reg)
    try:
        sess = TpuSession({})
        _query(sess, hi=8192, mult=105)
        sites = [k for k in reg.snapshot().get("tpu_compile_seconds", {})]
        assert any("phase=trace" in s for s in sites), sites
        assert any("phase=compile" in s for s in sites), sites
        temps = reg.snapshot().get("tpu_program_temp_bytes", {})
        assert temps, "largest-temp gauge never set"
        # high-water semantics: a smaller write never lowers the gauge
        site = next(iter(temps))
        label = site.split("=", 1)[1]
        before = temps[site]
        reg.set_gauge_max("tpu_program_temp_bytes", before - 1, site=label)
        assert reg.value("tpu_program_temp_bytes", site=label) == before
    finally:
        obs.uninstall()


def test_program_cost_has_live_twin_declared():
    from spark_rapids_tpu.obs.registry import EVENT_BACKED_METRICS, METRICS

    fam = EVENT_BACKED_METRICS["program_cost"]
    assert fam in METRICS


# ---------------------------------------------------------------------------
# 7. Perfetto: compile spans + cumulative compile-seconds counter
# ---------------------------------------------------------------------------
def test_perfetto_compile_track_and_counter():
    sess = TpuSession({"spark.rapids.tpu.eventLog.enabled": True})
    _query(sess, mult=106)
    trace = EV.chrome_trace(sess.events.records())
    spans = [e for e in trace["traceEvents"]
             if e.get("ph") == "X"
             and str(e.get("name", "")).startswith("compile:")]
    assert spans, "compile misses still invisible in the trace"
    for s in spans:
        assert s["dur"] > 0
        assert s["args"]["trace_ms"] is not None
    counters = [e for e in trace["traceEvents"]
                if e.get("ph") == "C" and e["name"] == "compile_seconds"]
    assert len(counters) == len(spans)
    secs = [c["args"]["seconds"] for c in counters]
    assert secs == sorted(secs) and secs[-1] > 0  # cumulative


# ---------------------------------------------------------------------------
# 8. --diff gates
# ---------------------------------------------------------------------------
def _cost_ev(site, bytes_, temp, compile_ms=10.0, ts=1):
    return {"ts": ts, "event": "program_cost", "site": site, "digest": "d",
            "backend": "cpu", "trace_ms": 1.0, "compile_ms": compile_ms,
            "flops": 1.0, "bytes_accessed": bytes_, "temp_bytes": temp,
            "argument_bytes": 0, "output_bytes": 0}


def test_diff_flags_grown_xla_bytes_and_temp():
    old = [_cost_ev("agg_plan", 1.0e6, 1 << 20)]
    new = [_cost_ev("agg_plan", 2.0e6, 1 << 20)]
    text, n = tpu_profile.diff_logs(old, new, threshold=0.2)
    assert n == 1 and "agg_plan.xla_bytes: REGRESSION" in text
    new_temp = [_cost_ev("agg_plan", 1.0e6, 4 << 20)]
    text, n = tpu_profile.diff_logs(old, new_temp, threshold=0.2)
    assert n == 1 and "agg_plan.peak_temp: REGRESSION" in text


def test_diff_ignores_compile_jitter_below_noise_floor():
    # 0.4ms -> 0.9ms is >2x but under the 1ms floor: jitter, not a
    # regression; bytes/temp identical
    old = [_cost_ev("sort", 1.0e6, 1 << 20, compile_ms=0.4)]
    new = [_cost_ev("sort", 1.0e6, 1 << 20, compile_ms=0.9)]
    text, n = tpu_profile.diff_logs(old, new, threshold=0.2)
    assert n == 0, text
    # but a REAL compile blowup (10ms -> 100ms) flags
    big = [_cost_ev("sort", 1.0e6, 1 << 20, compile_ms=100.0)]
    old10 = [_cost_ev("sort", 1.0e6, 1 << 20, compile_ms=10.0)]
    text, n = tpu_profile.diff_logs(old10, big, threshold=0.2)
    assert n == 1 and "sort.compile: REGRESSION" in text


# ---------------------------------------------------------------------------
# 9. explain_metrics lane labeling (the satellite fix) + xla columns
# ---------------------------------------------------------------------------
def test_explain_metrics_labels_bandwidth_lane():
    # deviceSync ON: the device lane feeds the column and says so
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.metrics.deviceSync.enabled": True,
    })
    _query(sess, mult=107)
    text = sess.explain_metrics()
    assert "hbm_gbps[device]=" in text
    assert "hbm_gbps[host]=" not in text.split("\n")[0]
    # cost plane was on (events): the xla columns and harvest footer ride
    assert "xla_bytes=" in text
    assert "programs harvested:" in text
    # deviceSync OFF: the host lane feeds it and the label SAYS host —
    # an unlabeled figure here silently overstated bandwidth (async
    # dispatch makes host time << device work)
    sess2 = TpuSession({})
    _query(sess2, hi=8192, mult=108)
    text2 = sess2.explain_metrics()
    assert "hbm_gbps[host]=" in text2
    assert "hbm_gbps[device]=" not in text2


def test_format_metrics_prefers_device_lane():
    from spark_rapids_tpu.exec.base import (
        BYTES_TOUCHED,
        OP_TIME_DEVICE,
        TOTAL_TIME,
        TpuExec,
    )
    from spark_rapids_tpu.conf import RapidsConf

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            return None

    node = Dummy(RapidsConf({}))
    node.metric(BYTES_TOUCHED, "bytes").add(10_000_000)
    node.metric(TOTAL_TIME, "ns").add(1_000_000)       # 10 GB/s via host
    from spark_rapids_tpu.exec.base import format_metrics

    text = format_metrics(node)
    assert "hbm_gbps[host]=10.00" in text
    node.metric(OP_TIME_DEVICE, "ns").add(10_000_000)  # 1 GB/s via device
    text = format_metrics(node)
    assert "hbm_gbps[device]=1.00" in text
    assert "hbm_gbps[host]" not in text


# ---------------------------------------------------------------------------
# 10. review fixes: conf peaks reach the offline tool; per-query bounds
# ---------------------------------------------------------------------------
def test_conf_declared_peaks_ride_events_into_roofline():
    # the offline profiler has no RapidsConf — the only channel for the
    # roofline.* confs is the harvested event itself
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.roofline.peakHbmGBps": 200.0,
        "spark.rapids.tpu.roofline.peakTflops": 2.0,
    })
    _query(sess, mult=211)
    costs = [r for r in sess.events.records()
             if r["event"] == "program_cost"]
    assert costs and all(r.get("peak_hbm_gbps") == 200.0
                         and r.get("peak_tflops") == 2.0 for r in costs)
    text, _ = tpu_profile.build_report(sess.events.records())
    assert "peaks: 200 GB/s, 2.0 TFLOP/s" in text
    # CLI flags still override the logged peaks
    text, _ = tpu_profile.build_report(sess.events.records(),
                                       peak_gbps=50.0)
    assert "peaks: 50 GB/s" in text
    import spark_rapids_tpu.xla_cost as XC2

    XC2._CONF_PEAKS = None  # don't leak conf peaks into later tests


def test_roofline_analyzer_delta_is_per_query():
    # ten queries, each compiling a 100MB program against a 150MB bound:
    # the old log-wide sum printed 1000MB > 150MB ("+850MB materialized
    # intermediates") for a kernel that materializes nothing
    queries = []
    events = []
    for qid in range(10):
        ev = _mk("program_cost", site="fused_chain", digest=f"q{qid}",
                 backend="cpu", trace_ms=1.0, compile_ms=1.0, flops=1.0,
                 bytes_accessed=100e6, temp_bytes=None,
                 argument_bytes=None, output_bytes=None,
                 op="TpuProjectExec")
        events.append(ev)
        queries.append({"query_id": qid, "events": [ev],
                        "analysis": {"bytes_by_op":
                                     {"TpuProjectExec": 150_000_000}}})
    lines = tpu_profile.roofline_section(events, queries,
                                         peak_gbps=100.0, peak_tflops=1.0)
    text = "\n".join(lines)
    assert "XLA touches 100.00MB <= analyzer bound 150.00MB" in text
    assert "materialized intermediates" not in text


def test_format_metrics_same_class_nodes_print_cost_once():
    import spark_rapids_tpu.xla_cost as XC2
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec.base import (
        OP_TIME_DEVICE,
        TpuExec,
        format_metrics,
    )

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            return None

    seq = XC2.snapshot()
    XC2.note_program_cost("fused_chain", "d1", 1000, 1000,
                          {"bytes_accessed": 8.0e6, "flops": 1.0},
                          op="Dummy")
    parent = Dummy(RapidsConf({}))
    child = Dummy(RapidsConf({}))
    parent.children = [child]
    parent.metric(OP_TIME_DEVICE, "ns").add(1_000_000)
    child.metric(OP_TIME_DEVICE, "ns").add(1_000_000)
    text = format_metrics(parent, cost_since=seq)
    # the class-wide harvest prints on ONE line, and with two Dummy
    # nodes no single device lane is the right denominator for it
    assert text.count("xla_bytes=8.0MB") == 1, text
    assert "xla_gbps" not in text, text


def test_roofline_shared_op_sites_get_one_combined_line():
    # agg_update and agg_plan both attribute to TpuHashAggregateExec:
    # each site dividing its bytes by the op's WHOLE device lane would
    # double-count time and understate both rows — the group gets ONE
    # combined achieved line over the summed bytes instead
    events = [
        _mk("program_cost", site="agg_update", digest="u", backend="cpu",
            trace_ms=1.0, compile_ms=1.0, flops=1.0e6,
            bytes_accessed=6.0e6, temp_bytes=None, argument_bytes=None,
            output_bytes=None, op="TpuHashAggregateExec"),
        _mk("program_cost", site="agg_plan", digest="p", backend="cpu",
            trace_ms=1.0, compile_ms=1.0, flops=1.0e6,
            bytes_accessed=2.0e6, temp_bytes=None, argument_bytes=None,
            output_bytes=None, op="TpuHashAggregateExec"),
        _mk("op_span", op="TpuHashAggregateExec", section="", start=0,
            dur=4_000_000, lane="device"),
    ]
    lines = tpu_profile.roofline_section(events, [], peak_gbps=100.0,
                                         peak_tflops=1.0)
    text = "\n".join(lines)
    # no per-site achieved figures for the shared op ...
    for line in text.splitlines():
        if line.strip().startswith("site="):
            assert "achieved" not in line, line
    # ... one combined line: (6e6+2e6) bytes / 4e6 ns = 2 GB/s
    assert ("op=TpuHashAggregateExec sites=agg_plan+agg_update "
            "achieved[device]=2.000GB/s (2.00% of peak)" in text), text
    assert ("furthest below roofline: TpuHashAggregateExec "
            "(agg_plan+agg_update)" in text), text
