"""Structured event log, Perfetto export, and the offline profiler.

Reference analog: the Spark event log + rapids-4-spark profiling tool
(SURVEY: tools layer). Pins four contracts:
  1. every event type round-trips through the JSONL sink with its full
     declared schema (events.EVENT_TYPES is the single source of truth);
  2. export_trace() emits valid Chrome trace-event JSON with
     monotonically ordered, non-negative spans;
  3. tools/tpu_profile.py parses a log into the report (golden sections,
     forecast-vs-actual with zero violations on a healthy run, VIOLATION
     + nonzero exit on a poisoned one) and --diff flags regressions;
  4. with event logging off (the default) NOTHING is emitted — no ring
     entries, no sink writes, no EventLogger.emit calls at all.
"""
import importlib.util
import json
import os
import sys

import pytest

from spark_rapids_tpu import events as EV
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.sql import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "tpu_profile", os.path.join(REPO, "tools", "tpu_profile.py"))
tpu_profile = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpu_profile)


@pytest.fixture(autouse=True)
def clean_logger():
    """Every test leaves the process-global logger uninstalled."""
    EV.uninstall()
    yield
    EV.uninstall()


def _dummy_value(field):
    """A JSON-typed placeholder per schema field (shape matters, not
    semantics: lists for list fields, strings for names, ints otherwise)."""
    if field in ("fallbacks", "warnings"):
        return [{"op": "X", "reasons": ["r"]}] if field == "fallbacks" else ["w"]
    if field in ("site_forecast", "bytes_by_op"):
        return {"site": 1}
    if field in ("plan_digest", "sql_hash", "op", "section", "lane", "site",
                 "direction", "kind", "codec"):
        return "x"
    if field in ("on_tpu", "bounded"):
        return True
    return 7


def _run_query(sess):
    df = (sess.range(0, 2048)
          .where(E.GreaterThanOrEqual(col("id"), lit(100)))
          .select(col("id"), E.Alias(E.Multiply(col("id"), lit(2)), "v"))
          .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))
    return df.collect()


# ---------------------------------------------------------------------------
# 1. schema round-trip
# ---------------------------------------------------------------------------
def test_every_event_type_roundtrips_through_jsonl(tmp_path):
    logger = EV.EventLogger(
        RapidsConf({"spark.rapids.tpu.eventLog.dir": str(tmp_path)}))
    emitted = {}
    for etype, fields in EV.EVENT_TYPES.items():
        payload = {f: _dummy_value(f) for f in fields}
        logger.emit(etype, **payload)
        emitted[etype] = payload
    logger.close()
    with open(logger.path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["event"] for r in recs] == list(EV.EVENT_TYPES)
    last_ts = 0
    for r in recs:
        assert isinstance(r["ts"], int) and r["ts"] >= last_ts
        last_ts = r["ts"]
        for field in EV.EVENT_TYPES[r["event"]]:
            assert r[field] == emitted[r["event"]][field], (
                f"{r['event']}.{field} did not round-trip")


def test_ring_buffer_fallback_without_dir():
    # no dir: enabled via eventLog.enabled, events land ONLY in the ring
    logger = EV.EventLogger(RapidsConf({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.eventLog.ringBuffer.size": 4}))
    assert logger.enabled and logger.path is None
    for i in range(10):
        logger.emit("compile_miss", site=f"s{i}", total=i)
    recs = logger.records()
    assert len(recs) == 4  # ring bound holds
    assert [r["site"] for r in recs] == ["s6", "s7", "s8", "s9"]


# ---------------------------------------------------------------------------
# 2. query lifecycle through a real session
# ---------------------------------------------------------------------------
def test_query_lifecycle_lands_in_jsonl(tmp_path):
    sess = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    rows = _run_query(sess)
    assert rows[0][1] == 1948  # count(id >= 100) over range(2048)
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    kinds = [r["event"] for r in recs]
    for expected in ("query_start", "plan_tagged", "plan_analysis",
                     "op_span", "op_batch", "query_end"):
        assert expected in kinds, f"missing {expected} in {sorted(set(kinds))}"
    qs = next(r for r in recs if r["event"] == "query_start")
    qe = next(r for r in recs if r["event"] == "query_end")
    assert qe["query_id"] == qs["query_id"] and qe["rows"] == 1
    assert qe["dur"] > 0
    # single-threaded session: the log is time-ordered as written
    ts = [r["ts"] for r in recs]
    assert ts == sorted(ts)
    # the analyzer's forecast rode along for the offline cross-check
    pa = next(r for r in recs if r["event"] == "plan_analysis")
    assert pa["bounded"] is True and isinstance(pa["site_forecast"], dict)


def test_device_lane_spans_with_device_sync(tmp_path):
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.metrics.deviceSync.enabled": True})
    _run_query(sess)
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    lanes = {r["lane"] for r in recs if r["event"] == "op_span"}
    assert lanes == {"host", "device"}  # the two timeline lanes
    dev = [r for r in recs
           if r["event"] == "op_span" and r["lane"] == "device"]
    assert all(r["section"] == "device_wait" and r["dur"] >= 0 for r in dev)


# ---------------------------------------------------------------------------
# 3. Perfetto export
# ---------------------------------------------------------------------------
def test_export_trace_is_valid_chrome_trace(tmp_path):
    # ring-buffer-only session (no dir): export still works
    sess = TpuSession({
        "spark.rapids.tpu.eventLog.enabled": True,
        "spark.rapids.tpu.metrics.deviceSync.enabled": True})
    _run_query(sess)
    out = str(tmp_path / "trace.json")
    sess.export_trace(out)
    with open(out) as f:
        trace = json.load(f)
    evs = trace["traceEvents"]
    assert evs, "empty trace"
    spans = [e for e in evs if e.get("ph") == "X"]
    assert spans, "no spans in trace"
    for e in spans:
        assert e["ts"] >= 0 and e["dur"] >= 0
    # after the thread-name metadata, events are monotonically ordered
    body = [e for e in evs if e.get("ph") != "M"]
    ts = [e["ts"] for e in body]
    assert ts == sorted(ts)
    names = {e["args"]["name"] for e in evs if e.get("ph") == "M"}
    assert any("[device]" in n for n in names)  # separate device track
    # the compile-miss counter track appears iff the run compiled (a warm
    # process-wide pipeline cache legitimately misses nothing)
    misses = [r for r in sess.events.records()
              if r["event"] == "compile_miss"]
    counters = {e["name"] for e in evs if e.get("ph") == "C"}
    assert ("compile_misses" in counters) == bool(misses)
    # a query span wraps the op spans
    assert any(e["name"].startswith("query ") for e in spans)


def test_export_trace_raises_when_disabled():
    sess = TpuSession({})
    with pytest.raises(RuntimeError, match="event logging is off"):
        sess.export_trace("/tmp/never-written.json")


# ---------------------------------------------------------------------------
# 4. the offline profiler
# ---------------------------------------------------------------------------
def _canned_events(byte_bound=1000, measured_bytes=512):
    """A minimal healthy log: one bounded query, two ops, one compile
    miss, a spill, shuffle traffic."""
    t = 1_000_000
    return [
        {"ts": t, "event": "query_start", "query_id": 1,
         "plan_digest": "abc", "sql_hash": "def"},
        {"ts": t + 1, "event": "plan_tagged", "query_id": 1, "on_tpu": True,
         "fallbacks": []},
        {"ts": t + 2, "event": "plan_analysis", "query_id": 1,
         "bounded": True, "site_forecast": {"project": 1},
         "bytes_by_op": {"TpuProjectExec": byte_bound,
                         "TpuRangeExec": 4096},
         "peak_hbm": 8192, "budget": None, "warnings": []},
        {"ts": t + 10, "event": "compile_miss", "site": "project",
         "total": 1},
        {"ts": t + 20, "event": "op_span", "op": "TpuRangeExec",
         "section": "", "start": t + 15, "dur": 3_000_000, "lane": "host"},
        {"ts": t + 30, "event": "op_span", "op": "TpuProjectExec",
         "section": "", "start": t + 25, "dur": 8_000_000, "lane": "host"},
        {"ts": t + 31, "event": "op_span", "op": "TpuProjectExec",
         "section": "device_wait", "start": t + 30, "dur": 5_000_000,
         "lane": "device"},
        {"ts": t + 40, "event": "op_batch", "op": "TpuRangeExec",
         "rows": 64, "bytes": 2048},
        {"ts": t + 41, "event": "op_batch", "op": "TpuProjectExec",
         "rows": 64, "bytes": measured_bytes},
        {"ts": t + 50, "event": "spill", "kind": "device_to_host",
         "bytes": 4096, "device_bytes": 1024},
        {"ts": t + 60, "event": "shuffle_write", "shuffle_id": 1,
         "map_id": 0, "reduce_id": 0, "rows": 64, "bytes": 800,
         "codec": "none"},
        {"ts": t + 61, "event": "shuffle_fetch", "shuffle_id": 1,
         "reduce_id": 0, "pieces": 1, "rows": 64, "bytes": 800,
         "codec": "none"},
        {"ts": t + 99, "event": "query_end", "query_id": 1,
         "dur": 90_000_000, "rows": 64},
    ]


def _write_log(tmp_path, events, name="log.jsonl"):
    p = str(tmp_path / name)
    with open(p, "w") as f:
        for r in events:
            f.write(json.dumps(r) + "\n")
    return p


def test_profiler_report_golden(tmp_path):
    p = _write_log(tmp_path, _canned_events())
    text, violations = tpu_profile.build_report(
        tpu_profile.load_events([p]))
    assert violations == 0
    # section headers
    for section in ("== queries ==", "== top ops by device time ==",
                    "== compile cache misses ==", "== shuffle ==",
                    "== spill timeline ==", "== forecast vs actual =="):
        assert section in text, text
    # the device-ranked top op is the one with a device lane
    top_line = text.split("== top ops by device time ==\n")[1].splitlines()[0]
    assert "TpuProjectExec" in top_line and "device=5.0ms" in top_line
    assert "query 1 plan=abc dur=90.0ms rows=64" in text
    assert "device_to_host" in text and "peak device watermark" in text
    assert "shuffle_write[none]: 1 piece(s)" in text
    assert "compile[project]: actual 1 <= forecast 1" in text
    assert "0 violation(s)" in text


def test_profiler_flags_forecast_violation(tmp_path):
    # measured bytes above the analyzer bound: VIOLATION + exit code 1
    p = _write_log(tmp_path, _canned_events(byte_bound=100,
                                            measured_bytes=512))
    text, violations = tpu_profile.build_report(
        tpu_profile.load_events([p]))
    assert violations == 1
    assert "VIOLATION" in text and "bytes[TpuProjectExec]" in text
    assert tpu_profile.main([p]) == 1


def test_profiler_flags_compile_storm(tmp_path):
    evs = _canned_events()
    evs += [{"ts": 2_000_000 + i, "event": "compile_miss", "site": "sort",
             "total": 2 + i} for i in range(9)]
    p = _write_log(tmp_path, sorted(evs, key=lambda r: r["ts"]))
    text, _ = tpu_profile.build_report(tpu_profile.load_events([p]))
    assert "sort: 9 <-- COMPILE STORM" in text


def test_diff_event_log_against_itself_is_clean(tmp_path):
    p = _write_log(tmp_path, _canned_events())
    text, n = tpu_profile.run_diff(p, p, threshold=0.2)
    assert n == 0 and "0 regression(s)" in text
    assert tpu_profile.main(["--diff", p, p]) == 0


def test_diff_flags_event_log_regression(tmp_path):
    a = _write_log(tmp_path, _canned_events(), "a.jsonl")
    slow = _canned_events()
    for r in slow:
        if r["event"] == "op_span" and r["op"] == "TpuProjectExec":
            r["dur"] *= 3  # 3x slower than the old log
    b = _write_log(tmp_path, slow, "b.jsonl")
    text, n = tpu_profile.run_diff(a, b, threshold=0.2)
    assert n >= 1 and "REGRESSION" in text and "TpuProjectExec" in text


# ---------------------------------------------------------------------------
# 5. instrumented subsystems through real runs
# ---------------------------------------------------------------------------
def test_shuffle_metrics_and_events(tmp_path):
    from spark_rapids_tpu import types as T

    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.shuffle.transport.class": "host",
        "spark.rapids.tpu.shuffle.mode": "host",
    })
    schema = T.StructType((T.StructField("k", T.IntegerType()),
                           T.StructField("v", T.LongType())))
    data = {"k": [i % 4 for i in range(64)], "v": list(range(64))}
    df = (sess.create_dataframe(data, schema, num_partitions=3)
          .group_by("k").agg(A.agg(A.Sum(col("v")), "s")))
    rows = sorted(df.collect())
    assert rows == sorted(
        (k, sum(v for v in range(64) if v % 4 == k)) for k in range(4))
    report = sess.explain_metrics()
    assert "shuffleBytesWritten=" in report
    assert "shuffleBytesFetched=" in report
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    writes = [r for r in recs if r["event"] == "shuffle_write"]
    fetches = [r for r in recs if r["event"] == "shuffle_fetch"]
    assert writes and all(r["bytes"] > 0 and r["codec"] == "none"
                          for r in writes)
    # the exchange shuffles PARTIAL aggregate outputs (keys x map
    # partitions), and every written row is fetched exactly once
    assert fetches and sum(r["rows"] for r in fetches) == sum(
        r["rows"] for r in writes) > 0


def test_spill_events_watermark_and_memory_footer(tmp_path):
    import numpy as np

    from spark_rapids_tpu.memory import SpillableVals
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.expr.values import ColV

    logger = EV.EventLogger(RapidsConf(
        {"spark.rapids.tpu.eventLog.enabled": True}))
    EV.install(logger)
    try:
        import jax.numpy as jnp

        BufferCatalog.reset(RapidsConf(
            {"spark.rapids.tpu.memory.hbm.budgetBytes": 100_000}))
        cat = BufferCatalog.get()

        def val():
            return ColV(jnp.zeros(8192, jnp.int64),
                        jnp.ones(8192, jnp.bool_))

        a = SpillableVals([val()])   # ~72KB
        b = SpillableVals([val()])   # pushes over budget -> a spills
        assert cat.metrics.device_to_host >= 1
        assert cat.metrics.peak_device_bytes > 100_000
        a.get_vals()                  # unspill
        assert cat.metrics.unspills >= 1
        kinds = [r["kind"] for r in logger.records()
                 if r["event"] == "spill"]
        assert "device_to_host" in kinds and "unspill" in kinds
        watermarks = [r["device_bytes"] for r in logger.records()
                      if r["event"] == "spill"]
        assert all(isinstance(w, int) for w in watermarks)
        a.close()
        b.close()
    finally:
        EV.uninstall()
        BufferCatalog.reset()
    # the explain_metrics footer surfaces the catalog counters
    sess = TpuSession({})
    _run_query(sess)
    assert "memory: device" in sess.explain_metrics()


# ---------------------------------------------------------------------------
# 6. zero overhead when off
# ---------------------------------------------------------------------------
def test_disabled_event_log_emits_nothing(tmp_path, monkeypatch):
    calls = []
    real_emit = EV.EventLogger.emit

    def spy(self, etype, **fields):
        calls.append(etype)
        return real_emit(self, etype, **fields)

    monkeypatch.setattr(EV.EventLogger, "emit", spy)
    sess = TpuSession({})  # defaults: event log OFF
    assert sess.events.enabled is False and sess.events.path is None
    _run_query(sess)
    assert EV.enabled() is False
    assert calls == []                 # no EventLogger.emit calls at all
    assert sess.events.records() == []  # ring untouched
    assert list(tmp_path.iterdir()) == []  # no sink files anywhere


def test_op_timed_fast_path_unchanged_when_disabled():
    """With logging off, op_timed must not attach event plumbing: the
    context manager is the plain timed() with event_op=None (no per-batch
    dict build, no emit)."""
    from spark_rapids_tpu.exec.base import TpuExec, timed

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            raise NotImplementedError

    d = Dummy(RapidsConf({}))
    seen = {}
    import spark_rapids_tpu.exec.base as base_mod

    orig = base_mod.timed

    def probe(metric, trace_name="", trace=False, event_op=None,
              event_section="", **kw):
        seen["event_op"] = event_op
        return orig(metric, trace_name, trace, event_op, event_section, **kw)

    base_mod.timed = probe
    try:
        with d.op_timed():
            pass
    finally:
        base_mod.timed = orig
    assert seen["event_op"] is None
