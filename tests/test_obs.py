"""Live observability plane: registry, /metrics + /status, watchdog.

Reference analog: SQLMetrics streaming into the live Spark UI (the
online half of observability; tests/test_events.py covers the offline
half). Pins the PR's acceptance contracts:
  1. the metric catalog is the single source of truth and every
     events.EVENT_TYPES entry has a live twin (the planes cannot drift);
  2. during a live query /metrics serves Prometheus-format gauges for
     the HBM watermark, compile misses, shuffle bytes, and scan-cache
     hit rate, and /status shows per-query per-op progress whose
     denominators come from the plan analyzer's row/batch forecasts;
  3. a deliberately stalled op, a tiny hbm budget, and a compile-miss
     burst each raise their typed watchdog alert in BOTH the event log
     and /status — one alert per episode;
  4. with the plane off (the default) NOTHING is touched: no registry
     method runs, no exporter/watchdog thread exists (the PR-5
     zero-overhead contract, mirrored);
  5. N concurrent emitter threads lose no increments, take no lock
     inversion against the BufferCatalog, and /status stays parseable
     mid-run.
"""
import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import pytest

from spark_rapids_tpu import events as EV
from spark_rapids_tpu import obs
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.obs.registry import METRICS, MetricsRegistry
from spark_rapids_tpu.obs.server import build_status
from spark_rapids_tpu.obs.watchdog import WatchdogRules, replay_alerts
from spark_rapids_tpu.sql import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tpu_top = _load_tool("tpu_top")
tpu_profile = _load_tool("tpu_profile")


@pytest.fixture(autouse=True)
def clean_plane():
    """Every test starts and ends with the plane down and no logger."""
    obs.shutdown()
    EV.uninstall()
    yield
    obs.shutdown()
    EV.uninstall()


def _run_query(sess):
    df = (sess.range(0, 2048)
          .where(E.GreaterThanOrEqual(col("id"), lit(100)))
          .select(col("id"), E.Alias(E.Multiply(col("id"), lit(2)), "v"))
          .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))
    return df.collect()


def _get(url):
    with urllib.request.urlopen(url, timeout=10) as r:
        return r.read().decode()


# ---------------------------------------------------------------------------
# 1. catalog + registry semantics
# ---------------------------------------------------------------------------
def test_every_event_type_has_a_live_twin():
    for etype in EV.EVENT_TYPES:
        fam = obs.EVENT_BACKED_METRICS.get(etype)
        assert fam is not None, f"{etype} has no live metric twin"
        assert fam in METRICS, f"{etype} -> {fam} is not declared"


def test_registry_counters_gauges_and_labels():
    reg = MetricsRegistry()
    reg.inc("tpu_compile_misses", 1, site="sort")
    reg.inc("tpu_compile_misses", 2, site="sort")
    reg.inc("tpu_compile_misses", 1, site="project")
    assert reg.value("tpu_compile_misses", site="sort") == 3
    assert reg.value("tpu_compile_misses", site="project") == 1
    reg.set_gauge("tpu_hbm_device_bytes", 4096)
    reg.set_gauge("tpu_hbm_device_bytes", 1024)  # gauge: last write wins
    assert reg.value("tpu_hbm_device_bytes") == 1024
    with pytest.raises(ValueError, match="undeclared label"):
        reg.inc("tpu_compile_misses", 1, nope="x")  # typo fails loudly


def test_prometheus_exposition_shape():
    import re

    reg = MetricsRegistry()
    reg.inc("tpu_op_rows", 128, op="TpuProjectExec")
    reg.observe("tpu_op_batch_seconds", 0.005, op="TpuProjectExec")
    text = reg.render_prometheus()
    # every declared family shows HELP/TYPE even with zero samples
    for name, (kind, _, _) in METRICS.items():
        ename = name + ("_total" if kind == "counter" else "")
        assert f"# TYPE {ename} {kind}" in text, ename
    # value class includes '-' INSIDE so negative exponents (2e-05) pass
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? [-+0-9.eE]+$")
    for line in text.strip().splitlines():
        if not line.startswith("#"):
            assert sample.match(line), line
    assert 'tpu_op_rows_total{op="TpuProjectExec"} 128' in text
    # histogram renders cumulative buckets + sum/count
    assert 'tpu_op_batch_seconds_bucket{op="TpuProjectExec",le="0.01"} 1' \
        in text
    assert 'tpu_op_batch_seconds_count{op="TpuProjectExec"} 1' in text


def test_open_span_table():
    reg = MetricsRegistry()
    t = reg.span_open("TpuSortExec", "", start_ns=100)
    assert reg.open_spans() == [("TpuSortExec", "", 100)]
    reg.span_close(t)
    assert reg.open_spans() == []


# ---------------------------------------------------------------------------
# 2. the acceptance path: live query -> /metrics + /status
# ---------------------------------------------------------------------------
def test_live_query_serves_metrics_and_progress(tmp_path):
    sess = TpuSession({
        "spark.rapids.tpu.metrics.http.enabled": True,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    })
    assert sess.obs_address is not None
    rows = _run_query(sess)
    assert rows[0][1] == 1948
    # a warm process-wide pipeline cache may legitimately compile
    # nothing for this query; drive one miss through the shared counter
    # path so the labeled sample is deterministic
    from spark_rapids_tpu.exec.base import note_compile_miss

    note_compile_miss("obs_probe_site")

    text = _get(sess.obs_address + "/metrics")
    # the four acceptance families, by exact exposition name
    assert "tpu_hbm_device_bytes" in text            # HBM watermark gauge
    assert 'tpu_compile_misses_total{site="obs_probe_site"} 1' in text
    assert "# TYPE tpu_shuffle_bytes_total counter" in text
    assert "# TYPE tpu_scan_cache_hit_ratio gauge" in text
    assert 'tpu_queries_total{state="finished"} 1' in text
    # per-op lane: the range source recorded its rows
    assert 'tpu_op_rows_total{op="TpuRangeExec"} 2048' in text

    st = json.loads(_get(sess.obs_address + "/status"))
    q = st["queries"][0]
    assert q["state"] == "finished" and q["rows_out"] == 1
    ops = {o["op"]: o for o in q["ops"]}
    # forecast-derived denominators: the analyzer's rows_by_op feeds the
    # denominator, record_batch the numerator
    rng = ops["TpuRangeExec"]
    assert rng["rows"] == 2048 and rng["rows_forecast"] == 2048
    assert rng["progress"] == 1.0
    agg = ops["TpuHashAggregateExec"]
    assert agg["rows_forecast"] == 1 and agg["batches_forecast"] == 1
    assert agg["progress"] == 1.0  # lazy row count -> batch denominator
    # the same forecasts rode into the event log for offline tools
    with open(sess.events.path) as f:
        recs = [json.loads(line) for line in f]
    pa = next(r for r in recs if r["event"] == "plan_analysis")
    assert pa["rows_by_op"]["TpuRangeExec"] == 2048
    assert pa["batches_by_op"]["TpuHashAggregateExec"] == 1


def test_shuffle_and_scan_cache_counters_feed_registry():
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache

    sess = TpuSession({
        "spark.rapids.tpu.metrics.live.enabled": True,
        "spark.rapids.tpu.shuffle.transport.class": "host",
        "spark.rapids.tpu.shuffle.mode": "host",
    })
    reg = obs.active()
    schema = T.StructType((T.StructField("k", T.IntegerType()),
                           T.StructField("v", T.LongType())))
    data = {"k": [i % 4 for i in range(64)], "v": list(range(64))}
    df = (sess.create_dataframe(data, schema, num_partitions=3)
          .group_by("k").agg(A.agg(A.Sum(col("v")), "s")))
    assert len(df.collect()) == 4
    written = reg.value("tpu_shuffle_bytes", direction="write",
                        codec="none")
    fetched = reg.value("tpu_shuffle_bytes", direction="fetch",
                        codec="none")
    assert written > 0 and fetched == written
    assert reg.value("tpu_shuffle_codec_seconds", op="encode") > 0

    cache = DeviceScanCache(max_bytes=1 << 20)
    cache.get(("nope",))               # miss
    cache.put(("k",), "v", 100)
    cache.get(("k",))                  # hit
    assert reg.value("tpu_scan_cache_ops", op="miss") == 1
    assert reg.value("tpu_scan_cache_ops", op="hit") == 1
    assert reg.value("tpu_scan_cache_hit_ratio") == 0.5

    # the h2d half of the transfer event (packed uploads) counts too
    import numpy as np

    from spark_rapids_tpu.io.arrow_convert import packed_upload

    packed_upload([np.arange(16, dtype=np.int64)])
    assert reg.value("tpu_transfer_bytes", direction="h2d") >= 128


def test_mesh_staging_reports_per_chip():
    pytest.importorskip("jax")
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device mesh")
    from spark_rapids_tpu import types as T

    sess = TpuSession({
        "spark.rapids.tpu.metrics.live.enabled": True,
        "spark.rapids.tpu.shuffle.mode": "ici",
    })
    schema = T.StructType((T.StructField("k", T.IntegerType()),
                           T.StructField("v", T.LongType())))
    data = {"k": [i % 8 for i in range(256)], "v": list(range(256))}
    df = (sess.create_dataframe(data, schema, num_partitions=4)
          .group_by("k").agg(A.agg(A.Sum(col("v")), "s")))
    assert len(df.collect()) == 8
    reg = obs.active()
    staged = {d: reg.value("tpu_mesh_staged_rows", device=str(d))
              for d in range(len(jax.devices()))}
    assert sum(staged.values()) == 256  # every row attributed to a chip
    assert sum(1 for v in staged.values() if v) >= 2  # truly per-device


# ---------------------------------------------------------------------------
# 3. watchdog alerts: log + /status
# ---------------------------------------------------------------------------
def _watchdog_session(tmp_path, extra=None):
    conf = {
        "spark.rapids.tpu.metrics.http.enabled": True,
        "spark.rapids.tpu.watchdog.enabled": True,
        # huge interval: tests drive check_now() deterministically
        "spark.rapids.tpu.watchdog.intervalMs": 3_600_000,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    }
    conf.update(extra or {})
    sess = TpuSession(conf)
    EV.install(sess.events)
    return sess, obs.plane()


def test_stalled_op_raises_watchdog_alert(tmp_path):
    from spark_rapids_tpu.exec.base import TpuExec

    sess, plane = _watchdog_session(tmp_path, {
        "spark.rapids.tpu.watchdog.stallThresholdMs": 1})

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            raise NotImplementedError

    d = Dummy(RapidsConf({}))
    cm = d.op_timed("decode")
    cm.__enter__()  # span stays OPEN: the deliberately stalled op
    try:
        time.sleep(0.01)
        new = plane.watchdog.check_now()
        assert [a.kind for a in new] == ["stall"]
        assert new[0].detail == "Dummy.decode"
        # one alert per episode: the same open span does not re-alert
        assert plane.watchdog.check_now() == []
    finally:
        cm.__exit__(None, None, None)
    # surfaced in the event log...
    alerts = [r for r in sess.events.records() if r["event"] == "alert"]
    assert alerts and alerts[0]["kind"] == "stall"
    # ...and in /status
    st = json.loads(_get(sess.obs_address + "/status"))
    assert any(a["kind"] == "stall" for a in st["alerts"])
    # cleared condition can fire again as a fresh episode
    cm2 = d.op_timed("decode")
    cm2.__enter__()
    try:
        time.sleep(0.01)
        assert [a.kind for a in plane.watchdog.check_now()] == ["stall"]
    finally:
        cm2.__exit__(None, None, None)


def test_tiny_hbm_budget_raises_pressure_alert(tmp_path):
    import jax.numpy as jnp

    from spark_rapids_tpu.expr.values import ColV
    from spark_rapids_tpu.memory import SpillableVals

    sess, plane = _watchdog_session(tmp_path, {
        "spark.rapids.tpu.watchdog.hbmPressureFraction": 0.5})
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 100_000}))
    try:
        sv = SpillableVals([ColV(jnp.zeros(8192, jnp.int64),
                                 jnp.ones(8192, jnp.bool_))])  # ~72KB
        assert BufferCatalog.get().device_bytes > 50_000
        new = plane.watchdog.check_now()
        assert [a.kind for a in new] == ["hbm_pressure"]
        assert new[0].value > new[0].threshold / 2
        st = json.loads(_get(sess.obs_address + "/status"))
        assert any(a["kind"] == "hbm_pressure" for a in st["alerts"])
        assert st["hbm"]["pressure"] > 0.5
        kinds = [r["kind"] for r in sess.events.records()
                 if r["event"] == "alert"]
        assert "hbm_pressure" in kinds
        sv.close()
    finally:
        BufferCatalog.reset()


def test_pressure_alert_without_catalog_budget(tmp_path):
    """The pressure rule must also fire when the session conf carries
    the budget but the catalog was lazily created under a DEFAULT conf
    (no budget -> the spiller never caps, which is exactly when an
    operator needs the alert): the watchdog falls back to its own
    conf-derived budget."""
    import jax.numpy as jnp

    from spark_rapids_tpu.expr.values import ColV
    from spark_rapids_tpu.memory import SpillableVals

    BufferCatalog.reset()  # default conf: cat.budget is None on CPU
    sess, plane = _watchdog_session(tmp_path, {
        "spark.rapids.tpu.watchdog.hbmPressureFraction": 0.5,
        "spark.rapids.tpu.memory.hbm.budgetBytes": 100_000,
    })
    try:
        assert BufferCatalog.get().budget is None
        sv = SpillableVals([ColV(jnp.zeros(8192, jnp.int64),
                                 jnp.ones(8192, jnp.bool_))])  # ~72KB
        new = plane.watchdog.check_now()
        assert [a.kind for a in new] == ["hbm_pressure"]
        sv.close()
    finally:
        BufferCatalog.reset()


def test_storm_threshold_has_one_home():
    """The 'one storm definition engine-wide' promise: the conf entry's
    default, the bare WatchdogRules() default, and tpu_profile's CLI
    default must all agree (a drifted copy fails here)."""
    from spark_rapids_tpu.conf import ANALYSIS_STORM_THRESHOLD

    assert WatchdogRules().storm_threshold \
        == ANALYSIS_STORM_THRESHOLD.default \
        == tpu_profile.DEFAULT_STORM_THRESHOLD


def test_compile_miss_burst_raises_storm_alert(tmp_path):
    from spark_rapids_tpu.exec.base import note_compile_miss

    sess, plane = _watchdog_session(tmp_path, {
        "spark.rapids.tpu.sql.analysis.recompileStorm.threshold": 5})
    for _ in range(5):
        note_compile_miss("test_site")
    new = plane.watchdog.check_now()
    assert [a.kind for a in new] == ["recompile_storm"]
    assert new[0].detail == "test_site" and new[0].value == 5
    st = json.loads(_get(sess.obs_address + "/status"))
    assert any(a["kind"] == "recompile_storm" for a in st["alerts"])
    assert any(r["event"] == "alert" for r in sess.events.records())


# ---------------------------------------------------------------------------
# 4. zero overhead when off (the PR-5 contract, mirrored)
# ---------------------------------------------------------------------------
def test_disabled_plane_touches_nothing(monkeypatch):
    calls = []
    for name in ("inc", "set_gauge", "observe", "span_open",
                 "note_compile_miss"):
        orig = getattr(MetricsRegistry, name)

        def spy(self, *a, __n=name, __o=orig, **k):
            calls.append(__n)
            return __o(self, *a, **k)

        monkeypatch.setattr(MetricsRegistry, name, spy)
    sess = TpuSession({})  # defaults: the plane is OFF
    assert sess.obs_address is None and obs.plane() is None
    _run_query(sess)
    assert obs.enabled() is False
    assert calls == []            # no registry method ran at all
    assert obs.tracker().status() == []  # progress untouched
    live = [t.name for t in threading.enumerate()
            if t.name in ("srtpu-metrics-http", "srtpu-watchdog")]
    assert live == []             # no exporter/watchdog thread


def test_op_timed_returns_plain_context_when_off():
    """With the plane off op_timed must hand back the unwrapped timed()
    context — no span registration, no per-batch obs wrapper."""
    from spark_rapids_tpu.exec.base import TpuExec

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            raise NotImplementedError

    d = Dummy(RapidsConf({}))
    assert type(d.op_timed()).__name__ == "_GeneratorContextManager"
    reg = MetricsRegistry()
    obs.install(reg)
    try:
        with d.op_timed():
            assert len(reg.open_spans()) == 1  # wrapper registered it
        assert reg.open_spans() == []
        assert reg.value("tpu_op_time_seconds", op="Dummy",
                         lane="host") > 0
    finally:
        obs.uninstall()


def test_direct_execute_consumer_leaks_no_live_query():
    """ml/columnar_rdd and bench drive _execute() + execute_columnar()
    directly, with no _run_collect finally: progress registration is
    deferred to the drain paths, so a direct consumer must leave no
    forever-'running' query behind and the start/finish counters must
    balance."""
    sess = TpuSession({"spark.rapids.tpu.metrics.live.enabled": True})
    df = sess.range(0, 256).agg(A.agg(A.Sum(col("id")), "s"))
    final = sess._execute(df.node)  # the direct-consumer path
    assert [b.num_rows for b in final.tpu_child.execute_columnar()] == [1]
    assert obs.tracker().live_count() == 0
    assert obs.tracker().status() == []  # nothing phantom, live or recent
    reg = obs.active()
    assert reg.value("tpu_queries", state="started") == 0
    # the collect path still counts one started + one finished
    _run_query(sess)
    assert reg.value("tpu_queries", state="started") == 1
    assert reg.value("tpu_queries", state="finished") == 1
    assert obs.tracker().live_count() == 0


def test_writer_progress_survives_intervening_query(tmp_path):
    """The writer claims its progress registration EAGERLY at plan
    time: a query collected between _batches() and the sink drain must
    not steal/overwrite the shared pending slot (the same race the
    event log fixes by capturing qid eagerly)."""
    from spark_rapids_tpu.sql.session import DataFrameWriter

    sess = TpuSession({"spark.rapids.tpu.metrics.live.enabled": True})
    df = sess.range(0, 256).agg(A.agg(A.Sum(col("id")), "s"))
    gen, _schema = DataFrameWriter(df)._batches()  # plan, don't drain
    assert _run_query(sess)[0][1] == 1948          # intervening query
    assert len(list(gen)) >= 1                     # now drain the sink
    reg = obs.active()
    assert reg.value("tpu_queries", state="started") == 2
    assert reg.value("tpu_queries", state="finished") == 2
    assert obs.tracker().live_count() == 0


# ---------------------------------------------------------------------------
# 5. concurrency: no lost increments, no inversion, parseable /status
# ---------------------------------------------------------------------------
def test_concurrent_emitters_and_status_reads(tmp_path):
    sess = TpuSession({
        "spark.rapids.tpu.metrics.http.enabled": True,
    })
    reg = obs.active()
    url = sess.obs_address
    errors = []
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            try:
                st = json.loads(_get(url + "/status"))
                assert isinstance(st["queries"], list)
                _get(url + "/metrics")
            except Exception as e:  # noqa: BLE001
                errors.append(e)
                return

    def query_thread(i):
        try:
            s = TpuSession({"spark.rapids.tpu.metrics.live.enabled": True})
            for _ in range(3):
                assert _run_query(s)[0][1] == 1948
            for _ in range(1000):
                reg.inc("tpu_op_rows", 1, op=f"T{i}")
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def catalog_thread():
        # spill-pressure traffic interleaving with registry emits: the
        # catalog holds ITS lock while calling the (leaf) registry lock
        import jax.numpy as jnp

        from spark_rapids_tpu.expr.values import ColV
        from spark_rapids_tpu.memory import SpillableVals

        try:
            BufferCatalog.reset(RapidsConf(
                {"spark.rapids.tpu.memory.hbm.budgetBytes": 150_000}))
            for _ in range(8):
                vals = [SpillableVals([ColV(
                    jnp.zeros(4096, jnp.int64),
                    jnp.ones(4096, jnp.bool_))]) for _ in range(4)]
                for v in vals:
                    v.get_vals()
                    v.close()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    rt = threading.Thread(target=reader)
    rt.start()
    threads = [threading.Thread(target=query_thread, args=(i,))
               for i in range(4)] + [threading.Thread(target=catalog_thread)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
        assert not t.is_alive(), "emitter deadlocked"
    stop.set()
    rt.join(timeout=30)
    assert not errors, errors
    # no lost increments: exact totals per thread-private label and
    # across the shared query counters
    for i in range(4):
        assert reg.value("tpu_op_rows", op=f"T{i}") == 1000
    assert reg.value("tpu_queries", state="started") == 12
    assert reg.value("tpu_queries", state="finished") == 12
    assert obs.tracker().live_count() == 0
    BufferCatalog.reset()


# ---------------------------------------------------------------------------
# 6. offline alert replay + the terminal view
# ---------------------------------------------------------------------------
def _replay_events():
    t = 1_000_000
    evs = [
        {"ts": t, "event": "query_start", "query_id": 1,
         "plan_digest": "abc", "sql_hash": "d"},
        {"ts": t + 1, "event": "plan_analysis", "query_id": 1,
         "bounded": True, "site_forecast": {}, "bytes_by_op": {},
         "rows_by_op": {}, "batches_by_op": {}, "peak_hbm": 1000,
         "budget": 100_000, "warnings": []},
        # a 2s span: the stall — plus its deviceSync device-lane twin,
        # which must NOT replay as a second alert for the same episode
        {"ts": t + 10, "event": "op_span", "op": "TpuSortExec",
         "section": "", "start": t + 5, "dur": 2_000_000_000,
         "lane": "host"},
        {"ts": t + 11, "event": "op_span", "op": "TpuSortExec",
         "section": "device_wait", "start": t + 6,
         "dur": 2_000_000_000, "lane": "device"},
        # watermark at 90% of the logged budget: pressure
        {"ts": t + 20, "event": "spill", "kind": "device_to_host",
         "bytes": 1, "device_bytes": 90_000},
    ]
    # 6 misses on one site within 1ms: a storm at threshold 5
    evs += [{"ts": t + 30 + i, "event": "compile_miss", "site": "sort",
             "total": i + 1} for i in range(6)]
    evs.append({"ts": t + 99, "event": "query_end", "query_id": 1,
                "dur": 90, "rows": 1})
    return evs


def test_replay_alerts_finds_all_three_kinds():
    rules = WatchdogRules(stall_ns=1_000_000_000, pressure_fraction=0.85,
                          storm_threshold=5,
                          storm_window_ns=10_000_000_000)
    alerts = replay_alerts(_replay_events(), rules)
    kinds = [a.kind for a in alerts]
    assert kinds == ["stall", "hbm_pressure", "recompile_storm"]
    # storm alerts once per episode, not once per extra miss
    assert kinds.count("recompile_storm") == 1
    # higher thresholds silence it — the tuning workflow
    quiet = WatchdogRules(stall_ns=10_000_000_000, pressure_fraction=0.99,
                          storm_threshold=50,
                          storm_window_ns=10_000_000_000)
    assert replay_alerts(_replay_events(), quiet) == []


def test_tpu_profile_alerts_mode(tmp_path, capsys):
    p = str(tmp_path / "log.jsonl")
    with open(p, "w") as f:
        for r in _replay_events():
            f.write(json.dumps(r) + "\n")
    rc = tpu_profile.main([p, "--alerts", "--stall-ms", "1000",
                           "--storm-threshold", "5"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "== watchdog alert replay ==" in out
    assert "stall: TpuSortExec" in out
    assert "hbm_pressure" in out and "recompile_storm" in out
    assert "3 alert(s)" in out


def test_tpu_top_renders_status():
    status = {
        "queries_live": 1,
        "queries": [{
            "query_id": 7, "plan_digest": "abc", "state": "running",
            "elapsed_ms": 1234.5, "rows_out": None,
            "ops": [
                {"op": "TpuRangeExec", "rows": 1024, "rows_forecast": 2048,
                 "batches": 1, "batches_forecast": 2, "bytes": 9216,
                 "progress": 0.5},
                {"op": "TpuShuffledHashJoinExec", "rows": 10,
                 "rows_forecast": None, "batches": 1,
                 "batches_forecast": None, "bytes": 80, "progress": None},
            ],
        }],
        "hbm": {"device_bytes": 50_000_000, "peak_device_bytes": 60_000_000,
                "spilled_bytes": 0, "budget_bytes": 100_000_000,
                "pressure": 0.5},
        "alerts": [{"kind": "stall", "detail": "TpuSortExec",
                    "value": 2e9, "threshold": 1e9, "ts": 0}],
        "metrics": {"tpu_compile_misses": {"site=sort": 3},
                    "tpu_scan_cache_ops": {"op=hit": 3, "op=miss": 1}},
    }
    text = tpu_top.render_status(status, clock="12:00:00")
    assert "query 7 [running]" in text
    assert "rows 1024/2048" in text and " 50.0%" in text
    assert "(unbounded)" in text           # no fake percentage
    assert "ALERT [stall] TpuSortExec" in text
    assert "HBM" in text and "50.0MB" in text
    assert "75% hit" in text and "compile misses: 3" in text


def test_build_status_is_json_serializable():
    reg = MetricsRegistry()
    obs.install(reg)
    try:
        reg.inc("tpu_op_rows", 5, op="X")
        reg.observe("tpu_op_batch_seconds", 0.2, op="X")
        st = build_status(reg, obs.tracker(), None)
        json.dumps(st)  # must never smuggle a non-JSON type
        assert st["metrics"]["tpu_op_rows"] == {"op=X": 5}
    finally:
        obs.uninstall()


# ---------------------------------------------------------------------------
# 7. event-log durability at teardown (satellite)
# ---------------------------------------------------------------------------
def test_dying_interpreter_leaves_parseable_log(tmp_path):
    """A session killed mid-query (SystemExit between query_start and
    query_end, no close()) must still leave a fully parseable JSONL log
    — the atexit flush plus line buffering guarantee no truncated final
    line."""
    script = f"""
import sys
sys.path.insert(0, {str(REPO)!r})
from spark_rapids_tpu.sql import TpuSession
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr.expressions import col

sess = TpuSession({{"spark.rapids.tpu.eventLog.dir": {str(tmp_path)!r}}})
df = sess.range(0, 512).agg(A.agg(A.Sum(col("id")), "s"))
final = sess._execute(df.node)   # emits query_start + plan events
it = final.tpu_child.execute_columnar()
next(it)                          # mid-query: first batch materialized
raise SystemExit(3)               # die WITHOUT close(); no query_end
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 3, r.stderr
    logs = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert len(logs) == 1
    with open(tmp_path / logs[0]) as f:
        recs = [json.loads(line) for line in f]  # every line parses
    kinds = [rec["event"] for rec in recs]
    assert "query_start" in kinds and "query_end" not in kinds
    # the offline profiler copes with the open window
    text, violations = tpu_profile.build_report(recs)
    assert "query 1" in text


def test_dropped_logger_not_pinned_by_atexit(tmp_path):
    """The atexit durability hook registers through a weakref: a
    short-lived session's logger that nobody close()s must still be
    collectable (no fd/ring-buffer accumulation until process exit)."""
    import gc
    import weakref

    logger = EV.EventLogger(RapidsConf(
        {"spark.rapids.tpu.eventLog.dir": str(tmp_path)}))
    logger.emit("compile_miss", site="x", total=1)
    ref = weakref.ref(logger)
    del logger
    gc.collect()
    assert ref() is None, "atexit hook pins the dropped logger"


def test_session_close_flushes_and_detaches(tmp_path):
    sess = TpuSession({"spark.rapids.tpu.eventLog.dir": str(tmp_path)})
    _run_query(sess)
    assert EV._ACTIVE is sess.events
    sess.close()
    assert EV.enabled() is False and sess.events._fh is None
    with open(sess.events.path) as f:
        for line in f:
            json.loads(line)


def test_flight_recorder_mode_ring_only_until_dumped(tmp_path):
    """eventLog.flightRecorder.enabled + eventLog.dir: events land ONLY
    in the ring (no streaming JSONL sink opened), and dump_flight_record
    writes the ring snapshot as one tpu-flightrec-<pid>-<episode>.jsonl;
    a streaming logger's dump is a no-op (already durable)."""
    logger = EV.EventLogger(RapidsConf({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.eventLog.flightRecorder.enabled": True}))
    assert logger.enabled and logger.path is None and logger._fh is None
    assert logger.flight_dir == str(tmp_path)
    logger.emit("compile_miss", site="x", total=1)
    assert os.listdir(tmp_path) == [], "flight recorder opened a sink"
    path = logger.dump_flight_record(1)
    assert os.path.basename(path) == f"tpu-flightrec-{os.getpid()}-1.jsonl"
    with open(path) as f:
        recs = [json.loads(line) for line in f]
    assert [r["event"] for r in recs] == ["compile_miss"]
    # a streaming logger has nowhere (and no need) to dump
    streaming = EV.EventLogger(RapidsConf(
        {"spark.rapids.tpu.eventLog.dir": str(tmp_path)}))
    assert streaming.dump_flight_record(1) is None
    streaming.close()


def test_watchdog_alert_dumps_flight_ring(tmp_path):
    """Each NEW watchdog alert episode dumps the ring — including the
    alert events just raised — one file per episode."""
    from spark_rapids_tpu.exec.base import TpuExec

    sess, plane = _watchdog_session(tmp_path, {
        "spark.rapids.tpu.eventLog.flightRecorder.enabled": True,
        "spark.rapids.tpu.watchdog.stallThresholdMs": 1})
    assert sess.events.flight_dir == str(tmp_path)

    class Dummy(TpuExec):
        @property
        def output_schema(self):
            raise NotImplementedError

    d = Dummy(RapidsConf({}))
    cm = d.op_timed("decode")
    cm.__enter__()
    try:
        time.sleep(0.01)
        assert [a.kind for a in plane.watchdog.check_now()] == ["stall"]
        dumps = sorted(f for f in os.listdir(tmp_path)
                       if f.startswith("tpu-flightrec-"))
        assert len(dumps) == 1
        with open(tmp_path / dumps[0]) as f:
            recs = [json.loads(line) for line in f]
        assert any(r["event"] == "alert" and r["kind"] == "stall"
                   for r in recs), "dump lost the triggering alert"
        # the same open episode does not dump again
        assert plane.watchdog.check_now() == []
        assert len([f for f in os.listdir(tmp_path)
                    if f.startswith("tpu-flightrec-")]) == 1
    finally:
        cm.__exit__(None, None, None)
    # a fresh episode gets its own numbered file
    cm2 = d.op_timed("decode")
    cm2.__enter__()
    try:
        time.sleep(0.01)
        assert plane.watchdog.check_now()
    finally:
        cm2.__exit__(None, None, None)
    assert len([f for f in os.listdir(tmp_path)
                if f.startswith("tpu-flightrec-")]) == 2


def test_flight_record_survives_dying_interpreter(tmp_path):
    """The satellite's acceptance path: ring-buffer mode (no streaming
    log), a watchdog alert fires MID-QUERY, the interpreter SystemExits
    without close() — and post-hoc diagnosis still works from the
    alert-triggered dump alone."""
    script = f"""
import sys, time
sys.path.insert(0, {str(REPO)!r})
from spark_rapids_tpu import obs
from spark_rapids_tpu.sql import TpuSession
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr.expressions import col

sess = TpuSession({{
    "spark.rapids.tpu.eventLog.dir": {str(tmp_path)!r},
    "spark.rapids.tpu.eventLog.flightRecorder.enabled": True,
    "spark.rapids.tpu.watchdog.enabled": True,
    "spark.rapids.tpu.watchdog.intervalMs": 3600000,
    "spark.rapids.tpu.watchdog.stallThresholdMs": 1,
}})
df = sess.range(0, 512).agg(A.agg(A.Sum(col("id")), "s"))
final = sess._execute(df.node)    # emits query_start into the ring
it = final.tpu_child.execute_columnar()
next(it)                          # mid-query: first batch materialized
cm = final.tpu_child.op_timed("wedged")
cm.__enter__()                    # a span that will never close
time.sleep(0.01)
alerts = obs.plane().watchdog.check_now()
assert alerts, "stall rule did not fire"
raise SystemExit(3)               # die WITHOUT close(); no query_end
"""
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 3, r.stderr
    # NO streaming log exists — the dump is the only artifact
    names = os.listdir(tmp_path)
    assert not any(n.startswith("tpu-events-") for n in names), names
    dumps = [n for n in names if n.startswith("tpu-flightrec-")]
    assert len(dumps) == 1, names
    with open(tmp_path / dumps[0]) as f:
        recs = [json.loads(line) for line in f]  # every line parses
    kinds = [rec["event"] for rec in recs]
    assert "query_start" in kinds and "query_end" not in kinds
    assert any(rec["event"] == "alert" and rec["kind"] == "stall"
               for rec in recs), kinds
    # the offline profiler reads the dump like any log
    text, _ = tpu_profile.build_report(recs)
    assert "query 1" in text
