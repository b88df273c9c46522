"""Concurrent multi-query serving: admission control, fair scheduling,
pipelined session execution (serve/scheduler.py + plan_cache.py), the
catalog reservation API, the semaphore acquire timeout, and the
thread-safety regressions for the process-shared compile caches.

The headline stress test is the ISSUE 9 acceptance path: N threads x M
queries against a deliberately tiny hbm.budgetBytes — zero OOMs, every
query completes, results match the single-threaded oracle, admission/
queue events balance, and the summed admitted forecasts never exceed the
budget (zero admission-forecast violations)."""
import importlib.util
import json
import os
import threading
import time

import pytest

from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu import events as EV
from spark_rapids_tpu import obs
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.memory import TpuSemaphore, TpuSemaphoreTimeout
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.serve import (
    QueryScheduler,
    ServeAdmissionRejected,
    ServeQueueTimeout,
    SharedPlanCache,
)
from spark_rapids_tpu.sql import TpuSession

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "tpu_profile", os.path.join(REPO, "tools", "tpu_profile.py"))
tpu_profile = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(tpu_profile)


@pytest.fixture(autouse=True)
def clean_serving_state():
    """Every test starts/ends with fresh process-global serving state."""
    QueryScheduler.reset()
    SharedPlanCache.reset()
    BufferCatalog.reset()
    TpuSemaphore.reset()
    EV.uninstall()
    obs.shutdown()
    yield
    QueryScheduler.reset()
    SharedPlanCache.reset()
    BufferCatalog.reset()
    TpuSemaphore.reset()
    EV.uninstall()
    obs.shutdown()


def _query_df(sess, mult: int, n: int = 2048):
    """A statically-bounded plan (in-memory range -> filter -> project ->
    COMPLETE aggregate) whose result depends on ``mult``."""
    return (sess.range(0, n)
            .where(E.GreaterThanOrEqual(col("id"), lit(100)))
            .select(col("id"),
                    E.Alias(E.Multiply(col("id"), lit(mult)), "v"))
            .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))


def _forecast_of(settings=None) -> int:
    """The analyzer's peak-HBM forecast for _query_df's shape."""
    sess = TpuSession(dict(settings or {},
                           **{"spark.rapids.tpu.serve.enabled": True}))
    _query_df(sess, 2).collect()
    an = sess.last_analysis
    assert an is not None and an.bounded and an.peak_hbm
    return an.peak_hbm


# ---------------------------------------------------------------------------
# 1. semaphore acquire timeout (satellite)
# ---------------------------------------------------------------------------
def test_semaphore_timeout_names_holder_and_duration():
    sem = TpuSemaphore.reset(RapidsConf({
        "spark.rapids.tpu.sql.concurrentTpuTasks": 1,
        "spark.rapids.tpu.sql.semaphore.acquireTimeoutMs": 150,
    }))
    held = threading.Event()
    release = threading.Event()

    def holder():
        sem.acquire_if_necessary()
        held.set()
        release.wait(10)
        sem.release_if_necessary()

    t = threading.Thread(target=holder, name="wedged-holder")
    t.start()
    assert held.wait(5)
    with pytest.raises(TpuSemaphoreTimeout) as ei:
        sem.acquire_if_necessary()
    msg = str(ei.value)
    assert "wedged-holder" in msg          # the culprit is named
    assert "acquireTimeoutMs" in msg       # and the escape-hatch conf
    release.set()
    t.join(5)
    # after the holder releases, acquisition succeeds within the timeout
    sem.acquire_if_necessary()
    sem.release_if_necessary()


def test_semaphore_default_still_blocks_forever_config():
    sem = TpuSemaphore.reset(RapidsConf({}))
    assert sem.timeout_ms == 0  # the reference behavior is the default


# ---------------------------------------------------------------------------
# 2. admission verdicts
# ---------------------------------------------------------------------------
def test_admission_rejects_plan_that_can_never_fit():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    with pytest.raises(ServeAdmissionRejected) as ei:
        sched.acquire("session-a", 0, 10 << 20, "d1")
    assert "exceeds the total HBM budget" in str(ei.value)
    assert sched.stats()["rejected"] == 1


def test_admission_reserves_and_queues_until_release():
    budget = 1 << 20
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": budget}))
    sched = QueryScheduler.reset(RapidsConf({}))
    t1 = sched.acquire("session-a", 0, 700_000, "d1")
    assert BufferCatalog.get().reserved_bytes == 700_000
    got = []

    def second():
        t2 = sched.acquire("session-b", 0, 700_000, "d2")
        got.append(t2)

    th = threading.Thread(target=second)
    th.start()
    time.sleep(0.2)
    assert not got  # 700k + 700k > 1M: queued, not admitted
    assert sched.stats()["waiting"] == 1
    sched.release(t1)
    th.join(5)
    assert got and got[0].verdict == "admit"
    assert BufferCatalog.get().reserved_bytes == 700_000
    sched.release(got[0])
    assert BufferCatalog.get().reserved_bytes == 0
    assert sched.stats()["peak_inflight_forecast"] <= budget


def test_bypass_admission_when_nothing_running():
    # residual device bytes above the budget must not wedge the queue:
    # with nothing active, the head admits anyway (spill enforces)
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    cat = BufferCatalog.get()
    cat._device_bytes = 2 << 20  # simulate resident cache pressure
    t = sched.acquire("session-a", 0, 500_000, "d1")
    assert t.bypass and sched.stats()["bypass_admissions"] == 1
    sched.release(t)


def test_unbounded_plan_admits_with_zero_reservation():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    t = sched.acquire("session-a", 0, None, "d1")
    assert t.verdict == "admit"
    assert BufferCatalog.get().reserved_bytes == 0
    sched.release(t)


def test_max_queue_depth_rejects_with_named_error():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf(
        {"spark.rapids.tpu.serve.maxQueueDepth": 1}))
    t1 = sched.acquire("session-a", 0, 900_000, "d1")
    waiter = threading.Thread(
        target=lambda: sched.release(
            sched.acquire("session-a", 0, 900_000, "d2")))
    waiter.start()
    time.sleep(0.2)  # d2 is now queued at depth 1
    with pytest.raises(ServeAdmissionRejected) as ei:
        sched.acquire("session-a", 0, 900_000, "d3")
    assert "maxQueueDepth" in str(ei.value)
    sched.release(t1)
    waiter.join(5)


def test_queue_timeout_raises_named_error():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf(
        {"spark.rapids.tpu.serve.queueTimeoutMs": 200}))
    t1 = sched.acquire("session-a", 0, 900_000, "d1")
    with pytest.raises(ServeQueueTimeout) as ei:
        sched.acquire("session-b", 0, 900_000, "d2")
    assert "queueTimeoutMs" in str(ei.value)
    assert sched.stats()["timeouts"] == 1
    sched.release(t1)


def test_timeout_pumps_the_successor_head():
    # queue [big, small] in one session while another holds the budget:
    # big's timeout must PUMP the queue so small (which fits the live
    # headroom) admits immediately — not at the next unrelated release
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    t1 = sched.acquire("sess-a", 0, 900_000, "hold")
    events = []

    def big():
        try:
            sched.acquire("sess-b", 0, 800_000, "big",
                          conf_=RapidsConf(
                              {"spark.rapids.tpu.serve.queueTimeoutMs":
                               300}))
        except ServeQueueTimeout:
            events.append("big-timeout")

    def small():
        t = sched.acquire("sess-b", 0, 50_000, "small")
        events.append("small-admitted")
        sched.release(t)

    tb = threading.Thread(target=big)
    tb.start()
    time.sleep(0.1)
    ts = threading.Thread(target=small)
    ts.start()
    tb.join(5)
    assert events and events[0] == "big-timeout"
    ts.join(2)  # must NOT need t1's release to proceed
    assert "small-admitted" in events
    sched.release(t1)


def test_large_head_is_not_starved_by_later_small_queries():
    # anti-starvation barrier: a later small query (same priority) must
    # not keep backfilling past a blocked large head — on release, the
    # large head admits FIRST
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    t1 = sched.acquire("sess-a", 0, 900_000, "hold")
    tickets = {}
    lock = threading.Lock()

    def run(sess, forecast, tag):
        t = sched.acquire(sess, 0, forecast, tag)
        with lock:
            tickets[tag] = t
        time.sleep(0.01)
        sched.release(t)

    tb = threading.Thread(target=run, args=("sess-b", 800_000, "big"))
    tb.start()
    time.sleep(0.1)  # big is queued (free is only ~100k)
    tsm = threading.Thread(target=run, args=("sess-c", 50_000, "small"))
    tsm.start()
    time.sleep(0.3)
    # small FITS the live headroom but arrived after the starving head:
    # the barrier holds it back
    assert tickets == {}
    sched.release(t1)
    tb.join(5)
    tsm.join(5)
    assert set(tickets) == {"big", "small"}
    # big admitted FIRST (admit order, not thread-wakeup order: both
    # admit in one pump once the blocker releases)
    assert tickets["big"].admit_ns < tickets["small"].admit_ns


def test_rejected_query_closes_its_event_window():
    budget = 60_000  # smaller than _query_df's peak forecast at n=65536
    settings = {
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.memory.hbm.budgetBytes": budget,
        "spark.rapids.tpu.eventLog.enabled": True,
    }
    BufferCatalog.reset(RapidsConf(settings))
    QueryScheduler.reset(RapidsConf(settings))
    sess = TpuSession(settings)
    with pytest.raises(ServeAdmissionRejected):
        _query_df(sess, 2, n=1 << 16).collect()
    recs = sess.events.records()
    starts = [r for r in recs if r["event"] == "query_start"]
    ends = [r for r in recs if r["event"] == "query_end"]
    assert len(starts) == 1 and len(ends) == 1  # window closed
    assert ends[0]["error"] is True
    adm = [r for r in recs if r["event"] == "admission"]
    assert adm and adm[-1]["verdict"] == "reject"


# ---------------------------------------------------------------------------
# 3. fairness: round-robin across sessions, priority tiers
# ---------------------------------------------------------------------------
def _drain_order(sched, submits):
    """Submit (session, priority) tickets from threads while a blocker
    holds the whole budget; release the blocker and record admit order."""
    order = []
    order_lock = threading.Lock()
    threads = []
    started = []

    def run(sess, prio, tag):
        t = sched.acquire(sess, prio, 900_000, tag)
        with order_lock:
            order.append(tag)
        time.sleep(0.01)
        sched.release(t)

    blocker = sched.acquire("blocker", 0, 900_000, "b0")
    for sess, prio, tag in submits:
        th = threading.Thread(target=run, args=(sess, prio, tag))
        th.start()
        started.append(th)
        time.sleep(0.05)  # deterministic enqueue order
    sched.release(blocker)
    for th in started:
        th.join(10)
    return order


def test_round_robin_alternates_sessions():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    order = _drain_order(sched, [
        ("sess-a", 0, "a1"), ("sess-a", 0, "a2"),
        ("sess-b", 0, "b1"), ("sess-b", 0, "b2"),
    ])
    # per-session FIFO always holds...
    assert order.index("a1") < order.index("a2")
    assert order.index("b1") < order.index("b2")
    # ...and round-robin interleaves the sessions instead of draining
    # all of a's backlog first (a submitted its whole backlog first)
    assert order != ["a1", "a2", "b1", "b2"]


def test_priority_session_drains_first():
    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    order = _drain_order(sched, [
        ("sess-lo", 0, "lo1"), ("sess-lo", 0, "lo2"),
        ("sess-hi", 5, "hi1"), ("sess-hi", 5, "hi2"),
    ])
    # the high-priority session's queries all admit before the
    # low-priority backlog finishes
    assert max(order.index("hi1"), order.index("hi2")) \
        < order.index("lo2")


# ---------------------------------------------------------------------------
# 4. shared plan cache
# ---------------------------------------------------------------------------
def test_plan_cache_shares_analysis_across_sessions():
    SharedPlanCache.reset()
    settings = {"spark.rapids.tpu.serve.enabled": True}
    s1, s2 = TpuSession(settings), TpuSession(settings)
    r1 = _query_df(s1, 3).collect()
    r2 = _query_df(s2, 3).collect()
    assert r1 == r2
    st = SharedPlanCache.get().stats()
    assert st["misses"] == 1 and st["hits"] >= 1  # analyzed ONCE
    assert st["warm"] == 1  # first completion marked the digest warm


def test_plan_cache_keys_on_conf_fingerprint():
    SharedPlanCache.reset()
    s1 = TpuSession({"spark.rapids.tpu.serve.enabled": True})
    s2 = TpuSession({"spark.rapids.tpu.serve.enabled": True,
                     "spark.rapids.tpu.sql.shapeBucket.minRows": 256})
    _query_df(s1, 3).collect()
    _query_df(s2, 3).collect()
    # different layout-affecting settings -> different cache entries
    assert SharedPlanCache.get().stats()["misses"] == 2


def test_plan_cache_single_flight_under_race():
    SharedPlanCache.reset()
    cache = SharedPlanCache.get()
    computes = []

    def compute():
        computes.append(1)
        time.sleep(0.1)
        return "analysis"

    results = []
    ths = [threading.Thread(
        target=lambda: results.append(cache.analysis_for(("k",), compute)))
        for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    assert len(computes) == 1  # one flight, seven waiters
    assert all(r[0] == "analysis" for r in results)
    assert sum(1 for r in results if not r[1]) == 1  # exactly one miss


# ---------------------------------------------------------------------------
# 5. the acceptance stress path: N threads x M queries, tiny budget
# ---------------------------------------------------------------------------
def test_stress_concurrent_sessions_tiny_budget(tmp_path):
    n_threads, n_queries = 4, 8
    forecast = _forecast_of()
    # room for ~2 admitted forecasts: real queueing under 4 threads, but
    # every single plan fits (no bypass, no rejects)
    budget = int(2.5 * forecast)
    settings = {
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.memory.hbm.budgetBytes": budget,
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
    }
    BufferCatalog.reset(RapidsConf(settings))
    QueryScheduler.reset(RapidsConf(settings))
    SharedPlanCache.reset()

    # single-threaded oracle, serve OFF (the plain collect path)
    oracle_sess = TpuSession({})
    oracle = {
        (ti, qi): _query_df(oracle_sess, 2 + (ti * n_queries + qi) % 5
                            ).collect()
        for ti in range(n_threads) for qi in range(n_queries)
    }

    results = {}
    errors = []
    lock = threading.Lock()

    def worker(ti):
        try:
            sess = TpuSession(settings)
            for qi in range(n_queries):
                rows = _query_df(sess, 2 + (ti * n_queries + qi) % 5
                                 ).collect()
                with lock:
                    results[(ti, qi)] = rows
        except Exception as e:  # pragma: no cover - the failure mode
            with lock:
                errors.append((ti, repr(e)))

    threads = [threading.Thread(target=worker, args=(ti,),
                                name=f"stress-{ti}")
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, f"queries failed under concurrency: {errors}"
    assert len(results) == n_threads * n_queries  # all queries completed
    for key, rows in results.items():
        assert rows == oracle[key], f"result mismatch for {key}"

    sched = QueryScheduler.instance()
    st = sched.stats()
    assert st["admitted"] == n_threads * n_queries
    assert st["rejected"] == 0 and st["timeouts"] == 0
    assert st["active"] == 0 and st["waiting"] == 0  # fully drained
    # zero admission-forecast violations: with no bypass, the summed
    # admitted forecasts never exceeded the budget at any point
    assert st["bypass_admissions"] == 0
    assert st["peak_inflight_forecast"] <= budget
    # the tiny budget actually exercised the queue
    assert st["queued"] > 0

    # admission/queue events balance across the merged per-session logs
    events = tpu_profile.load_events([str(tmp_path)])
    adm = [r for r in events if r.get("event") == "admission"]
    # every query logs exactly one terminal "admit"; queued ones logged
    # a "queue" verdict first, none were rejected
    assert sum(1 for r in adm if r["verdict"] == "admit") \
        == n_threads * n_queries
    assert not any(r["verdict"] == "reject" for r in adm)
    enq = sum(1 for r in events if r.get("event") == "queue"
              and r["op"] == "enqueue")
    deq = sum(1 for r in events if r.get("event") == "queue"
              and r["op"] == "dequeue")
    assert enq == deq and enq == st["queued"]
    # the offline profiler agrees: zero violations (forecast bounds hold
    # per query under by-thread attribution, queue events balance)
    report, violations = tpu_profile.build_report(events)
    assert violations == 0, report
    assert "== serving ==" in report and "admit=" in report

    # queue-wait spans render on per-session serve lanes in Perfetto
    trace = EV.chrome_trace(events)
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M"}
    assert any(t.startswith("serve session-") for t in tracks), tracks


def test_concurrent_execution_overlaps():
    """The pipelining claim, asserted structurally: with headroom for
    several forecasts, concurrent submits are simultaneously admitted
    (peak_active >= 2) and all results stay correct. The wall-clock
    queries/sec comparison is not made here: a micro-workload on a
    shared 2-core CI box measures only noise."""
    forecast = _forecast_of()
    settings = {
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.memory.hbm.budgetBytes": int(8 * forecast),
    }
    BufferCatalog.reset(RapidsConf(settings))
    QueryScheduler.reset(RapidsConf(settings))
    SharedPlanCache.reset()
    n_threads, n_queries = 4, 3
    errors = []

    def worker(ti):
        try:
            s = TpuSession(settings)
            for qi in range(n_queries):
                i = ti * n_queries + qi
                rows = _query_df(s, 2 + i % 5, n=4096).collect()
                assert rows[0][1] == 3996
        except Exception as e:  # pragma: no cover
            errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(ti,))
               for ti in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    assert not errors, errors
    st = QueryScheduler.instance().stats()
    assert st["admitted"] == n_threads * n_queries
    assert st["peak_active"] >= 2  # queries genuinely overlapped


# ---------------------------------------------------------------------------
# 6. /status + tpu_top surface the queue
# ---------------------------------------------------------------------------
def test_status_and_tpu_top_show_queue():
    from spark_rapids_tpu.obs.progress import ProgressTracker
    from spark_rapids_tpu.obs.registry import MetricsRegistry
    from spark_rapids_tpu.obs.server import build_status

    BufferCatalog.reset(RapidsConf(
        {"spark.rapids.tpu.memory.hbm.budgetBytes": 1 << 20}))
    sched = QueryScheduler.reset(RapidsConf({}))
    t1 = sched.acquire("session-9", 0, 900_000, "dead99beef99")
    waiter = threading.Thread(
        target=lambda: sched.release(
            sched.acquire("session-7", 1, 800_000, "feed77face77")))
    waiter.start()
    time.sleep(0.2)
    status = build_status(MetricsRegistry(), ProgressTracker(), None)
    json.dumps(status)  # /status must stay JSON-serializable
    serve = status["serve"]
    assert serve["stats"]["active"] == 1 and serve["stats"]["waiting"] == 1
    q = serve["queue"][0]
    assert q["session"] == "session-7" and q["position"] == 0
    assert "queued" in q["reason"]
    assert status["hbm"]["reserved_bytes"] == 900_000

    import importlib.util as iu

    spec = iu.spec_from_file_location(
        "tpu_top", os.path.join(REPO, "tools", "tpu_top.py"))
    tpu_top = iu.module_from_spec(spec)
    spec.loader.exec_module(tpu_top)
    frame = tpu_top.render_status(status)
    assert "session-7" in frame and "session-9" in frame
    assert "queued" in frame  # the admission verdict is visible
    sched.release(t1)
    waiter.join(5)


# ---------------------------------------------------------------------------
# 7. thread-safety regressions for the shared compile caches (satellite)
# ---------------------------------------------------------------------------
def test_cached_pipeline_compiles_once_under_race():
    from spark_rapids_tpu.exec import base as B

    cache = {}
    builds = []
    before = B.compile_miss_count()

    def build():
        builds.append(1)
        return lambda: "fn"

    barrier = threading.Barrier(8)

    def race():
        barrier.wait()
        B.cached_pipeline(cache, ("k",), "fused_chain", build)

    ths = [threading.Thread(target=race) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    assert len(builds) == 1  # one build...
    assert B.compile_miss_count() - before == 1  # ...one counted miss


def test_compile_counter_exact_under_concurrency():
    from spark_rapids_tpu.exec.base import CompileCounter

    c = CompileCounter()
    n_threads, n_each = 8, 500

    def bump():
        for _ in range(n_each):
            c.note("site-x")

    ths = [threading.Thread(target=bump) for _ in range(n_threads)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    total, by_site = c.snapshot()
    assert total == n_threads * n_each
    assert by_site["site-x"] == n_threads * n_each


def test_scanner_cache_single_instance_under_race(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.sql import session as S

    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(pa.table({"k": pa.array(
        np.arange(64, dtype="int64"))}), path)
    conf = RapidsConf({})
    S._SCANNER_CACHE.clear()
    got = []
    barrier = threading.Barrier(8)

    def race():
        barrier.wait()
        got.append(S._make_scanner(
            "parquet", path, (("columns", None),), conf))

    ths = [threading.Thread(target=race) for _ in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    assert len(got) == 8
    assert all(sc is got[0] for sc in got)  # ONE scanner, no duplicates


def test_scan_cache_accounting_consistent_under_race():
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache

    cache = DeviceScanCache(max_bytes=10_000)
    barrier = threading.Barrier(8)

    def race(i):
        barrier.wait()
        for j in range(50):
            key = ("p", i, j % 7)
            cache.get(key)
            cache.put(key, object(), 100 * (1 + j % 3))

    ths = [threading.Thread(target=race, args=(i,)) for i in range(8)]
    for t in ths:
        t.start()
    for t in ths:
        t.join(10)
    st = cache.stats()
    # byte accounting stayed single-entry: resident == sum over entries
    with cache._lock:
        real = sum(sz for (_, sz, _lid) in cache._entries.values())
    assert st["bytes"] == real
    assert st["bytes"] <= st["max_bytes"]


# ---------------------------------------------------------------------------
# 8. pipelined execution: host_prefetch overlaps the drain
# ---------------------------------------------------------------------------
def test_serve_parquet_prefetch_matches_oracle(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(7)
    n = 20_000
    pq.write_table(
        pa.table({
            "k": pa.array(rng.integers(0, 16, n).astype("int32")),
            "v": pa.array(rng.integers(0, 1000, n).astype("int64")),
        }),
        os.path.join(str(tmp_path), "t.parquet"), row_group_size=4096)
    plain = TpuSession({})
    oracle = sorted(
        plain.read.parquet(str(tmp_path)).group_by("k")
        .agg(A.agg(A.Sum(col("v")), "sv")).collect())
    served = TpuSession({"spark.rapids.tpu.serve.enabled": True})
    got = sorted(
        served.read.parquet(str(tmp_path)).group_by("k")
        .agg(A.agg(A.Sum(col("v")), "sv")).collect())
    assert got == oracle


def test_host_prefetch_runs_on_prefetch_pool(tmp_path):
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.exec.scan import TpuFileSourceScanExec
    from spark_rapids_tpu.sql.session import _make_scanner

    path = os.path.join(str(tmp_path), "t.parquet")
    pq.write_table(pa.table({
        "v": pa.array(np.arange(4096, dtype="int64"))}), path,
        row_group_size=1024)
    conf = RapidsConf({})
    scan = TpuFileSourceScanExec(
        conf, _make_scanner("parquet", path, (("columns", None),), conf),
        "parquet")
    scan.host_prefetch()
    assert scan._prefetch_dev is not None or scan._prefetch is not None
    rows = sum(b.num_rows for b in scan.execute_columnar())
    assert rows == 4096
    # futures were consumed by the drain, not re-read
    table = scan._prefetch_dev or scan._prefetch
    assert all(f is None for f in table)
