"""OOM retry + split-and-retry plane (memory/retry.py) and the
deterministic fault injector (faults.py).

Coverage, per the round-13 issue:
  * injector spec grammar (@N / %K / >C / ?K seeded) + determinism;
  * the OOM classifier over backend message patterns;
  * batch-split differential suite: depths 1-3 over the torture set
    (dict strings, all-null columns, zero-column count(*) batches,
    non-pow2 row counts) diffed row-exact against the unsplit batch,
    with the capacity-bucket/validity-padding invariants asserted;
  * the five-strategy aggregation matrix under forced splits, row-exact
    vs the CPU oracle;
  * retry -> success, split -> success, exhaustion -> typed
    TpuSplitAndRetryOOM (never a raw RESOURCE_EXHAUSTED escape);
  * named TpuOutOfDeviceMemory wrapping outside the harness;
  * serve integration: reservation released on OOM, ONE requeue with the
    forecast inflated, typed error on double failure;
  * reservation/semaphore leak audit across 8 failing queries;
  * shuffle fetch retry counters + capped exponential backoff;
  * the zero-overhead-off spy (no injector consulted, no harness
    machinery touched, with the confs at defaults);
  * watchdog retry-storm rule (live tick + offline replay) and the
    tpu_profile '== resilience ==' section.
"""
import threading

import numpy as np
import pytest

import spark_rapids_tpu  # noqa: F401
from spark_rapids_tpu import events as EV
from spark_rapids_tpu import faults
from spark_rapids_tpu import obs
from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar import ColumnarBatch, schema_of, split_batch
from spark_rapids_tpu.columnar.column import (
    choose_capacity,
    dict_column_from_pylist,
)
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.memory import (
    BufferCatalog,
    TpuOutOfDeviceMemory,
    TpuRetryOOM,
    TpuSemaphore,
    TpuSplitAndRetryOOM,
    is_device_oom,
    named_oom,
    with_oom_retry,
    with_oom_retry_nosplit,
)
from spark_rapids_tpu.memory.retry import concat_batches
from spark_rapids_tpu.serve import QueryScheduler, SharedPlanCache
from spark_rapids_tpu.sql import TpuSession
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit

from harness import compare_rows


@pytest.fixture(autouse=True)
def _clean_world():
    faults.uninstall()
    EV.uninstall()
    QueryScheduler.reset()
    SharedPlanCache.reset()
    BufferCatalog.reset()
    TpuSemaphore.reset()
    yield
    faults.uninstall()
    EV.uninstall()
    QueryScheduler.reset()
    SharedPlanCache.reset()
    BufferCatalog.reset()
    TpuSemaphore.reset()


NO_BACKOFF = {"spark.rapids.tpu.memory.oomRetry.backoffMs": 0}


def _q(sess):
    return (sess.range(0, 1024)
            .where(E.GreaterThanOrEqual(col("id"), lit(100)))
            .select(col("id"), E.Alias(E.Multiply(col("id"), lit(2)), "v"))
            .agg(A.agg(A.Sum(col("v")), "s"), A.agg(A.Count(None), "c")))


def _oracle():
    return _q(TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()


# ---------------------------------------------------------------------------
# 1. injector spec grammar + determinism
# ---------------------------------------------------------------------------
def test_fault_spec_nth_every_and_always():
    inj = faults.FaultInjector(RapidsConf(
        {"spark.rapids.tpu.test.faults.oom": "siteA@2,siteB%3,siteC"}))
    inj.check("oom", "siteA")  # arrival 1: no fire
    with pytest.raises(faults.InjectedOOM):
        inj.check("oom", "siteA")  # arrival 2
    inj.check("oom", "siteA")  # arrival 3: @2 fired once only
    for arrival in range(1, 7):
        if arrival % 3 == 0:
            with pytest.raises(faults.InjectedOOM):
                inj.check("oom", "siteB")
        else:
            inj.check("oom", "siteB")
    with pytest.raises(faults.InjectedOOM):
        inj.check("oom", "siteC")  # always


def test_fault_spec_cap_threshold_and_wildcard():
    inj = faults.FaultInjector(RapidsConf(
        {"spark.rapids.tpu.test.faults.oom": "Tpu*>512"}))
    inj.check("oom", "TpuSortExec", cap=512)  # not above
    with pytest.raises(faults.InjectedOOM):
        inj.check("oom", "TpuSortExec", cap=1024)
    inj.check("oom", "Other", cap=4096)  # pattern mismatch


def test_fault_spec_validation_rejects_bad_entries():
    for bad in ("site%0", "site@0", "site?0", "site@x", "site>-1"):
        with pytest.raises(ValueError):
            faults.FaultInjector(RapidsConf(
                {"spark.rapids.tpu.test.faults.oom": bad}))
    # fnmatch '?' inside a pattern survives when a real separator follows
    inj = faults.FaultInjector(RapidsConf(
        {"spark.rapids.tpu.test.faults.oom": "Tpu?ortExec@1"}))
    with pytest.raises(faults.InjectedOOM):
        inj.check("oom", "TpuSortExec")


def test_fault_spec_seeded_is_deterministic():
    def fires_at(seed):
        inj = faults.FaultInjector(RapidsConf({
            "spark.rapids.tpu.test.faults.oom": "s?8",
            "spark.rapids.tpu.test.faults.seed": seed}))
        for arrival in range(1, 9):
            try:
                inj.check("oom", "s")
            except faults.InjectedOOM:
                return arrival
        return None

    a = fires_at(7)
    assert a is not None and a == fires_at(7)
    # a different seed may pick a different arrival; same seed replays
    assert fires_at(13) == fires_at(13)


def test_injected_oom_classifies_as_device_oom():
    assert is_device_oom(faults.InjectedOOM("RESOURCE_EXHAUSTED: x"))
    assert is_device_oom(RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 bytes"))
    assert is_device_oom(RuntimeError("Failed to allocate request"))
    assert not is_device_oom(RuntimeError("shape mismatch"))
    assert not is_device_oom(TpuSplitAndRetryOOM("final"))
    # the named raw-site wrapper stays retryable by a surrounding harness
    assert is_device_oom(TpuOutOfDeviceMemory("raw"))


# ---------------------------------------------------------------------------
# 2. batch-split differential suite (torture set, depths 1-3)
# ---------------------------------------------------------------------------
def _torture_batch(n: int) -> ColumnarBatch:
    schema = schema_of(i=T.INT, d=T.DOUBLE, s=T.STRING, nul=T.LONG)
    data = {
        "i": [None if k % 7 == 0 else (k * 3) % 251 - 100 for k in range(n)],
        "d": [None if k % 11 == 0 else k / 3.0 - 5.0 for k in range(n)],
        "s": [None if k % 5 == 0 else ("x" * (k % 4)) + str(k)
              for k in range(n)],
        "nul": [None] * n,
    }
    batch = ColumnarBatch.from_pydict(data, schema)
    # ride a dict-encoded column alongside (aux planes must survive)
    dc = dict_column_from_pylist(
        [None if k % 3 == 0 else f"d{k % 6}" for k in range(n)])
    cols = list(batch.columns) + [dc]
    full = T.StructType(tuple(
        list(schema.fields) + [T.StructField("dict", T.STRING)]))
    return ColumnarBatch(cols, full, n)


def _split_rec(batch, depth):
    if depth == 0 or batch.num_rows < 2:
        return [batch]
    lo, hi = split_batch(batch)
    return _split_rec(lo, depth - 1) + _split_rec(hi, depth - 1)


@pytest.mark.parametrize("n", [5, 7, 1000])
@pytest.mark.parametrize("depth", [1, 2, 3])
def test_split_depths_row_exact_vs_unsplit_oracle(n, depth):
    batch = _torture_batch(n)
    want = batch.to_pydict()
    pieces = _split_rec(batch, depth)
    assert sum(p.num_rows for p in pieces) == n
    got = {k: [] for k in want}
    for p in pieces:
        # capacity-bucket invariant: every piece repacked to its own
        # sanctioned bucket, validity padding all-False beyond the rows
        for c in p.columns:
            assert c.capacity == choose_capacity(max(1, p.num_rows))
            v = np.asarray(c.validity)
            assert not v[p.num_rows:].any()
        for k, vs in p.to_pydict().items():
            got[k].extend(vs)
    assert got == want
    # and the pieces re-join row-exact through the standard concat path
    rejoined = concat_batches(RapidsConf({}), pieces)
    assert rejoined.to_pydict() == want


def test_split_zero_column_batch_keeps_capacity_bucket():
    schema = T.StructType(())
    b = ColumnarBatch([], schema, 1000, capacity=choose_capacity(1000))
    lo, hi = split_batch(b)
    assert (lo.num_rows, hi.num_rows) == (500, 500)
    assert lo.capacity == choose_capacity(500)
    assert hi.capacity == choose_capacity(500)


def test_split_floor_raises():
    b = ColumnarBatch.from_pydict({"a": [1]}, schema_of(a=T.INT))
    with pytest.raises(ValueError):
        split_batch(b)


# ---------------------------------------------------------------------------
# 3. five-strategy aggregation matrix under forced splits
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "strategy", ["MATMUL", "SCATTER", "RADIX", "PALLAS"])
def test_agg_strategies_row_exact_under_forced_splits(strategy):
    n = 1000  # non-pow2; capacity bucket 1024 > the >256 fault threshold
    data = {
        "k": [i % 7 if i % 11 else None for i in range(n)],
        "a": [(i * 13) % 400 - 200 for i in range(n)],
        "b": [None if i % 9 == 0 else i * 5 for i in range(n)],
    }
    schema = schema_of(k=T.INT, a=T.LONG, b=T.LONG)

    def build(s):
        return (s.create_dataframe(data, schema).group_by("k")
                .agg(A.agg(A.Sum(col("a")), "sa"),
                     A.agg(A.Min(col("a")), "mn"),
                     A.agg(A.Max(col("b")), "mx"),
                     A.agg(A.Count(col("b")), "cb"),
                     A.agg(A.Count(None), "cs")))

    cpu = build(TpuSession({"spark.rapids.tpu.sql.enabled": False})).collect()
    sess = TpuSession({
        "spark.rapids.tpu.sql.agg.strategy": strategy,
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec>256",
        **NO_BACKOFF})
    got = build(sess).collect()
    compare_rows(cpu, got)
    inj = faults.active()
    assert inj is not None and inj.fired(), \
        "fault never fired — the split path was not exercised"


# ---------------------------------------------------------------------------
# 4. retry / split / exhaustion through the engine
# ---------------------------------------------------------------------------
def test_retry_once_then_success_with_events():
    oracle = _oracle()
    sess = TpuSession({
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec@1",
        "spark.rapids.tpu.eventLog.enabled": True, **NO_BACKOFF})
    assert _q(sess).collect() == oracle
    evs = [r for r in sess.events.records() if r["event"] == "oom_retry"]
    assert evs, "no oom_retry events recorded"
    assert all(r["op"] == "TpuHashAggregateExec" for r in evs)


def test_split_paths_for_sort_join_project():
    n = 1000
    data = {"k": [i % 13 for i in range(n)],
            "v": [None if i % 17 == 0 else (i * 7) % 500 for i in range(n)]}
    schema = schema_of(k=T.INT, v=T.LONG)
    rdata = {"k": [i for i in range(13)],
             "w": [i * 100 for i in range(13)]}
    rschema = schema_of(k=T.INT, w=T.LONG)

    def builds(s):
        left = s.create_dataframe(data, schema)
        right = s.create_dataframe(rdata, rschema)
        return {
            "TpuProjectExec": left.select(
                col("k"), E.Alias(E.Add(col("v"), lit(1)), "v1")),
            "TpuSortExec": left.order_by("v", "k"),
            "TpuShuffledHashJoinExec": left.join(right, "k"),
        }

    cpu = {name: df.collect() for name, df in builds(
        TpuSession({"spark.rapids.tpu.sql.enabled": False})).items()}
    for name, want in cpu.items():
        sess = TpuSession({
            "spark.rapids.tpu.test.faults.oom": f"{name}*>512",
            **NO_BACKOFF})
        got = builds(sess)[name].collect()
        ignore_order = name != "TpuSortExec"
        compare_rows(want, got, ignore_order=ignore_order)
        inj = faults.active()
        assert inj is not None and inj.fired(), name
        faults.uninstall()


def test_exhaustion_raises_typed_error_not_raw():
    sess = TpuSession({
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec",
        "spark.rapids.tpu.memory.oomRetry.maxSplitDepth": 2, **NO_BACKOFF})
    with pytest.raises(TpuSplitAndRetryOOM) as ei:
        _q(sess).collect()
    e = ei.value
    assert e.op == "TpuHashAggregateExec"
    assert e.attempts >= 2 and e.split_depth == 2
    assert "RESOURCE_EXHAUSTED" in str(e)  # cause named, type is ours


def test_retry_disabled_propagates_raw():
    sess = TpuSession({
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec",
        "spark.rapids.tpu.memory.oomRetry.enabled": False})
    with pytest.raises(faults.InjectedOOM):
        _q(sess).collect()


def test_nosplit_harness_raises_typed_retry_oom():
    conf = RapidsConf(NO_BACKOFF)

    def boom():
        raise RuntimeError("RESOURCE_EXHAUSTED: no memory")

    with pytest.raises(TpuRetryOOM) as ei:
        with_oom_retry_nosplit("mergesite", boom, conf)
    assert ei.value.op == "mergesite" and ei.value.attempts == 2


def test_named_oom_wraps_raw_failures():
    with pytest.raises(TpuOutOfDeviceMemory) as ei:
        with named_oom("scan.decode"):
            raise RuntimeError("RESOURCE_EXHAUSTED: upload failed")
    assert ei.value.op == "scan.decode"
    assert "largest spillable" in str(ei.value)
    # non-OOM failures pass through untouched
    with pytest.raises(ValueError):
        with named_oom("scan.decode"):
            raise ValueError("not an oom")


def test_ensure_headroom_respects_host_cap_without_budget():
    import jax.numpy as jnp

    from spark_rapids_tpu.memory import SpillableHandle, TIER_DISK

    # NO device budget (backend reports nothing) but a tiny host cap:
    # the emergency spill must still push the host overage to disk —
    # recovering from device exhaustion must not manufacture host
    # exhaustion
    cat = BufferCatalog.reset(RapidsConf({
        "spark.rapids.tpu.memory.host.spillStorageSize": 1}))
    assert cat.budget is None
    h = SpillableHandle({"x": jnp.zeros(4096, jnp.int32)}, catalog=cat)
    freed = cat.ensure_headroom()
    assert freed == h.size
    assert h.tier == TIER_DISK, "host overage not drained to disk"
    assert cat.metrics.host_to_disk == 1
    h.close()


def test_harness_releases_pressure_by_spilling():
    import jax.numpy as jnp

    from spark_rapids_tpu.memory import SpillableHandle, TIER_HOST

    cat = BufferCatalog.reset(RapidsConf({}))
    h = SpillableHandle({"x": jnp.zeros(1024, jnp.int32)}, catalog=cat)
    conf = RapidsConf(NO_BACKOFF)
    calls = [0]

    def attempt(b):
        calls[0] += 1
        if calls[0] == 1:
            raise RuntimeError("RESOURCE_EXHAUSTED: injected")
        return b

    b = ColumnarBatch.from_pydict({"a": [1, 2, 3]}, schema_of(a=T.INT))
    out = with_oom_retry("op", attempt, b, conf)
    assert out is b
    # the retry's pressure release spilled the catalog buffer to host
    assert h.tier == TIER_HOST
    assert cat.metrics.device_to_host == 1
    h.close()


# ---------------------------------------------------------------------------
# 5. serve integration: requeue once, reservation hygiene
# ---------------------------------------------------------------------------
def _serve_settings(extra=None):
    s = {"spark.rapids.tpu.serve.enabled": True, **NO_BACKOFF}
    s.update(extra or {})
    return s


def test_serve_requeues_once_with_inflated_forecast():
    settings = _serve_settings({
        # first submit: fused-plan probe (@1) then the streaming harness
        # (@2, maxAttempts=1, depth 0) -> typed OOM -> requeue; the
        # requeued run's fused-plan probe (arrival 3) passes
        "spark.rapids.tpu.test.faults.oom":
            "TpuHashAggregateExec@1,TpuHashAggregateExec@2",
        "spark.rapids.tpu.memory.oomRetry.maxAttempts": 1,
        "spark.rapids.tpu.memory.oomRetry.maxSplitDepth": 0})
    QueryScheduler.reset(RapidsConf(settings))
    oracle = _oracle()
    sess = TpuSession(settings)
    assert _q(sess).collect() == oracle
    st = QueryScheduler.instance().stats()
    assert st["oom_requeues"] == 1, st
    assert st["active"] == 0 and st["waiting"] == 0, st
    assert BufferCatalog.get().reserved_bytes == 0


def test_serve_double_oom_raises_typed_after_one_requeue():
    settings = _serve_settings({
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec",
        "spark.rapids.tpu.memory.oomRetry.maxAttempts": 1,
        "spark.rapids.tpu.memory.oomRetry.maxSplitDepth": 0})
    QueryScheduler.reset(RapidsConf(settings))
    sess = TpuSession(settings)
    with pytest.raises(TpuSplitAndRetryOOM):
        _q(sess).collect()
    st = QueryScheduler.instance().stats()
    assert st["oom_requeues"] == 1, st
    assert st["active"] == 0 and st["waiting"] == 0, st
    assert BufferCatalog.get().reserved_bytes == 0


def test_leak_audit_eight_failing_queries():
    settings = _serve_settings({
        "spark.rapids.tpu.test.faults.oom": "*",
        "spark.rapids.tpu.memory.oomRetry.maxAttempts": 1,
        "spark.rapids.tpu.memory.oomRetry.maxSplitDepth": 0})
    QueryScheduler.reset(RapidsConf(settings))
    sess = TpuSession(settings)
    failures = 0
    for _ in range(8):
        try:
            _q(sess).collect()
        except (TpuSplitAndRetryOOM, TpuRetryOOM, TpuOutOfDeviceMemory):
            failures += 1
    assert failures == 8
    cat = BufferCatalog.get()
    assert cat.reserved_bytes == 0, "leaked admission reservations"
    assert TpuSemaphore.get().holder_names() == [], "leaked semaphore"
    st = QueryScheduler.instance().stats()
    assert st["active"] == 0 and st["waiting"] == 0, st
    with cat._lock:
        pinned = [h for h in cat._buffers.values() if h.pinned]
    assert not pinned, "leaked pinned buffers"


# ---------------------------------------------------------------------------
# 6. shuffle fetch: capped exponential backoff + retry counters
# ---------------------------------------------------------------------------
def test_fetch_backoff_is_capped_exponential():
    from spark_rapids_tpu.shuffle.network import ShuffleClient

    c = ShuffleClient(("127.0.0.1", 1), retry_wait_s=0.2,
                      retry_wait_cap_s=0.5)
    for attempt in range(8):
        span = min(0.5, 0.2 * (1 << attempt))
        for _ in range(16):
            d = c._backoff(attempt)
            assert span * 0.5 <= d <= span


def test_network_fetch_retries_counted_and_surfaced():
    from spark_rapids_tpu.shuffle.network import (
        NetworkShuffleTransport,
        ShuffleClient,
        ShuffleServer,
    )

    server = ShuffleServer()
    try:
        faults.install(RapidsConf(
            {"spark.rapids.tpu.test.faults.fetch": "network_fetch@1"}))
        client = ShuffleClient(server.address, retry_wait_s=0.01)
        t = NetworkShuffleTransport(server=None, remotes=(),
                                    owns_server=False)
        t._clients = [client]
        assert client.fetch_serialized(1, 0) == []
        assert client.retry_count == 1 and client.failure_count == 0
        st = t.stats()
        assert st["fetch_retries"] == 1 and st["fetch_failures"] == 0
    finally:
        server.close()


def test_network_fetch_exhaustion_counts_failure():
    from spark_rapids_tpu.shuffle.network import (
        FetchFailedError,
        ShuffleClient,
    )

    c = ShuffleClient(("127.0.0.1", 9), retries=2, retry_wait_s=0.01)
    with pytest.raises(FetchFailedError):
        c.fetch_serialized(1, 0)
    assert c.failure_count == 1 and c.retry_count == 1


# ---------------------------------------------------------------------------
# 7. zero-overhead-off spy
# ---------------------------------------------------------------------------
def test_zero_overhead_when_confs_off(monkeypatch):
    from spark_rapids_tpu.memory import retry as retry_mod

    consulted = []
    orig_check = faults.FaultInjector.check

    def spy_check(self, *a, **k):
        consulted.append("check")
        return orig_check(self, *a, **k)

    monkeypatch.setattr(faults.FaultInjector, "check", spy_check)
    recovered = []
    monkeypatch.setattr(
        retry_mod, "_release_pressure",
        lambda *a, **k: recovered.append(1) or 0)
    sess = TpuSession({})  # defaults: injector off, retry on but idle
    rows = _q(sess).collect()
    assert rows == _oracle()
    assert faults.enabled() is False and faults.active() is None
    assert consulted == [], "injector consulted with confs off"
    assert recovered == [], "recovery machinery ran on a clean query"


# ---------------------------------------------------------------------------
# 8. watchdog retry-storm + profiler resilience section
# ---------------------------------------------------------------------------
def test_watchdog_retry_storm_alerts_once_per_episode():
    from spark_rapids_tpu.obs.registry import MetricsRegistry
    from spark_rapids_tpu.obs.watchdog import (
        RETRY_STORM,
        Watchdog,
        WatchdogRules,
    )

    reg = MetricsRegistry()
    dog = Watchdog(reg, WatchdogRules(retry_storm_threshold=4), budget=0)
    for _ in range(4):
        reg.note_oom_retry("TpuSortExec")
    new = dog.check_now()
    assert [a.kind for a in new] == [RETRY_STORM]
    assert new[0].detail == "TpuSortExec" and new[0].value == 4
    assert dog.check_now() == []  # still storming: one alert per episode


def test_replay_alerts_flags_retry_storm():
    from spark_rapids_tpu.obs.watchdog import (
        RETRY_STORM,
        WatchdogRules,
        replay_alerts,
    )

    base = 1_000_000
    events = [
        {"ts": base + i * 1_000_000, "event": "oom_retry",
         "op": "TpuHashAggregateExec", "kind": "retry", "attempt": 1,
         "depth": 0, "watermark": 0, "budget": None}
        for i in range(5)
    ]
    alerts = replay_alerts(
        events, WatchdogRules(retry_storm_threshold=5))
    assert [a.kind for a in alerts] == [RETRY_STORM]


def test_profiler_resilience_section(tmp_path):
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "tpu_profile", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "tpu_profile.py"))
    tp = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tp)

    sess = TpuSession({
        "spark.rapids.tpu.eventLog.dir": str(tmp_path),
        "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec>256",
        **NO_BACKOFF})
    _q(sess).collect()
    sess.close()
    events = tp.load_events([str(tmp_path)])
    report, violations = tp.build_report(events)
    assert violations == 0, report
    assert "== resilience ==" in report
    body = report.split("== resilience ==", 1)[1].split("==", 1)[0]
    assert "TpuHashAggregateExec" in body
    assert "batch split" in body
    # and the events render on the Perfetto resilience track
    trace = EV.chrome_trace(events)
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("ph") == "M"}
    assert "resilience" in tracks


def test_obs_twins_count_retries_and_splits():
    from spark_rapids_tpu.obs.registry import MetricsRegistry

    reg = MetricsRegistry()
    obs.install(reg)
    try:
        sess = TpuSession({
            "spark.rapids.tpu.test.faults.oom": "TpuHashAggregateExec>256",
            **NO_BACKOFF})
        _q(sess).collect()
        retries = sum(
            v for _, v in reg._vals["tpu_oom_retries"].items())
        splits = sum(
            v for _, v in reg._vals["tpu_batch_splits"].items())
        assert retries >= 1 and splits >= 1
    finally:
        obs.uninstall()


# ---------------------------------------------------------------------------
# 9. chaos matrix: injected faults at every covered site — row-exact
#    completion or a typed error, never a raw escape, never a leak
# ---------------------------------------------------------------------------
TYPED = (TpuSplitAndRetryOOM, TpuRetryOOM, TpuOutOfDeviceMemory,
         faults.InjectedFault)


@pytest.mark.parametrize("channel,spec", [
    ("oom", "*>512"),
    ("oom", "*@1"),
    ("oom", "*?3"),
    ("compile", "*@2"),
])
def test_chaos_every_site(channel, spec):
    n = 1000
    data = {"k": [i % 13 for i in range(n)],
            "v": [None if i % 17 == 0 else (i * 7) % 500
                  for i in range(n)]}
    schema = schema_of(k=T.INT, v=T.LONG)

    rdata = {"k": list(range(13)), "w": [i * 100 for i in range(13)]}
    rschema = schema_of(k=T.INT, w=T.LONG)

    def builds(s):
        df = s.create_dataframe(data, schema)
        right = s.create_dataframe(rdata, rschema)
        return [
            df.select(col("k"), E.Alias(E.Add(col("v"), lit(1)), "v1")),
            df.order_by("v", "k"),
            df.group_by("k").agg(A.agg(A.Sum(col("v")), "sv"),
                                 A.agg(A.Count(None), "c")),
            df.join(right, "k"),
        ]

    cpu = [d.collect() for d in builds(
        TpuSession({"spark.rapids.tpu.sql.enabled": False}))]
    for i, want in enumerate(cpu):
        faults.uninstall()
        sess = TpuSession({
            f"spark.rapids.tpu.test.faults.{channel}": spec,
            **NO_BACKOFF})
        try:
            got = builds(sess)[i].collect()
            compare_rows(want, got, ignore_order=(i != 1))
        except TYPED:
            pass  # typed, named failure is an accepted chaos outcome
        assert BufferCatalog.get().reserved_bytes == 0
        assert TpuSemaphore.get().holder_names() == []
