"""The engine's names on the profiler's clock (exec/base.py): every program
is jitted under its cached_pipeline site's word, the phases inside a fused
program carry the same words as scopes, every host span carries its query
and nests under ``TpuSession.query``, and with ``sql.trace.enabled`` off no
annotation is built at all."""
import glob
import os
import re

import numpy as np
import pytest

from spark_rapids_tpu.exec import base as XB
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.sql import TpuSession

TRACE = "spark.rapids.tpu.sql.trace.enabled"
FUSION = "spark.rapids.tpu.sql.stageFusion"
#: float sums on the device, as both benchmark configurations state
CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
ROWS, ROW_GROUP = 3 * 4099, 4099  # sizes no other test compiles for
ENGINE_SPAN = re.compile(r"^(\w+Exec(\.\w+)*|TpuSession\.\w+)$")


def _write_table(directory) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    rng = np.random.default_rng(26)
    table = pa.table({
        "k": pa.array(rng.integers(0, 7, ROWS, dtype=np.int32)),
        "flag": pa.array(rng.choice(["A", "N", "R"], ROWS)),
        "qty": pa.array(rng.integers(1, 50, ROWS, dtype=np.int32)),
        "price": pa.array(np.round(rng.random(ROWS) * 100, 2)),
    })
    os.makedirs(directory, exist_ok=True)
    pq.write_table(table, os.path.join(directory, "t.parquet"),
                   row_group_size=ROW_GROUP)
    return str(directory)


def _by_int_key(sess, directory):
    """Fixed-width keys: the fused scan->filter->aggregate stage."""
    return (sess.read.parquet(directory)
            .where(E.LessThanOrEqual(col("qty"), lit(40)))
            .group_by("k")
            .agg(A.agg(A.Sum(col("price")), "s"),
                 A.agg(A.Count(col("qty")), "n")))


def _by_string_key(sess, directory):
    """A string key and an order by: per-row-group programs, a merge of
    the partials, a projection and a sort (TPC-H Q1's shape)."""
    return (sess.read.parquet(directory)
            .where(E.LessThanOrEqual(col("qty"), lit(40)))
            .group_by("flag")
            .agg(A.agg(A.Sum(col("price")), "s"),
                 A.agg(A.Average(col("qty")), "a"))
            .order_by("flag"))


# ---------------------------------------------------------------------------
# programs and scopes, from the lowering text
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def lowered(tmp_path_factory):
    """{site: lowering text with locations} of every program the two
    queries build, caught at the one chokepoint."""
    from spark_rapids_tpu import xla_cost

    assert not xla_cost.harvesting(), (
        "an earlier test file of this worker left a cost consumer on (an "
        "event sink, the obs plane or FORCE_HARVEST): programs come back "
        "wrapped and cannot be lowered here")
    directory = _write_table(tmp_path_factory.mktemp("names") / "t")
    texts = {}
    real = XB.cached_pipeline

    def spy(cache, key, site, build, *args, **kwargs):
        fn = real(cache, key, site, build, *args, **kwargs)

        def call(*call_args):
            word = site or fn.__name__
            if word not in texts:
                texts[word] = fn.lower(*call_args).as_text(debug_info=True)
            return fn(*call_args)

        return call

    XB.clear_pipeline_caches()
    XB.cached_pipeline = spy
    try:
        fused = TpuSession(dict(CONF, **{FUSION: "ON"}))
        assert len(_by_int_key(fused, directory).collect()) == 7
        plain = TpuSession(dict(CONF, **{FUSION: "OFF"}))
        assert len(_by_string_key(plain, directory).collect()) == 3
    finally:
        XB.cached_pipeline = real
        XB.clear_pipeline_caches()  # drop the spies with their programs
    return texts


#: the cached_pipeline sites the benchmark's two cells reach, and the
#: scopes each one's operations have to carry
SITES = {
    "agg_stage": ("pq_decode", "fused_chain", "agg_update", "agg_merge",
                  "project"),
    "agg_update": ("fused_chain", "agg_update"),
    "pq_decode": ("pq_decode",),
    "upload_unpack": ("upload_unpack",),
    "project": (),
    "sort": (),
}


@pytest.mark.parametrize("site", sorted(SITES))
def test_program_is_named_after_its_site_and_carries_its_scopes(
        lowered, site):
    assert site in lowered, sorted(lowered)
    text = lowered[site]
    assert re.search(r"module @jit_%s\b" % site, text), text[:200]
    assert "jit_run" not in text
    for scope in SITES[site]:
        assert re.search(r'loc\("[^"]*\b%s\b' % scope, text), (site, scope)


def test_every_program_the_queries_built_has_an_engine_word(lowered):
    assert set(lowered) <= set(XB.PROGRAM_WORDS)
    assert set(SITES) <= set(lowered)
    assert set(XB.SCOPE_WORDS) <= set(XB.PROGRAM_WORDS) | {"agg_merge"}


def test_program_refuses_a_word_that_is_not_in_the_vocabulary():
    with pytest.raises(AssertionError):
        XB.program("run")

    @XB.program("sort")
    def run(x):
        return x

    assert run.__name__ == run.__qualname__ == "sort"


def test_other_programs_keep_names_of_their_own():
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar.column import _jitted_materialize
    from spark_rapids_tpu.expr import eval as EV

    assert _jitted_materialize().__name__ in XB.OTHER_PROGRAM_WORDS
    fn = EV._compiled((), 8, ())
    assert fn.__name__ in XB.OTHER_PROGRAM_WORDS
    assert "module @jit_eval_exprs" in fn.lower([]).as_text()
    del jnp


# ---------------------------------------------------------------------------
# host spans, from a real trace of the CPU profiler
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One cold query under the profiler: [(thread, name, start, end,
    stats)] of the engine's spans."""
    import jax
    from jax.profiler import ProfileData

    root = tmp_path_factory.mktemp("spans")
    directory = _write_table(root / "t")
    out = str(root / "trace")
    sess = TpuSession(dict(CONF, **{TRACE: True, FUSION: "OFF"}))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        rows = _by_string_key(sess, directory).collect()
    finally:
        jax.profiler.stop_trace()
    assert len(rows) == 3
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        # a line a thread; the CPU profiler calls every one "python"
        for thread, line in enumerate(plane.lines):
            for e in line.events:
                if ENGINE_SPAN.match(e.name):
                    spans.append((thread, e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  dict(e.stats)))
    return spans, sess


def test_every_span_carries_the_query_and_nests_under_it(traced):
    spans, _ = traced
    queries = [s for s in spans if s[1] == "TpuSession.query"]
    assert len(queries) == 1
    _, _, q0, q1, qstats = queries[0]
    assert isinstance(qstats["query"], int)
    names = {s[1] for s in spans}
    scan, agg = "TpuFileSourceScanExec.", "TpuHashAggregateExec."
    assert {"TpuSession.plan", scan + "decode", scan + "cache_lookup",
            scan + "read_file", scan + "page_plan", scan + "plan_wait",
            scan + "upload", scan + "unpack_dispatch",
            scan + "decode_dispatch", agg + "update", agg + "merge",
            agg + "merge.lengths",
            agg + "merge.pull",
            agg + "merge.concat", agg + "merge.reduce", agg + "merge.eval",
            "TpuSortExec.sort", "ColumnarToRowExec.to_rows",
            "ColumnarToRowExec.d2h"} <= names
    for thread, name, s0, s1, stats in spans:
        assert stats.get("query") == qstats["query"], (thread, name)
        assert q0 <= s0 and s1 <= q1, (thread, name)


def test_the_decode_pool_threads_carry_the_same_names_and_query(traced):
    spans, _ = traced
    main = next(s[0] for s in spans if s[1] == "TpuSession.query")
    plans = [s for s in spans if s[1] == "TpuFileSourceScanExec.page_plan"]
    pool = [s for s in plans if s[0] != main]
    assert pool, sorted({s[0] for s in plans})
    decode = next(s for s in spans
                  if s[1] == "TpuFileSourceScanExec.decode")
    for s in pool:  # inside the decode section that submitted them
        assert decode[2] <= s[2] and s[3] <= decode[3]


def test_counts_sit_on_the_boundary_they_size(traced):
    spans, _ = traced
    by_name = {}
    for s in spans:
        by_name.setdefault(s[1].partition(".")[2], []).append(s[4])
    assert all(st["bytes"] > 0 for st in by_name["upload"])
    assert sum(st["bytes"] for st in by_name["d2h"]) > 0
    look = by_name["cache_lookup"]
    assert [st["cache"] for st in look] == ["miss"]
    assert look[0]["hits"] == 0 and look[0]["lookups"] == 3
    assert "host_decode" not in by_name  # every column took the device


def test_sections_are_metrics_whether_or_not_they_are_spans(traced):
    _, sess = traced
    report = sess.explain_metrics()
    for metric in ("readFileTime", "pagePlanTime", "planWaitTime",
                   "uploadTime", "unpackDispatchTime", "decodeDispatchTime",
                   "cacheLookupTime", "mergePullTime", "mergeConcatTime",
                   "mergeReduceTime", "d2hTime", "toRowsTime"):
        assert metric + "=" in report, metric
    assert "collect boundary (ColumnarToRowExec):" in report
    assert report.splitlines()[-1].startswith("memory")


# ---------------------------------------------------------------------------
# off is free
# ---------------------------------------------------------------------------
def test_with_tracing_off_no_annotation_is_built(tmp_path, monkeypatch):
    import jax

    built = []

    class Refuses:
        def __init__(self, *args, **kwargs):
            built.append(args)
            raise AssertionError(f"TraceAnnotation{args} built with "
                                 "sql.trace.enabled off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refuses)
    directory = _write_table(tmp_path / "t")
    sess = TpuSession(dict(CONF, **{FUSION: "OFF"}))
    assert len(_by_string_key(sess, directory).collect()) == 3
    # the sections still timed: a metric each, always on
    assert "uploadTime=" in sess.explain_metrics()
    assert len(_by_int_key(sess, directory).collect()) == 7
    assert built == []
    # and the same query with tracing on does build them
    on = TpuSession(dict(CONF, **{TRACE: True}))
    with pytest.raises(AssertionError, match="TpuSession.query"):
        _by_int_key(on, directory).collect()


def test_query_ids_are_taken_with_events_and_obs_off(tmp_path):
    from spark_rapids_tpu.sql import session as S

    directory = _write_table(tmp_path / "t")
    sess = TpuSession(CONF)
    before = S._QUERY_SEQ[0]
    _by_int_key(sess, directory).collect()
    _by_int_key(sess, directory).collect()
    assert S._QUERY_SEQ[0] == before + 2
    assert sess._active_query is None  # the ledger's id: events/obs only
    assert XB.current_query() is None  # and none leaks past the drain


def test_a_section_names_its_metric_by_one_rule():
    assert XB.section_metric("read_file") == "readFileTime"
    assert XB.section_metric("d2h") == "d2hTime"
    assert XB.section_metric("merge.pull") == "mergePullTime"
    assert XB.section_metric("unpack_dispatch") == "unpackDispatchTime"


def test_carry_binds_the_section_and_the_query_to_a_pool_task():
    from concurrent.futures import ThreadPoolExecutor

    from spark_rapids_tpu.conf import RapidsConf

    class Leaf(XB.TpuExec):
        pass

    leaf = Leaf(RapidsConf({}))
    seen = {}

    def task():
        seen["query"] = XB.current_query()
        with XB.phase("page_plan") as span:
            seen["span"] = span
        return 1

    assert XB.carry(task) is task  # nothing to bind: no wrapper at all
    with ThreadPoolExecutor(1) as pool:
        with XB.query_scope(41), leaf.op_timed("decode"):
            bound = XB.carry(task)
        assert pool.submit(bound).result() == 1
        assert seen["query"] == 41 and seen["span"] is XB.NO_SPAN
        assert leaf.metrics["pagePlanTime"].value > 0
        # the pool's thread keeps nothing of the task
        assert pool.submit(XB.current_query).result() is None
    # below no exec, a phase is nothing
    with XB.phase("upload") as span:
        assert span is XB.NO_SPAN
    assert "uploadTime" not in leaf.metrics
