"""``DataFrame.cache()``: a plan marked once is filled by the first action
and served from the device by every later one, as device batches on one
device and as one shard of global planes on every device of a mesh
(``exec/basic.TpuInMemoryTableScanExec``, ``sql/cache.py``). At the
``rehearse`` size of the benchmark's ``tpcds_sf100_store_sales_mesh4``
deployment, on the virtual CPU devices ``conftest.py`` sets up."""
import glob
import os
import re
import sys

import numpy as np
import pytest

import jax

from spark_rapids_tpu.exec import base as XB
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr.expressions import col
from spark_rapids_tpu.memory.catalog import BufferCatalog
from spark_rapids_tpu.sql import TpuSession

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
CELL = "store_sales_sf100.cached_report.mesh4"
P = "spark.rapids.tpu."
FLOAT_AGG = {P + "sql.variableFloatAgg.enabled": True}
MESH4 = dict(FLOAT_AGG, **{P + "shuffle.mode": "ici", P + "mesh.devices": 4})
ONE = dict(FLOAT_AGG, **{P + "shuffle.mode": "host"})
PLACEMENTS = {"mesh4": MESH4, "one_device": ONE}
SEED = 2**31 + 30
CACHED = "TpuInMemoryTableScanExec"
ENGINE_SPAN = re.compile(r"^(\w+Exec(\.\w+)*|TpuSession\.\w+)$")


@pytest.fixture(scope="module")
def cell():
    for p in (ROOT, BENCH):
        if p not in sys.path:
            sys.path.insert(0, p)
    import loader

    return loader.load_cell(CELL)


@pytest.fixture(scope="module")
def table(cell, tmp_path_factory):
    """(directory, path, query module, reference answer) at the
    configuration's rehearsal size: 18 row groups, so the four shards
    hold 5, 5, 4 and 4 of them and the last one is short."""
    size = cell["config"]["rehearse"]
    directory = str(tmp_path_factory.mktemp("cached"))
    path = cell["generator"].generate(
        cell["config"], SEED, directory, size["rows"],
        size["row_group_rows"])
    query = cell["queries"][0]
    return directory, path, query, query.reference(path)


def _close(sess):
    sess.close()  # frees what the session cached


def _held(got, want, query, limit=None):
    """Keys and integer columns exact, the float sum within the limit."""
    got, want = sorted(got), sorted(want)
    assert [(r[0], r[2], r[3]) for r in got] == [
        (r[0], r[2], r[3]) for r in want]
    worst = max(abs(g[1] - w[1]) / abs(w[1]) for g, w in zip(got, want))
    assert worst <= (query.FLOAT_LIMIT if limit is None else limit), worst


def _uncached(query, sess, directory):
    """The cell's query without ``.cache()``: cell 1's frame."""
    import loader

    plain = loader.load_module("query", "queries",
                               "store_sales_quantity_report")
    assert plain.DATE_CUT == query.DATE_CUT
    return plain.frame(sess, directory)


def _relation(sess):
    (rel,) = sess.cache_manager.relations()
    return rel


def _find(plan, name):
    node = getattr(plan, "tpu_child", plan)
    while node.node_name != name:
        node = node.children[0]
    return node


# ---------------------------------------------------------------------------
# (a) the same answer wherever the table lives
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("cached", [True, False], ids=["cached", "uncached"])
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_answer_is_the_reference_wherever_the_table_lives(
        table, placement, cached):
    directory, _, query, want = table
    sess = TpuSession(PLACEMENTS[placement])
    frame = (query.frame if cached
             else lambda s, d: _uncached(query, s, d))
    first = frame(sess, directory).collect()
    assert sess.plan_fallbacks() == []
    again = frame(sess, directory).collect()
    _held(first, want, query)
    assert sorted(again) == sorted(first)
    assert bool(sess.cache_manager) is cached
    if cached:
        rel = _relation(sess)
        assert rel.rows == len_rows(table) and rel.fills == 1
        assert rel.shards == (4 if placement == "mesh4" else 1)
        assert rel.hits == 1
    _close(sess)


def len_rows(table) -> int:
    import pyarrow.parquet as pq

    return pq.ParquetFile(table[1]).metadata.num_rows


def test_a_frame_built_anew_over_the_same_plan_finds_the_relation(table):
    """The harness rebuilds its DataFrame in every query, and Spark serves
    any plan that contains a cached one: a frame that never called
    ``cache()`` itself is served too."""
    directory, _, query, want = table
    sess = TpuSession(MESH4)
    marked = sess.read.parquet(directory).cache()
    assert marked.is_cached and not _relation(sess).filled
    assert sess.read.parquet(directory).is_cached
    _held(_uncached(query, sess, directory).collect(), want, query)
    rel = _relation(sess)
    assert rel.filled and rel.fills == 1 and rel.hits == 0
    _held(query.frame(sess, directory).collect(), want, query)
    assert rel.fills == 1 and rel.hits == 1
    # persist() is cache(); another plan over the same file is not cached
    assert sess.read.parquet(directory).persist().is_cached
    assert not sess.read.parquet(
        directory, columns=["ss_quantity"]).is_cached
    _close(sess)


def test_explain_names_the_cached_scan(table):
    directory, _, query, _ = table
    sess = TpuSession(MESH4)
    text = query.frame(sess, directory).explain()
    assert "<InMemoryTableScanExec> will run on TPU (cached: not filled" \
        in text
    query.frame(sess, directory).collect()
    rel = _relation(sess)
    line = next(ln for ln in query.frame(sess, directory).explain()
                .splitlines() if "InMemoryTableScanExec" in ln)
    assert (f"cached: {rel.rows} rows, {rel.bytes} bytes resident on 4 "
            "device(s)") in line
    assert CACHED + f"({rel.rows} rows" in sess.explain_metrics()
    assert sess.plan_fallbacks() == []
    _close(sess)


# ---------------------------------------------------------------------------
# (b) the second action stages nothing and compiles nothing
# ---------------------------------------------------------------------------
def _pointers(rel):
    return [s.data.unsafe_buffer_pointer()
            for plane in rel.planes.cols for s in plane.addressable_shards]


def test_second_action_on_a_mesh_stages_and_compiles_nothing(table):
    directory, _, query, want = table
    sess = TpuSession(MESH4)
    query.frame(sess, directory).collect()
    first = sess.last_executed_plan.tpu_child
    rel = _relation(sess)
    assert first.mesh_actuals["staging"]["source"] == "cached"
    assert first.metrics["h2dBytes"].value == rel.bytes > 0
    scan = _find(first, "TpuFileSourceScanExec")
    assert scan.metrics["hostDecodeTime"].value > 0
    assert scan.metrics["uploadTime"].value > 0
    arrays = [id(a) for a in rel.planes.cols]
    held = _pointers(rel)
    assert len(held) == 4 * 2 * 4  # shards x planes a column x columns
    compiles = XB.COMPILE_COUNTER.snapshot()[0]
    for hits in (1, 2):
        _held(query.frame(sess, directory).collect(), want, query)
        plan = sess.last_executed_plan.tpu_child
        assert plan is not first
        assert plan.metrics["h2dBytes"].value == 0
        assert plan.mesh_actuals["staging"]["source"] == "cached"
        assert plan.mesh_actuals["staging"]["staged_bytes"] == list(
            rel.planes.staged_bytes)
        scan = _find(plan, "TpuFileSourceScanExec")
        assert "hostDecodeTime" not in scan.metrics
        assert "uploadTime" not in scan.metrics
        assert _find(plan, CACHED).metrics["cacheHits"].value == 1
        assert rel.hits == hits and rel.fills == 1
        # the same jax.Array objects, the same device buffers
        assert [id(a) for a in rel.planes.cols] == arrays
        assert _pointers(rel) == held
    assert XB.COMPILE_COUNTER.snapshot()[0] == compiles
    _close(sess)


def test_second_action_on_one_device_reads_no_file(table):
    directory, _, query, want = table
    sess = TpuSession(ONE)
    query.frame(sess, directory).collect()
    rel = _relation(sess)
    kept = [id(b) for part in rel.batches for b in part]
    assert kept and rel.part_sizes and not any(
        getattr(b, "exclusive", False) for p in rel.batches for b in p)
    compiles = XB.COMPILE_COUNTER.snapshot()[0]
    _held(query.frame(sess, directory).collect(), want, query)
    plan = sess.last_executed_plan.tpu_child
    scan = _find(plan, "TpuFileSourceScanExec")
    assert not any(m.endswith("Time") and scan.metrics[m].value
                   for m in scan.metrics)
    assert [id(b) for part in rel.batches for b in part] == kept
    assert XB.COMPILE_COUNTER.snapshot()[0] == compiles
    assert rel.hits == 1 and rel.fills == 1
    _close(sess)


@pytest.fixture(scope="module")
def traced(table, tmp_path_factory):
    """Two actions on a 4-device mesh under the CPU profiler: the engine's
    spans [(name, start, end, stats)] by query, in the order the queries
    ran."""
    from jax.profiler import ProfileData

    directory, _, query, _ = table
    out = str(tmp_path_factory.mktemp("cached_trace"))
    sess = TpuSession(dict(MESH4, **{P + "sql.trace.enabled": True}))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        for _ in range(2):
            query.frame(sess, directory).collect()
    finally:
        jax.profiler.stop_trace()
    rel = _relation(sess)
    facts = {"rows": rel.rows, "bytes": rel.bytes}
    _close(sess)
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    by_query = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if ENGINE_SPAN.match(e.name):
                    stats = dict(e.stats)
                    by_query.setdefault(stats.get("query"), []).append(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns,
                         stats))
    assert None not in by_query and len(by_query) == 2
    return [by_query[q] for q in sorted(by_query)], facts


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_the_fill_is_a_span_with_the_scans_own_spans_under_it(traced):
    (first, _), facts = traced
    (fill,) = _named(first, CACHED + ".fill")
    assert fill[3]["rows"] == facts["rows"] == 70000
    assert fill[3]["bytes"] == facts["bytes"] and fill[3]["shards"] == 4
    scan = "TpuFileSourceScanExec."
    decodes, uploads = (_named(first, scan + "host_decode"),
                        _named(first, scan + "upload"))
    assert len(decodes) == len(uploads) == 4
    # 18 row groups x 4 columns, a shard's share on each span
    assert sorted(d[3]["columns"] for d in decodes) == [16, 16, 20, 20]
    assert all("ss_item_sk" in d[3]["names"].split(",") for d in decodes)
    assert sum(u[3]["bytes"] for u in uploads) == facts["bytes"]
    for s in decodes + uploads:
        assert fill[1] <= s[1] and s[2] <= fill[2], s[0]
    (serve,) = _named(first, CACHED + ".serve")
    assert serve[3]["hits"] == 0 and serve[3]["rows"] == 0
    (stage,) = _named(first, "TpuMeshAggregateExec.stage")
    assert stage[3]["h2d_bytes"] == facts["bytes"]
    assert stage[3]["source"] == "cached"
    assert stage[1] <= fill[1] and fill[2] <= stage[2]


def test_a_served_query_has_a_serve_span_and_no_scan_span(traced):
    (_, second), facts = traced
    names = {s[0] for s in second}
    assert not any(n.startswith("TpuFileSourceScanExec") for n in names)
    assert CACHED + ".fill" not in names
    (serve,) = _named(second, CACHED + ".serve")
    assert serve[3]["hits"] == 1 and serve[3]["source"] == "cached"
    assert serve[3]["rows"] == facts["rows"]
    assert serve[3]["bytes"] == facts["bytes"] and serve[3]["shards"] == 4
    (stage,) = _named(second, "TpuMeshAggregateExec.stage")
    assert stage[3]["h2d_bytes"] == 0 and stage[3]["source"] == "cached"
    assert stage[3]["shards"] == 4
    assert stage[3]["shard_rows_sum"] == facts["rows"]
    assert stage[3]["shard_rows_max"] == 5 * 4096
    (spmd,) = _named(second, "TpuMeshAggregateExec.spmd")
    # 4 shards x (4 blocks x 4096 rows x (5 + 9 + 9 + 9) bytes + counts)
    assert spmd[3]["exchange_bytes"] == 4 * (4 * 4096 * 32 + 16)
    assert spmd[3]["update_chunks"] == spmd[3]["update_chunks_live"] == 1
    assert {"TpuMeshAggregateExec.emit", "TpuSession.plan",
            "TpuSession.query", "ColumnarToRowExec.d2h"} <= names


def _mesh_agg_text(query, directory, conf, text_of):
    """``text_of(the mesh aggregate's jitted program, its arguments)`` at
    the dispatch of the cell's query."""
    from spark_rapids_tpu.exec import mesh as XM

    texts = {}
    real = XB.cached_pipeline

    def spy(cache, key, site, build, *args, **kwargs):
        got = real(cache, key, site, build, *args, **kwargs)
        fn = got[0] if isinstance(got, tuple) else got

        def call(*call_args):
            if site not in texts:
                texts[site] = text_of(fn, call_args)
            return fn(*call_args)

        return (call,) + tuple(got[1:]) if isinstance(got, tuple) else call

    XM._PROGRAM_CACHE.clear()
    XB.cached_pipeline = spy
    try:
        sess = TpuSession(conf)
        query.frame(sess, directory).collect()
        _close(sess)
    finally:
        XB.cached_pipeline = real
        XM._PROGRAM_CACHE.clear()
    return texts["mesh_agg"]


def test_the_spmd_program_carries_the_one_chip_scope_words(table):
    """``agg_update`` and ``agg_merge`` as the one-chip programs have
    them, ``mesh_exchange`` around the collective, in the lowering text of
    the program a cached query runs."""
    directory, _, query, _ = table
    text = _mesh_agg_text(
        query, directory, MESH4,
        lambda fn, args: fn.lower(*args).as_text(debug_info=True))
    assert re.search(r"module @jit_mesh_agg\b", text)
    for scope in ("fused_chain", "agg_update", "agg_merge", "project"
                  ) + XB.MESH_SCOPE_WORDS:
        assert re.search(r'loc\("[^"]*\b%s\b' % scope, text), scope
    assert not set(XB.MESH_SCOPE_WORDS) & set(XB.SCOPE_WORDS)


# ---------------------------------------------------------------------------
# (c) the shards add up
# ---------------------------------------------------------------------------
def test_every_row_of_the_file_is_in_exactly_one_shard(table):
    import pyarrow.parquet as pq

    directory, path, query, want = table
    sess = TpuSession(MESH4)
    query.frame(sess, directory).collect()
    rel = _relation(sess)
    planes, counts = rel.planes, [int(c) for c in rel.planes.counts]
    # row groups 0..17 round-robin: 5, 5, 4, 4 of 4096 rows, the last short
    assert counts == [5 * 4096, 4 * 4096 + 368, 4 * 4096, 4 * 4096]
    assert sum(counts) == rel.rows == 70000
    assert rel.per_device == {
        int(d.id): planes.cap * 24 for d in jax.devices()[:4]}
    names = list(query.READS)
    file_rows = pq.read_table(path, columns=names).to_pandas()
    shards = []
    for s, n in enumerate(counts):
        part = {}
        for j, name in enumerate(names):
            data = np.asarray(planes.cols[2 * j])[
                s * planes.cap: s * planes.cap + planes.cap]
            valid = np.asarray(planes.cols[2 * j + 1])[
                s * planes.cap: s * planes.cap + planes.cap]
            assert valid[:n].all() and not valid[n:].any()
            part[name] = data[:n]
        shards.append(part)
    order = ["ss_sold_date_sk", "ss_item_sk", "ss_quantity",
             "ss_wholesale_cost"]

    def ranked(columns):
        idx = np.lexsort([columns[c] for c in reversed(order)])
        return [np.asarray(columns[c])[idx] for c in order]

    both = {c: np.concatenate([p[c] for p in shards]) for c in names}
    for a, b in zip(ranked(both), ranked(
            {c: file_rows[c].to_numpy() for c in names})):
        assert np.array_equal(a, b)
    # the shards' partial answers, merged on the host, are the answer
    merged = {}
    for p in shards:
        keep = p["ss_sold_date_sk"] >= query.DATE_CUT
        for k in np.unique(p["ss_quantity"][keep]):
            rows = keep & (p["ss_quantity"] == k)
            s, q, c = merged.get(int(k), (0.0, 0, 0))
            merged[int(k)] = (s + float(p["ss_wholesale_cost"][rows].sum()),
                              q + int(p["ss_quantity"][rows].sum()),
                              c + int(rows.sum()))
    _held([(k,) + v for k, v in merged.items()], want, query, limit=1e-12)
    _close(sess)


def test_a_consumer_that_is_no_mesh_stage_reads_the_shards_as_batches(
        table):
    """A mesh-resident relation under a plan with no exchange: partition
    ``i`` of the cached scan is shard ``i``."""
    directory, _, query, _ = table
    sess = TpuSession(MESH4)
    frame = sess.read.parquet(directory).cache()
    (total,) = frame.agg(A.agg(A.Sum(col("ss_quantity")), "q"),
                         A.agg(A.Count(col("ss_item_sk")), "c")).collect()
    rel = _relation(sess)
    assert rel.planes is not None and rel.fills == 1
    rows = frame.select("ss_quantity").collect()
    assert len(rows) == rel.rows == total[1]
    assert sum(r[0] for r in rows) == total[0]
    assert rel.fills == 1
    _close(sess)


def test_a_plan_that_is_no_file_scan_caches_on_one_device():
    from spark_rapids_tpu import types as T

    sess = TpuSession(ONE)
    schema = T.StructType((T.StructField("k", T.INT, False),
                           T.StructField("v", T.LONG, False)))
    frame = sess.create_dataframe(
        {"k": [i % 3 for i in range(99)], "v": list(range(99))},
        schema).cache()
    want = sorted((k, sum(v for v in range(99) if v % 3 == k))
                  for k in range(3))
    for hits in (0, 1):
        got = frame.group_by("k").agg(A.agg(A.Sum(col("v")), "s")).collect()
        assert sorted(got) == want
        assert _relation(sess).hits == hits
    assert _relation(sess).files_key == ()
    _close(sess)


# ---------------------------------------------------------------------------
# (d) unpersist gives the bytes back; a rewritten file refills
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_unpersist_returns_the_catalogs_bytes(table, placement):
    directory, _, query, want = table
    cat = BufferCatalog.get()
    before = cat.resident_bytes()
    sess = TpuSession(PLACEMENTS[placement])
    frame = sess.read.parquet(directory).cache()
    query.frame(sess, directory).collect()
    rel = _relation(sess)
    now = cat.resident_bytes()
    for dev, nbytes in rel.per_device.items():
        assert now[dev] - before.get(dev, 0) == nbytes > 0
    assert cat.resident_bytes(0) == now[0]
    assert len(rel.per_device) == (4 if placement == "mesh4" else 1)
    assert frame.unpersist() is frame
    assert cat.resident_bytes() == before
    assert not sess.cache_manager and not frame.is_cached
    assert rel.planes is None and rel.batches is None
    # the plain scan answers from now on
    _held(_uncached(query, sess, directory).collect(), want, query)
    assert cat.resident_bytes() == before
    _close(sess)


def test_a_rewritten_file_misses_and_refills(cell, tmp_path):
    query = cell["queries"][0]
    directory = str(tmp_path)
    path = cell["generator"].generate(
        cell["config"], SEED + 1, directory, 30000, 4096)
    sess = TpuSession(MESH4)
    _held(query.frame(sess, directory).collect(),
          query.reference(path), query)
    rel = _relation(sess)
    old_key, old_bytes = rel.files_key, BufferCatalog.get().resident_bytes()
    assert old_key[0][0] == os.path.realpath(path) and rel.rows == 30000
    path = cell["generator"].generate(
        cell["config"], SEED + 2, directory, 50000, 4096)
    _held(query.frame(sess, directory).collect(),
          query.reference(path), query)
    assert _relation(sess) is rel and rel.fills == 2 and rel.hits == 0
    assert rel.files_key != old_key and rel.rows == 50000
    # the old planes' bytes went back before the new ones were booked
    cat = BufferCatalog.get().resident_bytes()
    assert cat[0] - old_bytes[0] == rel.per_device[0] - old_bytes[0]
    _held(query.frame(sess, directory).collect(),
          query.reference(path), query)
    assert rel.fills == 2 and rel.hits == 1
    _close(sess)
    assert BufferCatalog.get().resident_bytes() == {}


# ---------------------------------------------------------------------------
# (e) a fill that does not fit fails by name
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("placement", sorted(PLACEMENTS))
def test_a_fill_past_the_budget_raises_the_named_oom(table, placement):
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.memory.retry import TpuOutOfDeviceMemory

    directory, _, query, want = table
    conf = dict(PLACEMENTS[placement],
                **{P + "memory.hbm.budgetBytes": 64 * 1024})
    BufferCatalog.reset(RapidsConf(conf))
    try:
        sess = TpuSession(conf)
        with pytest.raises(TpuOutOfDeviceMemory) as err:
            query.frame(sess, directory).collect()
        assert err.value.op == CACHED + ".fill"
        assert err.value.budget == 64 * 1024
        assert "cached relation does not fit device" in str(err.value)
        assert BufferCatalog.get().resident_bytes() == {}
        assert not _relation(sess).filled
        # without the mark the same session answers
        sess.read.parquet(directory).unpersist()
        _held(_uncached(query, sess, directory).collect(), want, query)
        _close(sess)
    finally:
        BufferCatalog.reset()


# ---------------------------------------------------------------------------
# what the SF100 table forced: the update in chunks
# ---------------------------------------------------------------------------
SMALL_CAP = {P + "shuffle.mesh.aggExchangeCapacity": 128}


@pytest.fixture
def small_chunks(monkeypatch):
    """The update chunk at 4096 slots (the engine's is 2^23)."""
    from spark_rapids_tpu.exec import mesh as XM

    monkeypatch.setattr(XM, "AGG_UPDATE_CHUNK_ROWS", 4096)


def test_a_shard_larger_than_the_chunk_is_updated_in_chunks(
        table, small_chunks):
    directory, _, query, want = table
    sess = TpuSession(dict(MESH4, **SMALL_CAP))
    _held(query.frame(sess, directory).collect(), want, query)
    agg = sess.last_executed_plan.tpu_child
    # 5 row groups of 4096 pad to 32768 slots a shard: 8 chunks
    assert agg.mesh_actuals["staging"]["cap"] == 32768
    assert agg.mesh_actuals["update_chunks"] == 8
    # 5, 5, 4 and 4 of them hold a row; the other 14 skip their update
    assert agg.mesh_actuals["update_chunks_live"] == 18
    assert agg.mesh_actuals["exchange_cap"] == 128
    # every chunk's 128 partial rows cross unmerged, a skipped chunk's too
    assert agg.mesh_actuals["exchange_bytes"] == 4 * (
        4 * 8 * 128 * 32 + 16)
    _held(query.frame(sess, directory).collect(), want, query)
    _close(sess)


def test_a_chunk_with_more_groups_than_the_cap_retries(
        table, small_chunks):
    """``group by ss_item_sk``: some 4,000 groups a chunk of 4096 rows
    against a cap of 2048; the stage doubles the cap until every chunk
    fits (at the chunk's size the update is in one piece again) and every
    shard's some 19,500 groups do. Every doubling compiles the SPMD
    program anew, so the cap starts one doubling under the chunk, not the
    five of ``SMALL_CAP``: one chunked program, then four in one piece."""
    import pyarrow.parquet as pq

    directory, path, _, _ = table
    sess = TpuSession(dict(
        MESH4, **{P + "shuffle.mesh.aggExchangeCapacity": 2048}))
    got = (sess.read.parquet(directory).cache().group_by("ss_item_sk")
           .agg(A.agg(A.Count(col("ss_quantity")), "c"),
                A.agg(A.Sum(col("ss_quantity")), "q")).collect())
    pdf = pq.read_table(path).to_pandas()
    g = pdf.groupby("ss_item_sk").ss_quantity.agg(["count", "sum"])
    assert sorted(got) == sorted(
        (int(k), int(r["count"]), int(r["sum"])) for k, r in g.iterrows())
    agg = sess.last_executed_plan.tpu_child
    assert agg.mesh_actuals["programs"] == 5  # 2048 ... 32768, the shard
    _close(sess)


# ---------------------------------------------------------------------------
# the report's float sum as fixed-point limbs (PR 31): the chip's lowering,
# forced here, and the counts that say which lowering a program took
# ---------------------------------------------------------------------------
def test_clearing_reaches_a_module_cache_behind_many_instance_caches():
    """``clear_pipeline_caches()`` before a forced lowering has to empty
    the mesh stage's program cache whatever ran in the process before. The
    registry it sweeps is bounded for the per-instance caches of sort,
    window, join and exchange; a file that built 64 of them used to shut
    out a module's cache that came later, and the tests below were then
    served the other lowering's program (PR 32's tier-1 run)."""
    held = list(XB._ALL_PIPELINE_CACHES)
    cap = XB._PIPELINE_CACHE_REGISTRY_CAP
    of_instances = [{} for _ in range(cap + 1)]
    late = {}

    def build():
        return lambda: None

    try:
        for c in of_instances:
            XB.cached_pipeline(c, "k", None, build, per_instance=True)
        assert len(XB._ALL_PIPELINE_CACHES) == max(cap, len(held))
        XB.cached_pipeline(late, "k", None, build)
        assert late and XB.clear_pipeline_caches() >= 1
        assert late == {}
        # the instance cache the full registry turned away keeps its entry
        assert of_instances[-1] != {}
    finally:
        XB._ALL_PIPELINE_CACHES[:] = held
        XB._ALL_PIPELINE_CACHE_IDS.clear()
        XB._ALL_PIPELINE_CACHE_IDS.update(id(c) for c in held)


def _one_traced_query(query, directory, out):
    """The cell's query once, cached, on the 4-device mesh under the CPU
    profiler: (rows, the mesh aggregate, its spans by name)."""
    from jax.profiler import ProfileData

    sess = TpuSession(dict(MESH4, **SMALL_CAP,
                           **{P + "sql.trace.enabled": True}))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        rows = query.frame(sess, directory).collect()
    finally:
        jax.profiler.stop_trace()
    agg = sess.last_executed_plan.tpu_child
    _close(sess)
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("TpuMeshAggregateExec."):
                        spans.setdefault(e.name, []).append(dict(e.stats))
    return rows, agg, spans


@pytest.mark.parametrize("lowering", ["matmul", "scatter"])
def test_the_mesh_report_says_how_its_float_sum_lowers(
        table, small_chunks, lowering, tmp_path, monkeypatch):
    """Eight chunks a shard and the merge of their partials, under the
    chip's MATMUL lowering (forced) and the CPU backend's own: the same
    report, and on the ``.spmd`` / ``.overflow_pull`` spans the counts."""
    from spark_rapids_tpu.ops import bucket_reduce as BR

    directory, _, query, want = table
    monkeypatch.setattr(BR, "FORCE_MATMUL", lowering == "matmul")
    XB.clear_pipeline_caches()  # a program is cached by plan, not lowering
    try:
        rows, agg, spans = _one_traced_query(query, directory, str(tmp_path))
    finally:
        XB.clear_pipeline_caches()
    # the forced lowering sums the float column to the last places too
    _held(rows, want, query, limit=1e-12)
    assert agg.mesh_actuals["update_chunks"] == 8
    (spmd,) = spans["TpuMeshAggregateExec.spmd"]
    assert spmd["update_chunks"] == 8 and spmd["update_chunks_live"] == 18
    assert spmd["exchange_bytes"] == 4 * (4 * 8 * 128 * 32 + 16)
    (pull,) = spans["TpuMeshAggregateExec.overflow_pull"]
    if lowering == "matmul":
        assert spmd["float_sums_fixed"] == 1 and spmd["row_scatters"] == 0
    else:
        # each half: the ints', the counts' and the float sum's scatter
        assert spmd["float_sums_fixed"] == 0 and spmd["row_scatters"] == 6
    assert pull["float_detour"] == 0
    assert agg.mesh_actuals["float_sums_fixed"] == spmd["float_sums_fixed"]
    assert agg.mesh_actuals["row_scatters"] == spmd["row_scatters"]
    assert agg.mesh_actuals["float_detour"] == 0


def test_a_detour_on_a_shard_is_counted(table, small_chunks, monkeypatch):
    """An infinite price in one row group: its shard's detour runs, its
    group's sum is infinite and every other group's is the oracle's."""
    import shutil

    import pyarrow as pa
    import pyarrow.parquet as pq

    from spark_rapids_tpu.ops import bucket_reduce as BR

    directory, path, query, want = table
    t = pq.read_table(path)
    prices = t.column("ss_wholesale_cost").to_numpy().copy()
    dates = t.column("ss_sold_date_sk").to_numpy()
    row = int(np.flatnonzero(dates >= query.DATE_CUT)[0])
    prices[row] = np.inf
    quantity = int(t.column("ss_quantity")[row].as_py())
    spoiled = os.path.join(os.path.dirname(directory), "spoiled")
    os.makedirs(spoiled, exist_ok=True)
    pq.write_table(
        t.set_column(t.schema.get_field_index("ss_wholesale_cost"),
                     "ss_wholesale_cost", pa.array(prices)),
        os.path.join(spoiled, os.path.basename(path)),
        row_group_size=pq.ParquetFile(path).metadata.row_group(0).num_rows)
    monkeypatch.setattr(BR, "FORCE_MATMUL", True)
    XB.clear_pipeline_caches()
    try:
        sess = TpuSession(dict(MESH4, **SMALL_CAP))
        got = sorted(query.frame(sess, spoiled).collect())
        agg = sess.last_executed_plan.tpu_child
        assert agg.mesh_actuals["float_detour"] >= 1
        assert agg.mesh_actuals["row_scatters"] == 0
        _close(sess)
    finally:
        XB.clear_pipeline_caches()
        shutil.rmtree(spoiled)
    for g, w in zip(got, sorted(want)):
        assert (g[0], g[2], g[3]) == (w[0], w[2], w[3])
        if g[0] == quantity:
            assert g[1] == np.inf
        else:
            assert abs(g[1] - w[1]) <= 1e-12 * abs(w[1])


# ---------------------------------------------------------------------------
# a chunk with no live row skips its update (PR 33)
# ---------------------------------------------------------------------------
#: rows, rows a row group -> (slots a shard, chunks of 4096 that hold a row).
#: Row groups go to the four shards in turn
FILLS = {
    # 4 full row groups a shard
    "every_chunk_live": (16 * 4096, 4096, 16384, 16),
    # the ``table`` fixture's: 5, 5 (the last short), 4 and 4 of 8
    "a_last_live_chunk_partly_filled": (70000, 4096, 32768, 18),
    # 2, 1, 1 and 1 of 2
    "one_live_chunk": (5 * 4096, 4096, 8192, 5),
    # three row groups for four shards: 2, 2, 1 (of 3000 rows) and 0 of 2
    "a_shard_with_no_row": (2 * 8192 + 3000, 8192, 8192, 5),
}


@pytest.fixture(scope="module")
def filled(cell, tmp_path_factory):
    """fill -> (directory, path), made once for both lowerings."""
    made = {}

    def make(fill):
        if fill not in made:
            rows, row_group = FILLS[fill][:2]
            directory = str(tmp_path_factory.mktemp(fill))
            made[fill] = directory, cell["generator"].generate(
                cell["config"], SEED + 33, directory, rows, row_group)
        return made[fill]

    return make


def _chunked_and_whole(query, directory, monkeypatch):
    """The cell's query with the update in chunks of 4096 slots, then with
    the engine's chunk (above the shard: one piece): rows and aggregate
    of each."""
    from spark_rapids_tpu.exec import mesh as XM

    whole = XM.AGG_UPDATE_CHUNK_ROWS
    out = []
    for chunk in (4096, whole):
        monkeypatch.setattr(XM, "AGG_UPDATE_CHUNK_ROWS", chunk)
        sess = TpuSession(dict(MESH4, **SMALL_CAP))
        rows = query.frame(sess, directory).collect()
        assert sess.plan_fallbacks() == []
        out.append((rows, sess.last_executed_plan.tpu_child))
        _close(sess)
    return out


@pytest.mark.parametrize("lowering", ["matmul", "scatter"])
@pytest.mark.parametrize("fill", sorted(FILLS))
def test_skipping_empty_chunks_gives_the_one_piece_answer(
        cell, filled, fill, lowering, monkeypatch):
    from spark_rapids_tpu.ops import bucket_reduce as BR

    query = cell["queries"][0]
    directory, path = filled(fill)
    cap, live = FILLS[fill][2:]
    monkeypatch.setattr(BR, "FORCE_MATMUL", lowering == "matmul")
    XB.clear_pipeline_caches()
    try:
        (chunked, agg), (whole, one) = _chunked_and_whole(
            query, directory, monkeypatch)
    finally:
        XB.clear_pipeline_caches()
    assert agg.mesh_actuals["staging"]["cap"] == cap
    assert agg.mesh_actuals["update_chunks"] == cap // 4096
    assert agg.mesh_actuals["update_chunks_live"] == live
    assert one.mesh_actuals["update_chunks"] == 1
    assert one.mesh_actuals["update_chunks_live"] == 1
    assert (agg.mesh_actuals["float_sums_fixed"]
            == one.mesh_actuals["float_sums_fixed"]
            == (1 if lowering == "matmul" else 0))
    assert agg.mesh_actuals["float_detour"] == 0
    # keys, counts and integer sums exactly, the float sum to 1e-12
    _held(chunked, whole, query, limit=1e-12)
    _held(chunked, query.reference(path), query)


@pytest.mark.parametrize("lowering", ["matmul", "scatter"])
def test_a_chunks_update_sits_in_a_branch_of_the_compiled_program(
        table, small_chunks, lowering, monkeypatch):
    """No scatter and no matmul of a chunk's update outside the loop's
    conditional, in the compiled text of the small program: by the name
    stack (the branch comes before ``agg_update``) and by the computations
    the compiler kept."""
    from spark_rapids_tpu.ops import bucket_reduce as BR
    from tpu_compile_asks import (
        chunk_updates, computations_under_a_conditional, instructions_named)

    directory, _, query, _ = table
    monkeypatch.setattr(BR, "FORCE_MATMUL", lowering == "matmul")
    XB.clear_pipeline_caches()
    try:
        text = _mesh_agg_text(
            query, directory, dict(MESH4, **SMALL_CAP),
            lambda fn, args: fn.lower(*args).compile().as_text())
    finally:
        XB.clear_pipeline_caches()
    update, outside = chunk_updates(text)
    kinds = {"dot_general" if "dot_general" in n else "scatter"
             for _, n in update}
    # MATMUL keeps scatters in its hash tiers' own branches
    assert kinds == ({"dot_general", "scatter"} if lowering == "matmul"
                     else {"scatter"}), kinds
    assert outside == [], outside
    # the merge of the chunks' partials is outside it
    under = computations_under_a_conditional(text)
    merge = instructions_named(text, r"/agg_merge/.*(scatter|dot_general)")
    assert merge and not all(c in under for c, _ in merge)
