"""A file of the source's full record width through the engine's normal
path (``TpuSession.read.parquet(...).where(...).group_by(...).agg(...)``,
default ``sql.agg.strategy``): the scan reads only the columns the plan
names, several scan splits plan ``PARTIAL`` -> ``TpuShuffleExchangeExec``
-> ``FINAL`` with no fallback operator and the plain reference's rows, AUTO
on the ``tpu`` backend resolves no lowering the v5e compiler refuses, and
the spans and counts of the path carry the names ``docs/tuning.md`` lists,
with per-query counts that add up. The data is the benchmark's own
(``tpcds_sf10_store_sales_full`` at its rehearse size)."""
import glob
import os

import pytest

from tpu_compile_asks import REFUSED_ON_V5E, load_cell

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.exec import base as XB
from spark_rapids_tpu.exec.aggregate import (
    TpuHashAggregateExec, choose_agg_strategy)
from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
from spark_rapids_tpu.exec.scan import TpuFileSourceScanExec
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.sql import TpuSession

CELL = "store_sales_full.quantity_report"
#: TPC-H Q1 on the 16-column ``lineitem``: two string keys and an order
Q1_CELL = "lineitem_full.q1"
BATCH_BYTES = "spark.rapids.tpu.sql.reader.batchSizeBytes"
TRACE = "spark.rapids.tpu.sql.trace.enabled"
FUSION = "spark.rapids.tpu.sql.stageFusion"
SCAN, AGG, EXCHANGE = (
    "TpuFileSourceScanExec", "TpuHashAggregateExec", "TpuShuffleExchangeExec")


@pytest.fixture(autouse=True)
def one_chip_host(monkeypatch):
    """The suite shows eight virtual devices, and ``shuffle.mode=auto``
    then lowers a shuffle-bounded stage to the mesh; the cell's host shows
    one chip, so the one-host exchange is what its plan holds."""
    from spark_rapids_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "device_count", lambda: 1)


@pytest.fixture(scope="module")
def full_file(tmp_path_factory):
    """(bench, directory, path, a row group's bytes over all 23 columns)
    of the configuration's file at its rehearse size: four row groups."""
    import pyarrow.parquet as pq

    bench = load_cell(CELL)
    size = bench["config"]["rehearse"]
    directory = str(tmp_path_factory.mktemp("full"))
    path = bench["generator"].generate(
        bench["config"], 2**31 + 35, directory, size["rows"],
        size["row_group_rows"])
    md = pq.ParquetFile(path).metadata
    assert md.num_columns == 23 and md.num_row_groups == 4
    return bench, directory, path, md.row_group(0).total_byte_size


def _nodes(plan):
    yield plan
    for c in getattr(plan, "children", ()):
        yield from _nodes(c)
    if hasattr(plan, "tpu_child"):
        yield from _nodes(plan.tpu_child)


def _collect(bench, directory, conf):
    sess = TpuSession(dict(bench["config"]["conf"], **conf))
    rows = bench["queries"][0].frame(sess, directory).collect()
    return sess, sorted(rows)


# ---------------------------------------------------------------------------
# (a) the plan and its rows
# ---------------------------------------------------------------------------
def test_several_splits_plan_partial_exchange_final_and_the_plain_rows(
        full_file):
    bench, directory, path, rg_bytes = full_file
    q = bench["queries"][0]
    assert "spark.rapids.tpu.sql.agg.strategy" not in bench["config"]["conf"]
    sess, rows = _collect(bench, directory, {BATCH_BYTES: rg_bytes + 1})
    nodes = list(_nodes(sess.last_executed_plan))
    scan = next(n for n in nodes if isinstance(n, TpuFileSourceScanExec))
    assert scan.num_partitions == 4 >= 3
    modes = [n.mode for n in nodes if isinstance(n, TpuHashAggregateExec)]
    assert modes == [A.FINAL, A.PARTIAL]
    exchange = [n for n in nodes if isinstance(n, TpuShuffleExchangeExec)]
    assert len(exchange) == 1
    assert exchange[0].partitioning.describe() == (
        "HashPartitioning(keys=[0], n=4)")
    assert sess.plan_fallbacks() == []
    # the scan's schema is the four columns the query names, in file order
    assert tuple(f.name for f in scan.output_schema.fields) == q.READS
    want = q.reference(path)
    assert [(r[0], r[2], r[3]) for r in rows] == [
        (w[0], w[2], w[3]) for w in want]
    assert max(abs(r[1] - w[1]) / abs(w[1])
               for r, w in zip(rows, want)) <= q.FLOAT_LIMIT
    # one split (the default 2 GiB holds this file whole): COMPLETE, no
    # exchange, and the same answer
    one, one_rows = _collect(bench, directory, {})
    kinds = [type(n) for n in _nodes(one.last_executed_plan)]
    assert TpuShuffleExchangeExec not in kinds
    assert [(r[0], r[2], r[3]) for r in one_rows] == [
        (r[0], r[2], r[3]) for r in rows]
    assert max(abs(a[1] - b[1]) / abs(b[1])
               for a, b in zip(one_rows, rows)) <= q.FLOAT_LIMIT


@pytest.mark.parametrize("above,reads", [
    ("aggregate", ("k", "v")),
    ("project", ("k", "w")),
    ("filter", ("k", "v", "w", "x")),
    ("filter_project", ("v", "w")),
    ("sort_limit_project", ("k", "x")),
    ("count_star", ("k",)),
    ("user_columns", ("k", "w", "x")),
])
def test_a_scan_reads_the_columns_the_plan_above_it_names(
        above, reads, tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    col = E.col
    pq.write_table(pa.table({
        "k": pa.array([1, 2, 1, 2], pa.int32()),
        "v": pa.array([1.0, 2.0, 3.0, 4.0]),
        "w": pa.array([5, 6, 7, 8], pa.int64()),
        "x": pa.array([9, 9, 8, 8], pa.int32())}),
        str(tmp_path / "t.parquet"))
    sess = TpuSession({})

    def frame(df):
        if above == "user_columns":
            return df.select("k")
        return {
            "aggregate": lambda: df.group_by("k").agg(
                A.agg(A.Sum(col("v")), "s")),
            "project": lambda: df.select("k", E.Alias(
                E.Add(col("w"), E.lit(1)), "w1")),
            "filter": lambda: df.where(E.GreaterThan(col("x"), E.lit(8))),
            "filter_project": lambda: df.where(
                E.GreaterThan(col("w"), E.lit(5))).select("v"),
            "sort_limit_project": lambda: df.select("k", "x").order_by(
                "x").limit(3),
            "count_star": lambda: df.group_by().agg(
                A.agg(A.Count(), "n")),
        }[above]()

    path = str(tmp_path)
    pruned = frame(sess.read.parquet(
        path, columns=["k", "w", "x"] if above == "user_columns"
        else None)).collect()
    scan = next(n for n in _nodes(sess.last_executed_plan)
                if isinstance(n, TpuFileSourceScanExec))
    assert tuple(f.name for f in scan.output_schema.fields) == reads
    # the same rows as with every column read (the user's own list stands)
    every = frame(sess.read.parquet(
        path, columns=["k", "v", "w", "x"])).collect()
    assert sorted(pruned) == sorted(every)


# ---------------------------------------------------------------------------
# (b) AUTO on the tpu backend resolves nothing the compiler refuses
# ---------------------------------------------------------------------------
#: (ops, value expressions) of the benchmark's two queries
SHAPES = {
    "quantity_report": (
        ("sum", "count", "sum", "count", "count"),
        (E.BoundReference(2, T.DOUBLE, True),
         E.BoundReference(2, T.DOUBLE, True),
         E.BoundReference(1, T.INT, True), E.BoundReference(1, T.INT, True),
         E.BoundReference(0, T.INT, True))),
    "q1": (
        ("sum", "count") * 6 + ("count_star",),
        tuple(E.BoundReference(i // 2, T.DOUBLE, True)
              for i in range(12)) + (None,)),
}
#: capacities the cells' aggregates resolve a strategy at: a row group's
#: update, the fused stages' merges of 5, 9 and 14 row groups' partials,
#: the final merge of this cell's exchanged pieces and Q1's merges
CAPACITIES = (1 << 7, 1 << 8, 1 << 11, 1 << 12, 1 << 21, 1 << 24, 1 << 25)


@pytest.mark.parametrize("float_agg", [True, False])
@pytest.mark.parametrize("shape", sorted(SHAPES))
@pytest.mark.parametrize("cap", CAPACITIES)
def test_auto_on_tpu_resolves_no_refused_lowering(cap, shape, float_agg):
    conf = RapidsConf(
        {"spark.rapids.tpu.sql.variableFloatAgg.enabled": float_agg})
    ops, exprs = SHAPES[shape]
    pick, why = choose_agg_strategy(conf, cap, ops, exprs, backend="tpu")
    assert pick not in REFUSED_ON_V5E, (pick, why)
    assert pick == "MATMUL"


# ---------------------------------------------------------------------------
# (d) names and counts, from a real trace of the CPU profiler
# ---------------------------------------------------------------------------
def _traced_spans(tmp_path_factory, bench, directory, conf):
    """[(name, stats)] of the engine's spans of one cold query."""
    import jax
    from jax.profiler import ProfileData

    from spark_rapids_tpu.io.scan_cache import DeviceScanCache

    from unittest import mock

    from spark_rapids_tpu.parallel import mesh

    out = str(tmp_path_factory.mktemp("trace"))
    DeviceScanCache.reset()  # cold: the file is read
    one_chip = mock.patch.object(mesh, "device_count", lambda: 1)
    one_chip.start()
    sess = TpuSession(dict(bench["config"]["conf"], **conf, **{TRACE: True}))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=options)
    try:
        rows = bench["queries"][0].frame(sess, directory).collect()
    finally:
        jax.profiler.stop_trace()
        one_chip.stop()
    assert len(rows) == (4 if bench["name"] == Q1_CELL else 100)
    path = glob.glob(os.path.join(out, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.partition(".")[0] in (SCAN, AGG, EXCHANGE):
                    spans.append((e.name, dict(e.stats)))
    return spans


@pytest.fixture(scope="module", params=["OFF", "ON"])
def traced(request, tmp_path_factory, full_file):
    """The four-split query under the profiler, each split decoded on its
    own (what the CPU backend does) and through the fused stage (the
    chip's path)."""
    bench, directory, path, rg_bytes = full_file
    return request.param, path, _traced_spans(
        tmp_path_factory, bench, directory,
        {BATCH_BYTES: rg_bytes + 1, FUSION: request.param})


def _named(spans, name):
    return [stats for n, stats in spans if n == name]


def test_scan_spans_count_splits_row_groups_and_file_bytes(traced):
    import pyarrow.parquet as pq

    fusion, path, spans = traced
    per_split = _named(spans, SCAN + (".plan" if fusion == "ON"
                                      else ".decode"))
    assert [s["splits"] for s in per_split] == [1, 1, 1, 1]
    assert [s["row_groups"] for s in per_split] == [1, 1, 1, 1]
    md = pq.ParquetFile(path).metadata
    rg = md.row_group(0)
    reads = load_cell(CELL)["queries"][0].READS
    chunk_bytes = sum(
        md.row_group(g).column(i).total_compressed_size
        for g in range(md.num_row_groups) for i in range(rg.num_columns)
        if rg.column(i).path_in_schema in reads)
    plans = _named(spans, SCAN + ".page_plan")
    counted = [s["file_bytes"] for s in plans if "file_bytes" in s]
    assert len(counted) == 4 * len(reads)  # a chunk a column a row group
    assert sum(counted) == chunk_bytes
    opened = _named(spans, SCAN + ".read_file")
    assert len(opened) == 4
    assert all(s["file_bytes"] == md.serialized_size for s in opened)
    # nothing of the other 19 columns: under a tenth of the file
    touched = sum(counted) + sum(s["file_bytes"] for s in opened)
    assert touched < os.path.getsize(path) / 5


def test_exchange_spans_carry_bytes_rows_and_partitions(traced):
    _, _, spans = traced
    (mapped,) = _named(spans, EXCHANGE + ".map")
    assert mapped["partitions"] == 4 and mapped["inputs"] == 4
    assert mapped["rows"] == 4 * 100 and mapped["bytes"] > 0
    # each PARTIAL hands its 100 groups over at its row group's capacity:
    # the map program partitions their bucket, so every input is cut
    assert mapped["slots"] == 4 * 128 and mapped["cut"] == 4
    reduced = _named(spans, EXCHANGE + ".reduce")
    assert sum(s["rows"] for s in reduced) == mapped["rows"]
    assert sum(s["bytes"] for s in reduced) == mapped["bytes"]
    # every map input sent a piece to every partition it had a key for
    assert 4 <= sum(s["partitions"] for s in reduced) <= 16
    assert not _named(spans, EXCHANGE)  # no span without a section


def test_aggregate_spans_tell_partial_from_final_with_the_strategy(traced):
    fusion, _, spans = traced
    agg = [(n, s) for n, s in spans
           if n.startswith(AGG + ".") and n.count(".") == 1]
    assert agg and all(s["mode"] in (A.PARTIAL, A.FINAL) for _, s in agg)
    partial = [n for n, s in agg if s["mode"] == A.PARTIAL]
    final = [(n, s) for n, s in agg if s["mode"] == A.FINAL]
    # splits x partials = exchange inputs: a PARTIAL a split
    (mapped,) = _named(spans, EXCHANGE + ".map")
    want = AGG + (".stage" if fusion == "ON" else ".plan")
    assert partial.count(want) == 4 == mapped["inputs"]
    assert [n for n, _ in final] == [AGG + ".plan"]
    assert all(s["strategy"] == "SCATTER" for _, s in agg)  # CPU's AUTO


def test_exchange_programs_are_jit_exchange_under_their_scope(full_file):
    import re

    bench, directory, _, rg_bytes = full_file
    texts = {}
    real = XB.cached_pipeline

    def spy(cache, key, site, build, *args, **kwargs):
        fn = real(cache, key, site, build, *args, **kwargs)

        def call(*call_args):
            if fn.__name__ not in texts:
                texts[fn.__name__] = fn.lower(*call_args).as_text(
                    debug_info=True)
            return fn(*call_args)

        return call

    XB.clear_pipeline_caches()
    XB.cached_pipeline = spy
    try:
        _collect(bench, directory, {BATCH_BYTES: rg_bytes + 1})
    finally:
        XB.cached_pipeline = real
        XB.clear_pipeline_caches()
    (scope,) = XB.EXCHANGE_SCOPE_WORDS
    assert scope not in XB.SCOPE_WORDS + XB.MESH_SCOPE_WORDS
    for word in ("exchange", "exchange_slice", "exchange_concat"):
        assert word in XB.PROGRAM_WORDS
        text = texts[word]
        assert re.search(r"module @jit_%s\b" % word, text), text[:200]
        assert re.search(r'loc\("[^"]*\b%s\b' % scope, text), word


def test_the_exchange_map_program_is_not_rebuilt_by_a_second_query(
        full_file):
    bench, directory, _, rg_bytes = full_file
    _collect(bench, directory, {BATCH_BYTES: rg_bytes + 1})
    before = XB.COMPILE_COUNTER.snapshot()[1].get("exchange", 0)
    _collect(bench, directory, {BATCH_BYTES: rg_bytes + 1})
    assert XB.COMPILE_COUNTER.snapshot()[1].get("exchange", 0) == before


# ---------------------------------------------------------------------------
# (e) Q1 on the 16-column lineitem: string keys through a hash and a range
# exchange, from a real trace of the CPU profiler
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def q1_traced(tmp_path_factory):
    """The spans of ``lineitem_full.q1``'s query at its rehearse size,
    its four row groups cut into two scan splits as the timed file's 29
    are."""
    import pyarrow.parquet as pq

    bench = load_cell(Q1_CELL)
    size = bench["config"]["rehearse"]
    directory = str(tmp_path_factory.mktemp("li_full"))
    path = bench["generator"].generate(
        bench["config"], 2**31 + 37, directory, size["rows"],
        size["row_group_rows"])
    md = pq.ParquetFile(path).metadata
    assert md.num_columns == 16 and md.num_row_groups == 4
    return _traced_spans(
        tmp_path_factory, bench, directory,
        {BATCH_BYTES: 2 * md.row_group(0).total_byte_size + 1000})


def test_q1s_exchanges_say_their_kind_and_the_range_one_its_sampling(
        q1_traced):
    spans = q1_traced
    mapped = _named(spans, EXCHANGE + ".map")
    assert [m["kind"] for m in mapped] == ["hash", "range"]
    hashed, ranged = mapped
    # a PARTIAL a split into the hash exchange; the FINAL's one batch of
    # four groups into the range exchange
    assert (hashed["inputs"], hashed["partitions"]) == (2, 2)
    assert (ranged["inputs"], ranged["rows"], ranged["partitions"]) == (
        1, 4, 2)
    (sample,) = _named(spans, EXCHANGE + ".sample")
    assert (sample["samples"], sample["inputs"], sample["bounds"]) == (
        4, 1, 1)
    # the sampling's pull is a part of its span
    names = [n for n, _ in spans]
    assert names.index(EXCHANGE + ".sample") < names.index(
        EXCHANGE + ".d2h") < len(names)
    # both of the range exchange's partitions hold rows: two reduce sides
    # after it, one adaptive read's before
    reduced = _named(spans, EXCHANGE + ".reduce")
    assert [r["rows"] for r in reduced] == [8, 2, 2]


def test_q1s_merges_count_their_partials(q1_traced):
    merges = _named(q1_traced, AGG + ".merge")
    # two row groups' updates a split, then the one exchanged batch
    assert [(m["mode"], m["partials"]) for m in merges] == [
        (A.PARTIAL, 2), (A.PARTIAL, 2), (A.FINAL, 1)]
