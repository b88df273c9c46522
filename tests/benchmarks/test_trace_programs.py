"""``benchmarks/trace_programs.py`` on planes built by hand, its reader of
the file's metadata tables on a file written here, and each per-layer
metric that reads it against a reduction made by hand."""
import os

import pytest

import benchmark_testlib as lib  # noqa: F401  (puts benchmarks/ on the path)
import loader
import trace_programs as TP

MS = 1e6  # ns


def _op(name, start_ms, dur_ms, tf_op=None):
    stats = {} if tf_op is None else {"tf_op": tf_op}
    return (f"%{name} = f32[8]{{0}} fusion(...)", start_ms * MS, dur_ms * MS,
            stats)


def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _planes():
    """A slice of 100 ms with two queries. The device runs ``jit_agg_stage``
    (decode 20 ms in a loop of 24 ms, update 6 ms, 2 ms outside every
    scope), ``jit_sort`` (5 ms), an eager ``jit_dynamic_slice`` (1 ms) and
    one operation outside every module (1 ms). The host: query 7 with a
    scan whose parts nest, a pool thread, and query 8 served by the
    cache."""
    stage = "jit(agg_stage)/"
    device = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [
            ("jit_agg_stage(111)", 0 * MS, 40 * MS, {}),
            ("jit_sort(222)", 50 * MS, 5 * MS, {}),
            ("jit_dynamic_slice(333)", 60 * MS, 1 * MS, {})]},
        {"name": "XLA Ops", "events": [
            _op("while.1", 0, 24, stage + "pq_decode/while:"),
            _op("fusion.1", 1, 12,
                stage + "pq_decode/while/body/jit(_take)/gather:"),
            _op("fusion.2", 14, 8, stage + "pq_decode/while/body/select_n:"),
            _op("fusion.3", 24, 6, stage + "agg_update/dot_general:"),
            _op("copy.1", 30, 2, stage + "copy:"),
            _op("sort.1", 50, 5, "jit(sort)/sort:"),
            _op("slice.1", 60, 1, "jit(dynamic_slice)/dynamic_slice:"),
            _op("stray.1", 70, 1)]},
    ]}
    scan = "TpuFileSourceScanExec"
    host = {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [
            ("bench.slice", 0, 100 * MS, {}),
            ("bench.query", 0, 50 * MS, {}),
            ("bench.query", 50 * MS, 50 * MS, {}),
            _span("TpuSession.query", 0, 48, query=7),
            _span("TpuSession.plan", 0, 2, query=7),
            _span(scan + ".decode", 2, 30, query=7),
            _span(scan + ".cache_lookup", 2, 1, query=7, hits=0, lookups=4,
                  cache="miss"),
            _span(scan + ".read_file", 3, 2, query=7),
            _span(scan + ".plan_wait", 5, 10, query=7),
            _span(scan + ".host_decode", 15, 9, query=7, columns=2),
            _span(scan + ".upload", 16, 4, query=7, bytes=1000),
            _span(scan + ".upload", 25, 5, query=7, bytes=3000),
            _span("TpuHashAggregateExec.merge", 33, 10, query=7),
            _span("TpuHashAggregateExec.merge.pull", 33, 6, query=7),
            _span("TpuHashAggregateExec.merge.concat", 39, 3, query=7),
            _span("ColumnarToRowExec.to_rows", 44, 4, query=7),
            _span("ColumnarToRowExec.d2h", 44, 3, query=7, bytes=64),
            _span("TpuSession.query", 50, 49, query=8),
            _span("TpuSession.plan", 50, 4, query=8),
            _span(scan + ".cache_lookup", 55, 1, query=8, hits=4, lookups=4,
                  cache="hit"),
            ("PjitFunction(agg_stage)", 56 * MS, 1 * MS, {}),
            _span("ColumnarToRowExec.d2h", 90, 5, query=8, bytes=64)]},
        {"name": "srtpu-pqdec_0", "events": [
            _span(scan + ".page_plan", 5, 4, query=7),
            _span(scan + ".page_plan", 9, 5, query=7)]},
    ]}
    return [device, host]


def test_device_seconds_by_program_scope_and_label():
    r = TP.reduce_programs(_planes())
    assert r["window_s"] == pytest.approx(0.1)
    assert r["by_program"] == pytest.approx(
        {"agg_stage": 0.032, "sort": 0.005, "dynamic_slice": 0.001,
         TP.UNNAMED: 0.001})
    # the loop's own 4 ms count under the loop, its body's 20 ms once
    assert r["by_scope"] == pytest.approx(
        {"pq_decode": 0.024, "agg_update": 0.006})
    assert r["by_label"] == pytest.approx(
        {"pq_decode": 0.024, "agg_update": 0.006, "agg_stage": 0.002,
         "sort": 0.005, TP.UNNAMED: 0.002})
    assert r["device_s"] == pytest.approx(0.039)
    assert r["named_s"] == pytest.approx(0.037)


def test_an_operation_outside_every_module_or_in_an_eager_one_is_unnamed():
    r = TP.reduce_programs(_planes())
    assert r["unnamed_s"] == pytest.approx(0.002)
    names = dict(r["unnamed_ops"])
    assert set(names) == {"dynamic_slice: slice f32[8]", "?: stray f32[8]"}
    # and one that only its own name stack places still finds its program
    planes = _planes()
    planes[0]["lines"][0]["events"] = []
    r = TP.reduce_programs(planes)
    assert r["by_program"]["agg_stage"] == pytest.approx(0.032)
    assert r["by_label"]["sort"] == pytest.approx(0.005)


def test_span_self_time_nests_per_thread_and_counts_add_up():
    spans = TP.reduce_programs(_planes())["spans"]
    scan = "TpuFileSourceScanExec"
    decode = spans[scan + ".decode"]
    assert decode["count"] == 1 and decode["total_s"] == pytest.approx(0.030)
    # 30 ms less lookup 1, read_file 2, plan_wait 10, host_decode 9,
    # the second upload 5; the pool thread's page plans are not its parts
    assert decode["self_s"] == pytest.approx(0.003)
    # host_decode's own time is less the upload nested in it
    assert spans[scan + ".host_decode"]["self_s"] == pytest.approx(0.005)
    assert spans[scan + ".host_decode"]["counts"] == {"columns": 2}
    up = spans[scan + ".upload"]
    assert up["count"] == 2 and up["counts"] == {"bytes": 4000}
    assert up["self_s"] == pytest.approx(0.009)
    assert spans[scan + ".page_plan"]["self_s"] == pytest.approx(0.009)
    look = spans[scan + ".cache_lookup"]
    assert look["counts"] == {"hits": 4, "lookups": 8}
    assert look["values"] == {"cache": {"miss": 1, "hit": 1}}
    merge = spans["TpuHashAggregateExec.merge"]
    assert merge["self_s"] == pytest.approx(0.001)
    # a span of the runtime is no engine span; query is no count
    assert "PjitFunction(agg_stage)" not in spans
    assert "query" not in spans["TpuSession.plan"]["counts"]


def test_the_split_by_query_and_the_cut_at_the_slice():
    r = TP.reduce_programs(_planes())
    assert r["query_marks"] == 2
    assert [q for q, _, _ in r["query_spans"]] == [7, 8]
    by_q = r["spans_by_query"]
    assert set(by_q) == {7, 8}
    assert by_q[7]["TpuSession.plan"]["total_s"] == pytest.approx(0.002)
    assert by_q[8]["TpuSession.plan"]["total_s"] == pytest.approx(0.004)
    assert "TpuFileSourceScanExec.upload" not in by_q[8]
    assert by_q[7]["TpuFileSourceScanExec.page_plan"]["count"] == 2
    assert r["device_by_query"][7] == pytest.approx(
        {"pq_decode": 0.024, "agg_update": 0.006, "agg_stage": 0.002})
    assert r["device_by_query"][8] == pytest.approx(
        {"sort": 0.005, TP.UNNAMED: 0.002})
    # a slice that ends at 45 ms cuts the events that cross it
    planes = _planes()
    planes[1]["lines"][0]["events"][0] = ("bench.slice", 0, 45 * MS, {})
    r = TP.reduce_programs(planes)
    assert r["window_s"] == pytest.approx(0.045)
    assert "sort" not in r["by_program"]
    assert r["spans"]["ColumnarToRowExec.d2h"]["total_s"] == pytest.approx(
        0.001)
    assert set(r["spans_by_query"]) == {7}


def test_names():
    assert TP.module_word("jit_agg_update(12345)") == "agg_update"
    assert TP.module_word("jit__multi_slice(1)") == "_multi_slice"
    assert TP.module_word("SyncTensorsGraph.1") is None
    assert TP.scope_word(
        "jit(agg_stage)/pq_decode/while/body/jit(_take)/gather:") == (
            "pq_decode")
    assert TP.scope_word("jit(agg_stage)/agg_merge") == "agg_merge"
    assert TP.scope_word("jit(sort)/sort:") is None
    assert TP.scope_word(None) is None
    for name in ("TpuFileSourceScanExec.upload", "TpuSortExec",
                 "TpuHashAggregateExec.merge.pull", "ColumnarToRowExec.d2h",
                 "TpuSession.query"):
        assert TP.ENGINE_SPAN.match(name), name
    for name in ("bench.slice", "PjitFunction(sort)",
                 "TpuLoadedExecutable::ExecuteLaunch", "TpuSession"):
        assert not TP.ENGINE_SPAN.match(name), name


def test_the_vocabulary_is_the_engines():
    from spark_rapids_tpu.exec import base

    assert TP.PROGRAM_WORDS == base.PROGRAM_WORDS + base.OTHER_PROGRAM_WORDS
    assert TP.SCOPE_WORDS == base.SCOPE_WORDS


# ---------------------------------------------------------------------------
# the file's metadata tables
# ---------------------------------------------------------------------------
def _varint(n):
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _stat(metadata_id, value):
    kind = 5 if isinstance(value, str) else 3
    return _field(1, metadata_id) + _field(kind, value)


def _xspace():
    stat_names = {1: "tf_op", 2: "program_id", 3: "flops"}
    big = "%fusion.9 = f32[1048576]{0} fusion(" + "x" * 300 + ")"
    metas = [
        (1, big, [_stat(1, "jit(agg_stage)/pq_decode/gather:"),
                  _stat(2, 111), _stat(3, 5)]),
        (2, "%fusion.1 = s32[128]{0} fusion()",
         [_stat(2, 111), _stat(1, "jit(agg_stage)/agg_update/reduce:")]),
        (3, "%fusion.1 = s32[128]{0} fusion()",
         [_stat(2, 222), _field(1, 1) + _field(7, 3)]),  # a ref value
        (4, "%copy.1 = f32[8]{0} copy()", [_stat(3, 7)]),
    ]
    device = _field(2, "/device:TPU:0")
    device += _field(3, b"\x12\x07XLA Ops" + _field(4, b"\x08\x01\x18\x05"))
    for key, name in stat_names.items():
        device += _field(5, _field(1, key)
                         + _field(2, _field(1, key) + _field(2, name)))
    for key, name, stats in metas:
        body = _field(1, key) + _field(2, name)
        for st in stats:
            body += _field(5, st)
        device += _field(4, _field(1, key) + _field(2, body))
    host = _field(2, "/host:CPU") + _field(
        4, _field(1, 1) + _field(2, _field(1, 1) + _field(2, "a span")))
    return _field(1, device) + _field(1, host) + _field(4, "a-host-name")


def test_op_metadata_reads_the_tables_and_steps_over_the_lines(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace())
    meta = TP.op_metadata(str(path))
    assert set(meta) == {"/device:TPU:0"}  # a host plane names no operation
    by_name = meta["/device:TPU:0"]
    big = next(n for n in by_name if n.startswith("%fusion.9"))
    assert by_name[big] == [{"tf_op": "jit(agg_stage)/pq_decode/gather:",
                             "program_id": 111}]
    # two programs hold an operation of one name: both are kept, and the
    # module that encloses the event decides (a ref value reads the name
    # of the stat it points at)
    same = by_name["%fusion.1 = s32[128]{0} fusion()"]
    assert same == [{"program_id": 111,
                     "tf_op": "jit(agg_stage)/agg_update/reduce:"},
                    {"program_id": 222, "tf_op": "flops"}]
    assert TP._op_names({"_metadata": same}, 222) == "flops"
    assert TP._op_names({"_metadata": same}, 111).endswith("reduce:")
    assert TP._op_names({"_metadata": same}, None).endswith("reduce:")
    assert TP._op_names({"tf_op": "by hand"}, 1) == "by hand"
    assert "%copy.1 = f32[8]{0} copy()" not in by_name  # nothing wanted


def test_for_ctx_takes_the_newest_trace_once(tmp_path, monkeypatch):
    old = tmp_path / ".cache" / "trace" / "a" / "plugins" / "x.xplane.pb"
    new = tmp_path / ".cache" / "trace" / "b" / "plugins" / "y.xplane.pb"
    for p, t in ((old, 100), (new, 200)):
        p.parent.mkdir(parents=True)
        p.write_bytes(b"")
        os.utime(p, (t, t))
    assert TP.newest_trace(str(tmp_path)) == str(new)
    assert TP.newest_trace(str(tmp_path / ".cache" / "trace" / "a")) == (
        str(old))
    assert TP.newest_trace(str(tmp_path / "nothing")) is None
    assert TP.TRACE_ROOT == os.path.join(lib.BENCH, ".cache", "trace")
    calls = []
    monkeypatch.setattr(TP, "newest_trace", lambda: str(new))
    monkeypatch.setattr(TP, "read_xplane",
                        lambda path: calls.append(path) or _planes())
    ctx = {"trace": {"busy_s": 1.0, "queries": 2}}
    assert TP.for_ctx(ctx)["query_marks"] == 2
    assert TP.for_ctx(ctx) is ctx["trace_programs"] and calls == [str(new)]
    assert TP.for_ctx({}) is None  # an untraced run reads no stale file


# ---------------------------------------------------------------------------
# the metric files
# ---------------------------------------------------------------------------
def _ctx(reduced):
    return {"trace": {"busy_s": 0.040, "window_s": 0.1, "queries": 2},
            "trace_programs": reduced, "counters": {}}


#: what each new reader makes of the planes above: 2 queries, 40 ms busy
WANT = {
    "plan_ms_per_query": 3.0,                # (2 + 4) / 2
    "scan_host_ms_per_query": 8.0,           # 2 + 9 + 5 self, over 2
    "host_fallback_columns": 1.0,            # 2 / 2
    "h2d_bytes_per_query": 2000.0,           # 4000 / 2
    "scan_cache_hit_share": 50.0,            # 4 of 8
    "decode_device_share": 60.0,             # 24 of 40
    "agg_device_share": 15.0,                # 6 of 40
    "merge_host_ms_per_query": 1.5,          # concat 3, not the pull
    "collect_d2h_ms_per_query": 4.0,         # (3 + 5) / 2
    "device_unnamed_share": 5.0,             # 2 of 40
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_the_reduction(name):
    reader = loader.load_metrics()[name]
    reduced = TP.reduce_programs(_planes())
    assert reader.read(_ctx(reduced)) == pytest.approx(WANT[name])
    # no trace, no reduction: nothing, and nothing raised
    assert reader.read({"trace": None, "counters": {}}) is None
    assert reader.read(_ctx(None)) is None


def _parent_planes():
    """What the parent of PR 26 leaves in a trace: every program a
    ``jit_run``, no name stack worth the name, PR 25's spans only."""
    planes = _planes()
    device, host = planes
    device["lines"][0]["events"] = [
        ("jit_run(1)", 0, 40 * MS, {}),
        ("jit_materialize_dict(2)", 50 * MS, 5 * MS, {})]
    device["lines"][1]["events"] = [
        (n, s, d, {"tf_op": "jit(run)/jit(main)/mul:"} if st else {})
        for n, s, d, st in device["lines"][1]["events"]]
    keep = ("bench.slice", "bench.query", "TpuFileSourceScanExec.decode",
            "TpuHashAggregateExec.merge")
    host["lines"][0]["events"] = [
        (n, s, d, {}) for n, s, d, _ in host["lines"][0]["events"]
        if n in keep]
    host["lines"][1]["events"] = []
    return planes


@pytest.mark.parametrize("name", sorted(WANT))
def test_metric_reads_nothing_from_a_program_without_the_names(name):
    reduced = TP.reduce_programs(_parent_planes())
    # its one program with a name of its own does not make it named
    assert reduced["by_label"] == pytest.approx(
        {"materialize_dict": 0.005, TP.UNNAMED: 0.034})
    assert loader.load_metrics()[name].read(_ctx(reduced)) is None


def test_a_warm_cell_reads_zero_where_the_cold_one_reads_work():
    """Query 8's half of the planes alone: the cache served the scan, the
    stage merged in the program. The scan's and the merge's host metrics
    are 0 there, not missing: the cell's line keeps them."""
    planes = _planes()
    host = planes[1]
    host["lines"][0]["events"] = [
        ev for ev in host["lines"][0]["events"]
        if ev[0].startswith("bench.") or ev[3].get("query") == 8]
    host["lines"][0]["events"].append(
        _span("TpuHashAggregateExec.stage", 56, 2, query=8))
    host["lines"][1]["events"] = []
    ctx = _ctx(TP.reduce_programs(planes))
    readers = loader.load_metrics()
    for name in ("scan_host_ms_per_query", "host_fallback_columns",
                 "h2d_bytes_per_query", "merge_host_ms_per_query"):
        assert readers[name].read(ctx) == 0.0, name
    assert readers["scan_cache_hit_share"].read(ctx) == 100.0
