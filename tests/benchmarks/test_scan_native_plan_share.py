"""``scan_native_plan_share``: the chunks whose pages one native call
walked, over the chunk plans of the slice, from the ``chunks`` and ``native`` counts on
the scan's ``page_plan`` spans; read from planes built by hand and from
the trace of ``lineitem.q1``'s rehearsal."""
import json
import os

import pytest

import benchmark_testlib as lib
import loader
import run as bench_run
import trace_programs as TP

MS = 1e6  # ns
NAME = "scan_native_plan_share"
PLAN = "TpuFileSourceScanExec.page_plan"


def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _planes(chunk_counts):
    """One query: the pool plans a chunk for each of ``chunk_counts``; the
    consumer's own ``page_plan`` (its ``plan_decode``) carries no count."""
    main = [("bench.slice", 0, 100 * MS, {}),
            _span("TpuSession.query", 0, 90, query=7),
            _span(PLAN, 80, 2, query=7, cpu_us=1_000)]
    pool = [_span(PLAN, 5 + 10 * i, 8, query=7, **c)
            for i, c in enumerate(chunk_counts)]
    return [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": main},
        {"name": "srtpu-pqdec_0", "events": pool}]}]


def _read(planes):
    ctx = {"trace": {"busy_s": 0.01, "window_s": 0.1, "queries": 1},
           "trace_programs": TP.reduce_programs(planes), "counters": {}}
    return loader.load_metrics()[NAME].read(ctx)


NATIVE = {"chunks": 1, "native": 1, "pages": 106}
LOOP = {"chunks": 1, "native": 0, "pages": 106}


@pytest.mark.parametrize("counts,want", [
    ([NATIVE] * 7, 100.0),
    ([NATIVE, LOOP, NATIVE, LOOP], 50.0),
    ([LOOP] * 3, 0.0),
], ids=["all-native", "half", "python-loop"])
def test_metric_is_the_share_of_chunk_plans_made_natively(counts, want):
    reader = loader.load_metrics()[NAME]
    assert reader.UNIT == "%"
    assert _read(_planes(counts)) == pytest.approx(want)


def test_metric_reads_nothing_where_no_chunk_was_planned():
    reader = loader.load_metrics()[NAME]
    assert reader.read({"trace": None, "counters": {}}) is None
    # the scan cache served the slice: no chunk plan
    assert _read(_planes([])) is None
    # the parent's program: chunk plans whose spans carry no count
    assert _read(_planes([{}, {}])) is None
    # no query span: a program from before the names
    planes = _planes([NATIVE])
    planes[0]["lines"][0]["events"] = [
        ev for ev in planes[0]["lines"][0]["events"]
        if ev[0] != "TpuSession.query"]
    assert _read(planes) is None


def test_metric_reads_100_from_a_rehearsal_that_plans_chunks(tmp_path):
    """``lineitem.q1``'s traced rehearsal with a scan cache of one byte, so
    every query of the slice plans its chunks: each is a snappy chunk, and
    one native call walked each (a chunk it refuses to pyarrow counts too,
    as ``l_extendedprice``'s PLAIN DOUBLE ones do at the cell's size)."""
    import jax

    from spark_rapids_tpu import native

    root = lib.copy_benchmarks(tmp_path)
    name = loader.load_cell("lineitem.q1", root)["config"]["name"]
    path = os.path.join(root, "configs", name + ".json")
    with open(path) as f:
        config = json.load(f)
    config["conf"]["spark.rapids.tpu.scan.deviceCache.maxBytes"] = 1
    with open(path, "w") as f:
        json.dump(config, f)
    result = bench_run.execute(lib.rehearse_args("lineitem.q1", trace=1),
                               jax.devices(), bench_root=root)
    assert result["answers_correct"] is True
    trace = TP.newest_trace(os.path.join(root, ".cache", "trace"))
    reduced = TP.reduce_programs(TP.read_xplane(trace))
    queries = len(reduced["query_spans"])
    ctx = {"trace": {"queries": queries}, "trace_programs": reduced,
           "counters": {}}
    plans = reduced["spans"][PLAN]["counts"]
    # four row groups of the seven columns, a query
    assert plans["chunks"] == 4 * 7 * queries
    want = 100.0 if native.available() else 0.0
    assert loader.load_metrics(root)[NAME].read(ctx) == want
