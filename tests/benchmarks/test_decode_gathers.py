"""``decode_gathers_per_query``: the count the engine attaches to the spans
that splice or dispatch a query's decode programs, read from planes built
by hand and from the trace of each cell's rehearsal."""
import json
import os
import shutil

import pytest

import benchmark_testlib as lib
import loader
import run as bench_run
import trace_programs as TP

MS = 1e6  # ns
CELLS = ["store_sales.quantity_report", "lineitem.q1"]


def _devices():
    import jax

    return jax.devices()


def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _planes(stage_counts, dispatch_counts):
    """Two queries: the first takes the per-row-group path (a
    ``decode_dispatch`` a row group), the second the fused stage."""
    scan, agg = "TpuFileSourceScanExec.", "TpuHashAggregateExec."
    events = [("bench.slice", 0, 100 * MS, {}),
              _span("TpuSession.query", 0, 48, query=7),
              _span(scan + "cache_lookup", 1, 1, query=7, hits=0, lookups=2),
              _span("TpuSession.query", 50, 49, query=8),
              _span(scan + "cache_lookup", 51, 1, query=8, hits=2, lookups=2)]
    events += [_span(scan + "decode_dispatch", 5 + 10 * i, 2, query=7, **c)
               for i, c in enumerate(dispatch_counts)]
    events += [_span(agg + "stage", 60, 2, query=8, **c)
               for c in stage_counts]
    return [{"name": "/device:TPU:0", "lines": []},
            {"name": "/host:CPU", "lines": [{"name": "python",
                                             "events": events}]}]


def _ctx(reduced, queries=2):
    return {"trace": {"busy_s": 0.04, "window_s": 0.1, "queries": queries},
            "trace_programs": reduced, "counters": {}}


def _read(planes):
    reader = loader.load_metrics()["decode_gathers_per_query"]
    return reader.read(_ctx(TP.reduce_programs(planes)))


def test_metric_sums_the_spans_counts_over_the_queries():
    assert _read(_planes([{"gathers": 28}],
                         [{"gathers": 3}, {"gathers": 5}])) == 18.0
    # nothing left to gather is a reading, not a gap
    assert _read(_planes([{"gathers": 0}], [{"gathers": 0}])) == 0.0
    # every row group served decoded from the cache: no program ran
    assert _read(_planes([], [])) == 0.0


def test_metric_reads_nothing_where_the_program_does_not_count():
    reader = loader.load_metrics()["decode_gathers_per_query"]
    assert reader.UNIT == "count"
    # the parent's spans carry no such count
    assert _read(_planes([{}], [{}, {}])) is None
    assert reader.read({"trace": None, "counters": {}}) is None
    assert reader.read(_ctx(None)) is None
    # no query span: a program from before the names
    planes = _planes([{"gathers": 1}], [])
    planes[1]["lines"][0]["events"] = [
        ev for ev in planes[1]["lines"][0]["events"]
        if ev[0] != "TpuSession.query"]
    assert _read(planes) is None


@pytest.mark.parametrize("cell", CELLS)
def test_metric_reads_the_count_from_a_rehearsals_spans(cell, tmp_path,
                                                        monkeypatch):
    """The traced rehearsal of a cell: what the reader makes of its trace
    is what the engine counted from the keys of the chunks it planned."""
    from spark_rapids_tpu.io import parquet_device as PD

    root = str(tmp_path / "benchmarks")
    shutil.copytree(lib.BENCH, root, ignore=shutil.ignore_patterns(
        ".cache", ".scratch", "__pycache__"))
    if cell == CELLS[0]:
        # the CPU backend does not fuse the stage by itself; the chip does
        name = loader.load_cell(cell, root)["config"]["name"]
        with open(os.path.join(root, "configs", name + ".json")) as f:
            config = json.load(f)
        config["conf"]["spark.rapids.tpu.sql.stageFusion"] = "ON"
        with open(os.path.join(root, "configs", name + ".json"), "w") as f:
            json.dump(config, f)
    planned = []
    real = PD.plan_decode

    def spy(*args, **kwargs):
        out = real(*args, **kwargs)
        planned.append(PD.key_gathers(out[1]))
        return out

    monkeypatch.setattr(PD, "plan_decode", spy)
    result = bench_run.execute(lib.rehearse_args(cell, trace=1), _devices(),
                               bench_root=root)
    assert result["answers_correct"] is True and result["metrics"] == {}
    path = TP.newest_trace(os.path.join(root, ".cache", "trace"))
    assert path is not None
    reduced = TP.reduce_programs(TP.read_xplane(path))
    queries = len(reduced["query_spans"])
    assert queries == bench_run.TRACED_QUERIES
    value = loader.load_metrics(root)["decode_gathers_per_query"].read(
        _ctx(reduced, queries))
    spans = TP.section_spans(reduced, "stage", "decode_dispatch")
    assert value == sum(s["counts"]["gathers"] for s in spans) / queries
    assert planned and sum(planned) > 0
    if cell == CELLS[0]:
        # the fused stage splices every row group's chunks in every
        # query, the cached ones too: all the first query planned
        assert len(spans) == 1 and spans[0]["count"] == queries
        assert value == sum(planned)
    else:
        # per row group: the scan cache holds DECODED batches, so the
        # slice dispatches only what it did not keep
        assert value <= sum(planned)
