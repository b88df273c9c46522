"""The four-chip cell ``store_sales_sf100.cached_report.mesh4``: its files
against ``BENCHMARK.json``, its generator and plain reference, its
rehearsal on four virtual CPU devices, the parent's failure mode (a
``DataFrame`` without ``cache``), and the per-layer metrics it brings, on
planes built by hand and on the trace of its own rehearsal."""
import json
import os
import time

import numpy as np
import pytest

import benchmark_contract as contract
import benchmark_testlib as lib
import compare
import loader
import run as bench_run
import trace_mesh
import trace_programs as TP
import trace_reduce as TR

CELL = "store_sales_sf100.cached_report.mesh4"
CONFIG = "tpcds_sf100_store_sales_mesh4"
QUERY = "store_sales_cached_quantity_report"
NEW_METRICS = {
    "cache_resident_share": "%", "mesh_h2d_bytes_per_query": "bytes",
    "exchange_device_share": "%", "exchange_bytes_per_query": "bytes",
    "exchange_roofline": "%", "mesh_agg_roofline": "%",
    "mesh_shard_rows_skew": "ratio"}
MS = 1e6  # ns


def _devices():
    import jax

    return jax.devices()


# ---------------------------------------------------------------------------
# the contract and the files found by name
# ---------------------------------------------------------------------------
def test_the_cell_and_its_configuration_are_there_as_accepted():
    """Wherever they stand in ``BENCHMARK.json``: what may be appended
    after them is ``test_benchmark_contract.py``'s to hold."""
    assert (CELL, CONFIG, [QUERY]) == (
        contract.MESH4["cell"], contract.MESH4["config"],
        contract.MESH4["queries"])
    contract.check_mesh4_cell(lib.load_spec(), lib.BENCH)


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_names_the_cell(name):
    entry = contract.entry_of(lib.load_spec()["per_layer"], name, "metric")
    assert entry["unit"] == NEW_METRICS[name]
    assert CELL in entry["workloads"]
    assert entry["moves"] == "rows_per_s"
    assert entry["layer"] in ("memory", "mesh", "kernels")


def test_the_configuration_states_the_deployment():
    config = loader.load_json("config", "configs", CONFIG)
    sf10 = loader.load_json("config", "configs", "tpcds_sf10_store_sales")
    assert config["rows"] == 287_997_024 == 137 * 2_097_152 + 687_200
    assert config["row_group_rows"] == sf10["row_group_rows"] == 1 << 21
    assert config["chips"] == 4 and config["files"] == 1
    # cell 1's four columns at its widths; the SF100 item table's keys
    assert [(c["name"], c["type"], c["width_bytes"])
            for c in config["columns"]] == [
        (c["name"], c["type"], c["width_bytes"]) for c in sf10["columns"]]
    assert "1..204000" in config["columns"][0]["distribution"]
    assert len(config["columns"]) + len(config["other_columns"]) == 23
    assert config["conf"] == {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        "spark.rapids.tpu.shuffle.mode": "ici",
        "spark.rapids.tpu.mesh.devices": 4}
    assert set(sf10["guarantees"]) < set(config["guarantees"])
    assert "no query of the window reads the file" in \
        config["guarantees"]["residency"]
    q = loader.load_module("query", "queries", QUERY)
    # 4 + 4 + 8 + 4 bytes a row; the planes a chip holds: 24 bytes a slot
    assert q.needed_bytes(config) == 287_997_024 * 20 == 5_759_940_480
    assert q.rows_scanned(config) == 287_997_024
    assert 4 * (1 << 27) * 24 == 12_884_901_888


# ---------------------------------------------------------------------------
# the generator and the plain reference
# ---------------------------------------------------------------------------
def test_generator_writes_row_group_by_row_group_the_same_file_a_seed(
        tmp_path):
    import hashlib

    import pyarrow.parquet as pq

    bench = loader.load_cell(CELL)
    gen, config = bench["generator"], bench["config"]

    def digest(path):
        with open(path, "rb") as f:
            return hashlib.sha1(f.read()).hexdigest()

    # two row groups that hold every domain, and a short third
    a = gen.generate(config, 2**31 + 5, str(tmp_path / "a"), 460_000,
                     225_000)
    b = gen.generate(config, 2**31 + 5, str(tmp_path / "b"), 460_000,
                     225_000)
    c = gen.generate(config, 2**31 + 6, str(tmp_path / "c"), 460_000,
                     225_000)
    assert digest(a) == digest(b) != digest(c)
    pf = pq.ParquetFile(a)
    md = pf.metadata
    assert [md.row_group(i).num_rows for i in range(md.num_row_groups)] == [
        225_000, 225_000, 10_000]
    assert [f.name for f in pf.schema_arrow] == [
        "ss_item_sk", "ss_quantity", "ss_wholesale_cost", "ss_sold_date_sk"]
    assert [str(f.type) for f in pf.schema_arrow] == [
        "int32", "int32", "double", "int32"]
    for rg in (0, 1):  # every value of every domain, planted
        t = pf.read_row_group(rg)
        assert len(np.unique(t["ss_item_sk"].to_numpy())) == 204_000
        assert len(np.unique(t["ss_quantity"].to_numpy())) == 100
        assert len(np.unique(t["ss_wholesale_cost"].to_numpy())) == 9750
        assert len(np.unique(t["ss_sold_date_sk"].to_numpy())) == 2400
    cost = pf.read_row_group(0)["ss_wholesale_cost"].to_numpy()
    assert cost.min() >= 1.0 and cost.max() <= 100.0
    assert np.allclose(cost * 100, np.round(cost * 100))


def test_cached_report_reference_by_hand(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    q = loader.load_module("query", "queries", QUERY)
    path = str(tmp_path / "store_sales.parquet")
    pq.write_table(pa.table({
        "ss_item_sk": pa.array([1, None, 3, 4, 5, 6], pa.int32()),
        "ss_quantity": pa.array([7, 7, 9, 9, 9, 7], pa.int32()),
        "ss_wholesale_cost": pa.array([1.5, 2.25, 10.0, 0.5, 4.0, 100.0]),
        # the last row is before the cut and is filtered out
        "ss_sold_date_sk": pa.array(
            [q.DATE_CUT, q.DATE_CUT + 1, q.DATE_CUT, q.DATE_CUT + 9,
             q.DATE_CUT, q.DATE_CUT - 1], pa.int32()),
    }), path, row_group_size=2)  # three blocks of rows, read two at a time
    q.BLOCK_ROW_GROUPS, real = 2, q.BLOCK_ROW_GROUPS
    try:
        # count(ss_item_sk) leaves the null out; the sums do not
        want = [(7, 3.75, 14, 1), (9, 14.5, 27, 3)]
        assert q.reference(path) == want
        assert q.reference(path, "float32") == want
        assert q.reference(path, "float32_blocked") == want
    finally:
        q.BLOCK_ROW_GROUPS = real


def test_reference_in_blocks_is_cell_ones_reference(tmp_path):
    """The same query on the same file: the blocked reader and cell 1's
    ``read_parquet`` agree, keys and integers exactly."""
    bench = loader.load_cell(CELL)
    q = bench["queries"][0]
    plain = loader.load_module("query", "queries",
                               "store_sales_quantity_report")
    path = bench["generator"].generate(
        bench["config"], 2**31 + 7, str(tmp_path), 300_000, 1 << 14)
    got, want = q.reference(path), plain.reference(path)
    assert [(r[0], r[2], r[3]) for r in got] == [
        (r[0], r[2], r[3]) for r in want]
    assert max(abs(g[1] - w[1]) / w[1] for g, w in zip(got, want)) < 1e-13
    assert (q.KEYS, q.EXACT, q.FLOAT, q.ORDERED) == (
        plain.KEYS, plain.EXACT, plain.FLOAT, plain.ORDERED)


def test_float32_control_is_not_correct_for_the_cached_report(tmp_path):
    """The control at a size a test run can hold: the reference in float32
    row by row fails the float limit alone, the float64 reference passes
    against itself, the blocked control reads lower than row by row."""
    bench = loader.load_cell(CELL)
    q = bench["queries"][0]
    path = bench["generator"].generate(
        bench["config"], 2**31 + 5, str(tmp_path), 8_000_000, 1 << 20)
    want = q.reference(path)
    names = bench["query_names"]
    c = compare.compare_window([(0, q.reference(path, "float32"))], [want],
                               [q], names)
    assert c["rows_wrong"][0] == 0 and c["exact_wrong"][0] == 0
    assert c["float_rel_err"][0] > c["float_rel_err"][1]
    assert not compare.all_within(c)
    blocked = compare.compare_window(
        [(0, q.reference(path, "float32_blocked"))], [want], [q], names)
    assert 0 < blocked["float_rel_err"][0] < c["float_rel_err"][0]
    # unlike cell 1's, this cell's limit (1e-10: the chips read 7e-14)
    # refuses the blocked control too
    assert not compare.all_within(blocked)
    assert compare.all_within(
        compare.compare_window([(0, want)], [want], [q], names))


# ---------------------------------------------------------------------------
# the rehearsal, and the parent's failure mode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_cell_on_four_devices(trace, capsys):
    rc = bench_run.main(["--workload", CELL, "--seed", str(2**31 + 7),
                         "--seconds", "0.3", "--trace", str(trace),
                         "--rehearse"])
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["metrics"] == {} and "busy_s" not in result["device"]
    assert result["answers_correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["window"]["compiles"] == 0
    assert result["compared"]["rows_wrong"] == {"value": 0.0, "limit": 0.0}
    assert result["compared"]["placement_wrong"]["value"] == 0.0
    err = result["compared"]["float_rel_err"]
    assert err["value"] <= err["limit"] < 1e-3
    assert "first query:" in out.err and "window:" in out.err


def test_a_dataframe_without_cache_ends_in_the_warm_up_error(
        monkeypatch, capsys):
    """The parent's side of the cell: its ``DataFrame`` has no ``cache``,
    so every query raises at once, ``Driver.one`` records it, and the run
    ends with the warm-up error and no result line. It does not hang and
    it does not run the window."""
    from spark_rapids_tpu.sql import session

    for name in ("cache", "persist"):
        monkeypatch.delattr(session.DataFrame, name)
    t0 = time.perf_counter()
    rc = bench_run.main(["--workload", CELL, "--seed", "11", "--seconds",
                         "30", "--rehearse"])
    took = time.perf_counter() - t0
    out = capsys.readouterr()
    assert rc == 1 and out.out == ""
    assert "a warm-up query failed" in out.err
    assert "AttributeError" in out.err and "'cache'" in out.err
    assert "window:" not in out.err
    assert took < 20, took  # the data and three queries that raise


# ---------------------------------------------------------------------------
# the readers, on planes built by hand
# ---------------------------------------------------------------------------
def _op(name, start_ms, dur_ms, tf_op):
    return (f"%{name} = f32[8]{{0}} fusion(...)", start_ms * MS,
            dur_ms * MS, {"tf_op": tf_op})


def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _mesh_planes(chips=4, served=True, names=True, row_scatters=None):
    """A slice of 100 ms, two queries on ``chips`` chips, each running
    ``jit_mesh_agg`` for 40 ms: 30 ms of update in a loop, 2 ms under
    ``mesh_exchange``, 6 ms of merge, 2 ms outside every scope. The
    ``spmd`` spans carry ``row_scatters`` where one is given (PR 31's
    count: the parent of that PR has none)."""
    prog = "jit(mesh_agg)/jit(main)/jit(shmap_body)/"
    planes = []
    for chip in range(chips):
        ops, modules = [], []
        for q0 in (0, 50):
            modules.append((f"jit_mesh_agg({7 + chip})", q0 * MS, 40 * MS,
                            {}))
            ops += [
                _op("while.1", q0, 30, prog + "while:"),
                _op("fusion.1", q0 + 1, 28,
                    prog + "while/body/agg_update/dot_general:"),
                _op("all-to-all.1", q0 + 30, 1,
                    prog + "mesh_exchange/all_to_all:"),
                _op("sort.2", q0 + 31, 1, prog + "mesh_exchange/sort:"),
                _op("fusion.3", q0 + 32, 6, prog + "agg_merge/dot_general:"),
                _op("copy.1", q0 + 38, 2, prog + "copy:")]
        planes.append({"name": f"/device:TPU:{chip}", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops}]})
    agg, cached = trace_mesh.MESH_AGG, trace_mesh.CACHED_SCAN
    events = [("bench.slice", 0, 100 * MS, {})]
    for qid, q0 in ((7, 0), (8, 50)):
        events.append(("bench.query", q0 * MS, 50 * MS, {}))
        if names:
            events.append(_span("TpuSession.query", q0, 49, query=qid))
        hit = served or qid == 8
        if not hit:
            events.append(_span(cached + ".fill", q0 + 1, 5, query=qid,
                                rows=1000, bytes=24000, shards=chips))
        events += [
            _span(cached + ".serve", q0 + 6, 0.1, query=qid, hits=int(hit),
                  rows=1000 if hit else 0, bytes=24000 if hit else 0,
                  shards=chips, source="cached"),
            _span(agg + ".stage", q0 + 1, 6, query=qid,
                  h2d_bytes=0 if hit else 24000, source="cached",
                  shards=chips, shard_rows_max=280, shard_rows_sum=1000),
            _span(agg + ".spmd", q0 + 7, 1, query=qid,
                  exchange_bytes=80_000_000, exchange_cap=4096,
                  **({} if row_scatters is None
                     else {"row_scatters": row_scatters}))]
    planes.append({"name": "/host:CPU", "lines": [
        {"name": "python", "events": events}]})
    return planes


class _Q:
    @staticmethod
    def needed_bytes(config):
        return config["rows"] * 20

    @staticmethod
    def rows_scanned(config):
        return config["rows"]


def _ctx(planes, chips=4, busy_s=0.04):
    return {"trace": {"busy_s": busy_s, "window_s": 0.1, "queries": 2,
                      "query_indices": [0, 0], "chips_traced": chips},
            "trace_programs": TP.reduce_programs(planes),
            "mesh_planes": planes, "device_kind": "TPU v5 lite",
            "peaks": loader.load_peaks("TPU v5 lite"),
            "config": {"rows": 1000}, "queries": [_Q], "counters": {}}


def _read_all(ctx):
    readers = loader.load_metrics()
    return {name: readers[name].read(ctx) for name in NEW_METRICS}


def test_readers_on_a_window_served_from_resident_planes():
    got = _read_all(_ctx(_mesh_planes()))
    assert got["cache_resident_share"] == 100.0
    assert got["mesh_h2d_bytes_per_query"] == 0
    assert got["exchange_bytes_per_query"] == 80_000_000
    # 2 ms of a query's 40 ms on every chip
    assert got["exchange_device_share"] == pytest.approx(100 * 0.004 / 0.04)
    # a chip's share of 160 MB (40 MB) at 200 GB/s = 0.2 ms, over 4 ms
    assert got["exchange_roofline"] == pytest.approx(100 * 0.0002 / 0.004)
    # 2 queries x 20,000 bytes over 4 chips x 819 GB/s, against 80 ms of
    # mesh_agg on a chip
    assert got["mesh_agg_roofline"] == pytest.approx(
        100 * (40_000 / (4 * 819e9)) / 0.08)
    assert got["mesh_shard_rows_skew"] == pytest.approx(280 / 250)
    for name in ("exchange_roofline", "mesh_agg_roofline"):
        assert 0 < got[name] < 100, name


@pytest.mark.parametrize("planted,reads", [
    (None, None),  # a program from before the count: left out of the line
    (0, 0.0),      # every aggregate in the limb matmul: a 0 that is read
    (3, 3.0),      # a scatter float sum and a min/max family came back
])
def test_row_scatters_are_the_spmd_spans_count_over_the_queries(
        planted, reads):
    reader = loader.load_metrics()["agg_row_scatters_per_query"]
    assert (reader.NAME, reader.UNIT) == ("agg_row_scatters_per_query",
                                          "count")
    got = reader.read(_ctx(_mesh_planes(row_scatters=planted)))
    assert got == reads and (got is None) == (reads is None)
    # one chip, or no engine names: no such span, nothing read
    assert reader.read(_ctx(_mesh_planes(names=False, row_scatters=0))) \
        is None
    entry = contract.entry_of(lib.load_spec()["per_layer"], reader.NAME,
                              "metric")
    assert CELL in entry["workloads"] and entry["better"] == "lower"
    assert (entry["layer"], entry["moves"], entry["source"]) == (
        "fused stage and groupby ops", "rows_per_s", "program_counter")


@pytest.mark.parametrize("chips", [1, 2, 4])
def test_scan_roofline_is_against_the_peak_of_the_chips_traced(chips):
    """Four chips' bytes over four chips' peak: a mesh cell's reading can
    pass 100% only where one chip's could."""
    readers = loader.load_metrics()
    planes = _mesh_planes(chips=chips)
    ctx = _ctx(planes)
    # busy seconds and the count of chips as the reduction itself gives them
    ctx["trace"].update(TR.reduce_planes(
        [dict(p, lines=[dict(line, events=[ev[:3] for ev in line["events"]])
                        for line in p["lines"]]) for p in planes]))
    assert ctx["trace"]["chips_traced"] == chips
    assert ctx["trace"]["busy_s"] == pytest.approx(0.08)  # a chip's own
    # 2 queries x 20,000 bytes over the chips' 819 GB/s each
    got = readers["scan_roofline"].read(ctx)
    assert got == pytest.approx(100 * (40_000 / (chips * 819e9)) / 0.08)
    # the whole query's share beside the kernel's: here every busy second
    # is mesh_agg's, so the two agree; one chip's peak for four chips'
    # bytes would read four times that
    assert got == pytest.approx(readers["mesh_agg_roofline"].read(ctx))


def test_a_query_that_fills_is_not_served_from_residency():
    got = _read_all(_ctx(_mesh_planes(served=False)))
    assert got["cache_resident_share"] == 50.0
    assert got["mesh_h2d_bytes_per_query"] == 12000


def test_scope_seconds_are_a_chips_own():
    planes = _mesh_planes(chips=2)
    assert trace_mesh.scope_seconds(planes, "mesh_exchange") == {
        "scope": pytest.approx(0.004), "chips": 2}
    assert trace_mesh.scope_seconds(planes, "agg_update")["scope"] == \
        pytest.approx(0.056)
    # the accepted reduction files the exchange under its program, and
    # reads the update and the merge through the shared scope words
    r = TP.reduce_programs(planes)
    assert r["by_label"] == pytest.approx(
        {"agg_update": 0.056, "agg_merge": 0.012, "mesh_agg": 0.012})
    share = loader.load_metrics()["agg_device_share"].read(
        _ctx(planes, chips=2))
    assert share == pytest.approx(100 * 0.068 / 0.04)


def test_readers_read_nothing_from_a_program_without_the_names():
    """The parent of the PR that brought the spans, or a cell on one chip:
    every new metric is left out of the line."""
    bare = _mesh_planes(names=False)
    assert set(_read_all(_ctx(bare)).values()) == {None}
    one_chip = [p for p in _mesh_planes(chips=1)
                if p["name"] != "/host:CPU"]
    one_chip.append({"name": "/host:CPU", "lines": [{"name": "python",
                    "events": [("bench.slice", 0, 100 * MS, {}),
                               _span("TpuSession.query", 0, 49, query=7),
                               _span("TpuHashAggregateExec.stage", 1, 2,
                                     query=7, gathers=28)]}]})
    for plane in one_chip[:1]:  # an agg_stage program, no mesh scope
        for line in plane["lines"]:
            line["events"] = [
                (ev[0].replace("mesh_agg", "agg_stage"), ev[1], ev[2],
                 {"tf_op": "jit(agg_stage)/agg_update/dot_general:"}
                 if "tf_op" in ev[3] else ev[3])
                for ev in line["events"]]
    assert set(_read_all(_ctx(one_chip, chips=1)).values()) == {None}


def test_ici_peaks_name_the_kinds_peaks_json_has():
    with open(os.path.join(lib.BENCH, "peaks.json")) as f:
        accepted = json.load(f)
    with open(os.path.join(lib.BENCH, "ici_peaks.json")) as f:
        ici = json.load(f)
    assert set(ici) == set(accepted)
    for kind, row in ici.items():
        assert row["hbm_GB/s"] == accepted[kind]["hbm_GB/s"]
        assert row["ici_GB/s"] == 200 and "1,600 Gbit/s" in row["source"]
    assert trace_mesh.ici_peak({"device_kind": "TPU v9 imaginary"}) is None


# ---------------------------------------------------------------------------
# the readers, on the trace of the cell's own rehearsal
# ---------------------------------------------------------------------------
def test_readers_on_the_rehearsals_trace(tmp_path):
    root = lib.copy_benchmarks(tmp_path)
    result = bench_run.execute(lib.rehearse_args(CELL, trace=1), _devices(),
                               bench_root=root)
    assert result["answers_correct"] is True and result["metrics"] == {}
    path = TP.newest_trace(os.path.join(root, ".cache", "trace"))
    planes = TP.read_xplane(path)
    bench = loader.load_cell(CELL, root)
    ctx = _ctx(planes, chips=0, busy_s=0.0)
    ctx["config"] = dict(bench["config"], **bench["config"]["rehearse"])
    ctx["queries"] = bench["queries"]
    assert len(ctx["trace_programs"]["query_spans"]) == 2
    got = _read_all(ctx)
    # the window's queries are served from the four resident shards
    assert got["cache_resident_share"] == 100.0
    assert got["mesh_h2d_bytes_per_query"] == 0
    assert got["exchange_bytes_per_query"] == 4 * (4 * 4096 * 32 + 16)
    assert got["mesh_shard_rows_skew"] == pytest.approx(
        5 * 4096 / (70000 / 4))
    # no device plane on the CPU: the device's metrics read nothing
    assert got["exchange_device_share"] is None
    assert got["exchange_roofline"] is None
    assert got["mesh_agg_roofline"] is None
    # accepted readers: no file scanned in the slice, nothing uploaded
    readers = loader.load_metrics(root)
    for name in ("scan_host_ms_per_query", "host_fallback_columns",
                 "scan_cache_hit_share", "merge_host_ms_per_query",
                 "decode_gathers_per_query"):
        assert readers[name].read(ctx) is None, name
    assert readers["h2d_bytes_per_query"].read(ctx) == 0
    assert readers["plan_ms_per_query"].read(ctx) > 0
