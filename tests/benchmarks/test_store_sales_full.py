"""The cell ``store_sales_full.quantity_report`` on the configuration
``tpcds_sf10_store_sales_full``: its files against ``BENCHMARK.json``, its
generator (the source's 23-column record, the four read columns as cell 1
has them) and the plain reference on it, its rehearsal (also with the scan
cut into the splits the timed size has, so that the partial -> exchange ->
final plan runs), the float32 controls, one altered answer a compared
number, and the per-layer metrics it brings, on planes built by hand."""
import json

import numpy as np
import pytest

import benchmark_contract as contract
import benchmark_testlib as lib
import compare
import loader
import run as bench_run
import trace_programs as TP

CELL = "store_sales_full.quantity_report"
CONFIG = "tpcds_sf10_store_sales_full"
QUERY = "store_sales_quantity_report"
NEW_METRICS = {
    "scan_splits_per_query": ("count", "scan"),
    "scan_file_bytes_per_query": ("bytes", "scan"),
    "shuffle_ms_per_query": ("ms", "fused stage and groupby ops"),
    "shuffle_bytes_per_query": ("bytes", "fused stage and groupby ops"),
    "shuffle_device_share": ("%", "fused stage and groupby ops")}
JOINED_LISTS = (
    "scan_host_ms_per_query", "host_fallback_columns",
    "scan_cache_hit_share", "merge_host_ms_per_query",
    "decode_gathers_per_query")
MS = 1e6  # ns


def _devices():
    import jax

    return jax.devices()


@pytest.fixture
def one_chip_host(monkeypatch):
    """The suite shows eight virtual devices, where ``shuffle.mode=auto``
    takes the mesh; the cell's host shows one chip."""
    from spark_rapids_tpu.parallel import mesh

    monkeypatch.setattr(mesh, "device_count", lambda: 1)


@pytest.fixture
def splits_as_timed(monkeypatch, one_chip_host):
    """The rehearse size holds four row groups of 2.5 MB where the timed
    size holds fourteen of 216 MB: the default ``reader.batchSizeBytes``
    lowered in proportion cuts the rehearsal into splits as the timed file
    is cut (the configuration's conf is not edited). Counts the reduce
    sides that ran."""
    from spark_rapids_tpu.conf import MAX_READER_BATCH_SIZE_BYTES
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.sql import session

    monkeypatch.setattr(session, "_SCANNER_CACHE", {})
    monkeypatch.setattr(MAX_READER_BATCH_SIZE_BYTES, "default", 6_000_000)
    seen = {"reduces": 0}
    real = TpuShuffleExchangeExec.reduce

    def counted(self, pieces):
        seen["reduces"] += 1
        return real(self, pieces)

    monkeypatch.setattr(TpuShuffleExchangeExec, "reduce", counted)
    return seen


# ---------------------------------------------------------------------------
# the contract and the files found by name
# ---------------------------------------------------------------------------
def test_the_cell_and_its_configuration_are_in_the_contract():
    spec = lib.load_spec()
    cell = contract.entry_of(spec["workloads"], CELL, "cell")
    config = contract.entry_of(spec["configs"], CONFIG, "configuration")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "quantity_report", 1)
    assert config["reduced"] == []
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    bench = loader.load_cell(CELL)
    assert bench["query_names"] == [QUERY]
    assert bench["cell"]["compile_misses_per_query_at_most"] == 0
    for word in ("144", "default conf", "partial", "exchange", "final"):
        assert word in cell["why"], word


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_names_the_cell(name):
    entry = contract.entry_of(lib.load_spec()["per_layer"], name, "metric")
    unit, layer = NEW_METRICS[name]
    assert (entry["unit"], entry["layer"]) == (unit, layer)
    assert CELL in entry["workloads"]
    assert entry["moves"] == "rows_per_s"
    assert loader.load_metrics()[name].UNIT == unit


@pytest.mark.parametrize("name", JOINED_LISTS)
def test_the_cell_joins_the_one_chip_file_metrics(name):
    entry = contract.entry_of(lib.load_spec()["per_layer"], name, "metric")
    assert CELL in entry["workloads"]


def test_the_configuration_states_the_deployment():
    config = loader.load_json("config", "configs", CONFIG)
    sf10 = loader.load_json("config", "configs", "tpcds_sf10_store_sales")
    assert config["rows"] == sf10["rows"] == 28_800_991
    assert config["row_group_rows"] == sf10["row_group_rows"] == 1 << 21
    assert (config["chips"], config["files"]) == (1, 1)
    # cell 1's four columns word for word, the other 19 as it lists them
    assert config["columns"] == sf10["columns"]
    assert config["other_columns"] == sf10["other_columns"]
    every = config["columns"] + config["other_columns"]
    assert len(every) == 23
    assert sum(c["width_bytes"] for c in every) == 144 == config[
        "record_bytes"]
    assert sorted(c["type"] for c in config["other_columns"]) == (
        ["float64"] * 11 + ["int32"] * 7 + ["int64"])
    # the default conf but for what cell 1 states too; nothing cut
    assert config["conf"] == sf10["conf"] == {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    assert config["guarantees"] == sf10["guarantees"]
    assert config["reduced"] == [] and "reduced_notes" not in config
    assert len(config["source"]) <= 200 and "table 3-2" in config["source"]
    q = loader.load_module("query", "queries", QUERY)
    assert q.needed_bytes(config) == 28_800_991 * 20
    assert q.rows_scanned(config) == 28_800_991


# ---------------------------------------------------------------------------
# the generator and the plain reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    """Two row groups that hold every domain and a short third, from one
    seed twice and from another; and the four read columns alone."""
    bench = loader.load_cell(CELL)
    gen, config = bench["generator"], bench["config"]
    root = tmp_path_factory.mktemp("full")
    seed = 2**31 + 35
    paths = {
        "a": gen.generate(config, seed, str(root / "a"), 460_000, 225_000),
        "b": gen.generate(config, seed, str(root / "b"), 460_000, 225_000),
        "c": gen.generate(config, seed + 1, str(root / "c"), 460_000,
                          225_000),
        "read_only": gen.generate(
            dict(config, other_columns=[]), seed, str(root / "four"),
            460_000, 225_000)}
    return bench, paths


def _digest(path):
    import hashlib

    with open(path, "rb") as f:
        return hashlib.sha1(f.read()).hexdigest()


def test_generator_writes_the_same_23_column_file_a_seed(generated):
    import pyarrow.parquet as pq

    bench, paths = generated
    config = bench["config"]
    assert _digest(paths["a"]) == _digest(paths["b"]) != _digest(paths["c"])
    pf = pq.ParquetFile(paths["a"])
    md = pf.metadata
    assert [md.row_group(i).num_rows for i in range(md.num_row_groups)] == [
        225_000, 225_000, 10_000]
    every = config["columns"] + config["other_columns"]
    arrow = {"int32": "int32", "int64": "int64", "float64": "double"}
    assert [(f.name, str(f.type)) for f in pf.schema_arrow] == [
        (c["name"], arrow[c["type"]]) for c in every]
    assert pf.schema_arrow.names[:4] == list(bench["queries"][0].READS)[:1] \
        + ["ss_quantity", "ss_wholesale_cost", "ss_sold_date_sk"]
    # the filler draws anew in every row group and has no nulls
    t0, t1 = pf.read_row_group(0), pf.read_row_group(1)
    for c in config["other_columns"]:
        assert t0[c["name"]].null_count == 0
        assert not t0[c["name"]].equals(t1[c["name"]]), c["name"]


@pytest.mark.parametrize("column,distinct", [
    ("ss_item_sk", 102_000), ("ss_quantity", 100),
    ("ss_wholesale_cost", 9750), ("ss_sold_date_sk", 2400)])
def test_read_columns_keep_cell_ones_domains_in_every_row_group(
        column, distinct, generated):
    """Every value planted in every row group that can hold the domain:
    the decode programs are keyed by a row group's dictionary sizes, so a
    seed compiles no program of its own."""
    import pyarrow.parquet as pq

    _, paths = generated
    for key in ("a", "c"):
        pf = pq.ParquetFile(paths[key])
        for rg in (0, 1):
            values = pf.read_row_group(rg, columns=[column])[
                column].to_numpy()
            assert len(np.unique(values)) == distinct
    lows = {"ss_item_sk": (1, 102_000), "ss_quantity": (1, 100),
            "ss_wholesale_cost": (1.0, 100.0),
            "ss_sold_date_sk": (2_450_815, 2_453_214)}
    lo, hi = lows[column]
    assert lo <= values.min() and values.max() <= hi


def test_the_plain_answer_is_the_same_with_and_without_the_filler(
        generated):
    import pyarrow.parquet as pq

    bench, paths = generated
    q = bench["queries"][0]
    four = pq.ParquetFile(paths["read_only"])
    assert four.schema_arrow.names == list(q.READS)
    full = pq.ParquetFile(paths["a"]).read(columns=list(q.READS))
    assert full.equals(four.read())
    assert q.reference(paths["a"]) == q.reference(paths["read_only"])
    assert len(q.reference(paths["a"])) == 100


def test_float32_control_is_not_correct_for_the_full_width_cell(tmp_path):
    """The control at a size a test run can hold, on the four read columns
    (the same with or without the filler, see above): the reference in
    float32 row by row fails the float limit alone, the float64 reference
    passes against itself, the blocked control reads lower."""
    bench = loader.load_cell(CELL)
    q = bench["queries"][0]
    path = bench["generator"].generate(
        dict(bench["config"], other_columns=[]), 2**31 + 5, str(tmp_path),
        8_000_000, 1 << 20)
    want = q.reference(path)
    names = bench["query_names"]
    c = compare.compare_window([(0, q.reference(path, "float32"))], [want],
                               [q], names)
    assert c["rows_wrong"][0] == 0 and c["exact_wrong"][0] == 0
    assert c["float_rel_err"][0] > c["float_rel_err"][1] == q.FLOAT_LIMIT
    assert not compare.all_within(c)
    blocked = compare.compare_window(
        [(0, q.reference(path, "float32_blocked"))], [want], [q], names)
    assert 0 < blocked["float_rel_err"][0] < c["float_rel_err"][0]
    assert compare.all_within(
        compare.compare_window([(0, want)], [want], [q], names))


# ---------------------------------------------------------------------------
# the rehearsal
# ---------------------------------------------------------------------------
def _rehearse(trace, capsys):
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
    for number in ("rows_wrong", "exact_wrong", "order_wrong",
                   "placement_wrong"):
        assert result["compared"][number] == {"value": 0.0, "limit": 0.0}
    err = result["compared"]["float_rel_err"]
    assert err["value"] <= err["limit"] == 4e-6
    assert "first query:" in out.err and "window:" in out.err
    return result


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_cell_and_prints_no_device_metric(
        trace, capsys, one_chip_host):
    _rehearse(trace, capsys)


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_in_the_timed_sizes_splits_runs_the_exchange(
        trace, capsys, splits_as_timed):
    result = _rehearse(trace, capsys)
    # a reduce side every drain: the first query, two warm-ups, the
    # window's queries and the placement check after it
    assert splits_as_timed["reduces"] == 4 + result["attempted"]


def _alter_count(rows):
    rows = list(rows)
    rows[0] = rows[0][:-1] + (rows[0][-1] + 1,)
    return rows


def _alter_float(rows):
    rows = list(rows)  # a float sum off by a thousandth of itself
    rows[0] = rows[0][:1] + (rows[0][1] * (1 + 1e-3),) + rows[0][2:]
    return rows


def _drop_row(rows):
    return list(rows)[1:]


@pytest.mark.parametrize("fault,number", [
    (_alter_count, "exact_wrong"), (_alter_float, "float_rel_err"),
    (_drop_row, "rows_wrong")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        fault, number, monkeypatch, splits_as_timed):
    """One answer of the window altered at the columnar-to-row boundary
    (every later one too), on the partial -> exchange -> final plan."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    real = ColumnarBatch.to_rows
    state = {"calls": 0}

    def broken(self):
        rows = real(self)
        state["calls"] += 1
        return fault(rows) if state["calls"] > 3 and len(rows) > 1 else rows

    monkeypatch.setattr(ColumnarBatch, "to_rows", broken)
    result = bench_run.execute(lib.rehearse_args(CELL), _devices())
    assert splits_as_timed["reduces"] >= 4
    assert result["answers_correct"] is False and result["correct"] is False
    c = result["compared"][number]
    assert c["value"] > c["limit"]


# ---------------------------------------------------------------------------
# the readers, on planes built by hand
# ---------------------------------------------------------------------------
def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _op(name, start_ms, dur_ms, tf_op):
    return (f"%{name} = f32[8]{{0}} fusion(...)", start_ms * MS,
            dur_ms * MS, {"tf_op": tf_op})


def _device_plane(counts, exchange):
    """The chip's side of the same slice: a query runs ``jit_agg_stage``
    for 10 ms and, where the plan has an exchange, ``jit_exchange`` for 20
    (a sort of 12 ms and a gather of 8), under the scope
    ``shuffle_exchange`` unless it is the parent's program."""
    scope = "shuffle_exchange/" if counts else ""
    ops, modules = [], []
    for q0 in (0, 50):
        modules.append(("jit_agg_stage(2)", (q0 + 2) * MS, 10 * MS, {}))
        ops.append(_op("fusion.1", q0 + 2, 10,
                       "jit(agg_stage)/jit(main)/agg_update/dot_general:"))
        if exchange:
            prog = f"jit(exchange)/jit(main)/{scope}"
            modules.append(("jit_exchange(3)", (q0 + 12) * MS, 20 * MS, {}))
            ops += [_op("sort.1", q0 + 12, 12, prog + "sort:"),
                    _op("fusion.2", q0 + 24, 8, prog + "gather:")]
    return {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": modules},
        {"name": "XLA Ops", "events": ops}]}


def _planes(counts=True, exchange=True, cached=False):
    """A slice of 100 ms, two queries of two splits each: per split a scan
    ``plan`` span (5 ms; unless ``cached`` with a ``read_file`` of 1 ms
    and two ``page_plan`` of 1 ms nested), then the exchange's ``map`` span
    (30 ms, the two splits' work nested in it: 14 ms of its own) and one
    ``reduce`` (2 ms). ``counts`` off is the parent's program: the same
    spans under its names, with no count and no scope. The chip's plane
    beside them (``_device_plane``)."""
    scan, agg = "TpuFileSourceScanExec", "TpuHashAggregateExec"
    ex = "TpuShuffleExchangeExec"
    events = [("bench.slice", 0, 100 * MS, {})]

    def c(**kw):
        return kw if counts else {}

    for qid, q0 in ((7, 0), (8, 50)):
        events += [("bench.query", q0 * MS, 50 * MS, {}),
                   _span("TpuSession.query", q0, 49, query=qid)]
        if exchange:
            events.append(_span(
                ex + ".map" if counts else ex, q0 + 1, 30, query=qid,
                **c(partitions=2, bytes=20_000, rows=200, inputs=2)))
        for i, s0 in enumerate((q0 + 2, q0 + 10)):
            events.append(_span(agg + ".stage", s0, 8, query=qid,
                                **c(mode="partial", strategy="MATMUL")))
            events.append(_span(scan + ".plan", s0, 5, query=qid,
                                **c(splits=1, row_groups=9 - 4 * i)))
            events.append(_span(scan + ".cache_lookup", s0, 0.5, query=qid,
                                hits=9 if cached else 0, lookups=9,
                                cache="hit" if cached else "miss"))
            if not cached:
                events.append(_span(scan + ".read_file", s0 + 1, 1,
                                    query=qid, **c(file_bytes=1000)))
                for k in (2, 3):
                    events.append(_span(
                        scan + ".page_plan", s0 + k, 1, query=qid,
                        **c(file_bytes=500_000)))
        if exchange:
            events.append(_span(
                ex + ".reduce", q0 + 32, 2, query=qid,
                **c(partitions=4, bytes=20_000, rows=200)))
    return [_device_plane(counts, exchange),
            {"name": "/host:CPU", "lines": [
                {"name": "python", "events": events}]}]


def _ctx(planes):
    return {"trace": {"busy_s": 0.06, "window_s": 0.1, "queries": 2,
                      "query_indices": [0, 0], "chips_traced": 1},
            "trace_programs": TP.reduce_programs(planes),
            "scan_planes": planes,
            "peaks": loader.load_peaks("TPU v5 lite"),
            "config": {"rows": 1000}, "queries": [], "counters": {}}


def _read(planes):
    readers = loader.load_metrics()
    ctx = _ctx(planes)
    return {name: readers[name].read(ctx) for name in NEW_METRICS}


@pytest.mark.parametrize("planes,want", [
    # two splits a query read from the file: a footer and two chunks each
    (_planes(), {
        "scan_splits_per_query": 2.0,
        "scan_file_bytes_per_query": 2 * (1000 + 2 * 500_000),
        "shuffle_ms_per_query": 30 - 16 + 2,
        "shuffle_bytes_per_query": 20_000.0,
        "shuffle_device_share": 100 * 40 / 60}),
    # the scan cache served every row group: no file byte is touched
    (_planes(cached=True), {
        "scan_splits_per_query": 2.0, "scan_file_bytes_per_query": 0.0,
        "shuffle_ms_per_query": 30 - 16 + 2,
        "shuffle_bytes_per_query": 20_000.0,
        "shuffle_device_share": 100 * 40 / 60}),
    # one split and no exchange (cell 1's plan): the shuffle says nothing
    (_planes(exchange=False), {
        "scan_splits_per_query": 2.0,
        "scan_file_bytes_per_query": 2 * (1000 + 2 * 500_000),
        "shuffle_ms_per_query": None, "shuffle_bytes_per_query": None,
        "shuffle_device_share": None}),
    # the parent's program: spans without counts, the exchange's map side
    # under the exec's bare name, its programs under no scope
    (_planes(counts=False), {
        "scan_splits_per_query": None, "scan_file_bytes_per_query": None,
        "shuffle_ms_per_query": 30 - 16 + 2,
        "shuffle_bytes_per_query": None, "shuffle_device_share": None}),
], ids=["from_the_file", "from_the_scan_cache", "no_exchange", "parent"])
def test_readers_on_planes_built_by_hand(planes, want):
    got = _read(planes)
    assert set(got) == set(want)
    for name, value in want.items():
        assert got[name] == (pytest.approx(value) if value is not None
                             else None), name


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_is_silent_where_there_is_nothing_to_read(name):
    reader = loader.load_metrics()[name]
    assert reader.read({"trace": None, "counters": {}}) is None
    no_names = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.slice", 0, 100 * MS, {})]}]}]
    assert reader.read(_ctx(no_names)) is None
