"""The cell ``lineitem_full.q1`` on the configuration ``tpch_lineitem_full``:
its files against ``BENCHMARK.json``, its generator (the source's 16-column
record in the specification's order, the seven read columns as cell 2 has
them) and the plain reference on it, the float32 control, its rehearsal
with the scan cut into the splits the timed size has (so that the partial
-> hash exchange -> final -> range exchange -> sort plan runs), one altered
answer a compared number (the rows' order among them), and the two
per-layer metrics it brings, on planes built by hand."""
import json

import numpy as np
import pytest

import benchmark_contract as contract
import benchmark_testlib as lib
import compare
import loader
import run as bench_run
import trace_programs as TP

CELL = "lineitem_full.q1"
CONFIG = "tpch_lineitem_full"
QUERY = "lineitem_q1"
NEW_METRICS = {
    "range_sample_ms_per_query": ("ms", "query_p95_s", "program_span"),
    "merge_partials_per_query": ("count", "rows_per_s", "program_counter")}
LAYER = "fused stage and groupby ops"
JOINED_LISTS = (
    "scan_host_ms_per_query", "host_fallback_columns",
    "scan_cache_hit_share", "merge_host_ms_per_query",
    "decode_gathers_per_query", "scan_splits_per_query",
    "scan_file_bytes_per_query", "shuffle_ms_per_query",
    "shuffle_bytes_per_query", "shuffle_device_share")
#: TPC-H v3 section 1.4's order
RECORD = ("l_orderkey", "l_partkey", "l_suppkey", "l_linenumber",
          "l_quantity", "l_extendedprice", "l_discount", "l_tax",
          "l_returnflag", "l_linestatus", "l_shipdate", "l_commitdate",
          "l_receiptdate", "l_shipinstruct", "l_shipmode", "l_comment")
MS = 1e6  # ns


def _devices():
    import jax

    return jax.devices()


@pytest.fixture
def splits_as_timed(monkeypatch):
    """The cell's host shows one chip (the suite shows eight virtual
    devices, where ``shuffle.mode=auto`` takes the mesh), and the timed
    file's 29 row groups of 128.9 MB pack into two scan partitions under
    the default ``reader.batchSizeBytes``: lowered in proportion, it cuts
    the rehearsal's four row groups of 1.16 MB into two and two (the
    configuration's conf is not edited). Counts the reduce sides that ran,
    by the kind of their exchange's partitioning."""
    from spark_rapids_tpu.conf import MAX_READER_BATCH_SIZE_BYTES
    from spark_rapids_tpu.exec.exchange import TpuShuffleExchangeExec
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.sql import session

    monkeypatch.setattr(mesh, "device_count", lambda: 1)
    monkeypatch.setattr(session, "_SCANNER_CACHE", {})
    monkeypatch.setattr(MAX_READER_BATCH_SIZE_BYTES, "default", 2_500_000)
    seen = {"hash": 0, "range": 0}
    real = TpuShuffleExchangeExec.reduce

    def counted(self, pieces):
        seen[self.partitioning.kind] += 1
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
        CONFIG, "q1", 1)
    assert config["reduced"] == []
    assert config["file"] == f"benchmarks/configs/{CONFIG}.json"
    bench = loader.load_cell(CELL)
    # cell 2's query file as it is: its reference, limit and order hold
    assert bench["query_names"] == [QUERY]
    assert bench["query_names"] == loader.load_cell("lineitem.q1")[
        "query_names"]
    # no higher than cell 2's 1: the sort's program is found in a
    # process-wide cache, so a steady query compiles nothing
    assert bench["cell"]["compile_misses_per_query_at_most"] == 0
    for word in ("128-byte", "default conf", "hash exchange",
                 "range exchange", "sort"):
        assert word in cell["why"], word


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_each_new_metric_names_the_cell_alone(name):
    entry = contract.entry_of(lib.load_spec()["per_layer"], name, "metric")
    unit, moves, source = NEW_METRICS[name]
    assert (entry["unit"], entry["layer"], entry["moves"],
            entry["source"]) == (unit, LAYER, moves, source)
    assert entry["workloads"] == [CELL]  # cell 2's line is left as it is
    assert loader.load_metrics()[name].UNIT == unit


@pytest.mark.parametrize("name", JOINED_LISTS)
def test_the_cell_joins_the_file_and_shuffle_metrics(name):
    entry = contract.entry_of(lib.load_spec()["per_layer"], name, "metric")
    assert entry["workloads"][-1] == CELL


def test_the_configuration_states_the_deployment():
    config = loader.load_json("config", "configs", CONFIG)
    cut = loader.load_json("config", "configs", "tpch_lineitem")
    assert config["rows"] == cut["rows"] == 59_986_052
    assert config["row_group_rows"] == cut["row_group_rows"] == 1 << 21
    assert (config["chips"], config["files"], config["table"]) == (
        1, 1, "lineitem")
    # cell 2's seven columns word for word, the other nine as it lists them
    assert config["columns"] == cut["columns"]
    assert config["other_columns"] == cut["other_columns"]
    assert config["write_other_columns"] is True
    every = config["columns"] + config["other_columns"]
    assert len(every) == 16
    assert sorted(c["name"] for c in every) == sorted(RECORD)
    assert tuple(config["column_order"]) == RECORD
    assert sum(c["width_bytes"] for c in every) == 128 == config[
        "record_bytes"]
    # the default conf but for the one key cell 2 states too; nothing cut
    assert config["conf"] == {
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    assert set(cut["conf"]) - set(config["conf"]) == {
        "spark.rapids.tpu.sql.agg.strategy"}
    assert config["guarantees"] == cut["guarantees"]
    assert config["reduced"] == [] and "reduced_notes" not in config
    assert len(config["source"]) <= 200
    for word in ("section 1.4", "16 columns", "128 bytes", "SF10",
                 "59,986,052"):
        assert word in config["source"], word
    assumed = " ".join(config["assumed"])
    for word in ("filler", "no nulls", "snappy", "PLAIN", "two partitions",
                 "16 and 13", "sql.agg.strategy", "sql.shuffle.partitions"):
        assert word in assumed, word
    assert config["rehearse"] == cut["rehearse"]
    q = loader.load_module("query", "queries", QUERY)
    assert q.needed_bytes(config) == 59_986_052 * 38
    assert q.rows_scanned(config) == 59_986_052


# ---------------------------------------------------------------------------
# the generator and the plain reference
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def generated(tmp_path_factory):
    """Two row groups that hold every domain and a short third, from one
    seed twice and from another; the seven read columns alone; and cell
    2's own file of the same seed."""
    bench = loader.load_cell(CELL)
    gen, config = bench["generator"], bench["config"]
    root = tmp_path_factory.mktemp("li_full")
    seed = 2**31 + 37
    size = (100_000, 40_000)
    cell2 = loader.load_cell("lineitem.q1")
    paths = {
        "a": gen.generate(config, seed, str(root / "a"), *size),
        "b": gen.generate(config, seed, str(root / "b"), *size),
        "c": gen.generate(config, seed + 1, str(root / "c"), *size),
        "read_only": gen.generate(
            dict(config, write_other_columns=False), seed,
            str(root / "seven"), *size),
        "cell2": cell2["generator"].generate(
            cell2["config"], seed, str(root / "cell2"), *size)}
    return bench, paths


def _digest(path):
    import hashlib

    with open(path, "rb") as f:
        return hashlib.sha1(f.read()).hexdigest()


def test_generator_writes_the_same_16_column_file_a_seed(generated):
    import pyarrow.parquet as pq

    bench, paths = generated
    config = bench["config"]
    assert _digest(paths["a"]) == _digest(paths["b"]) != _digest(paths["c"])
    pf = pq.ParquetFile(paths["a"])
    md = pf.metadata
    assert [md.row_group(i).num_rows for i in range(md.num_row_groups)] == [
        40_000, 40_000, 20_000]
    kinds = {c["name"]: c["type"]
             for c in config["columns"] + config["other_columns"]}
    arrow = {"int32": "int32", "int64": "int64", "float64": "double",
             "string": "string", "date32": "date32[day]"}
    assert [(f.name, str(f.type)) for f in pf.schema_arrow] == [
        (name, arrow[kinds[name]]) for name in RECORD]
    # the filler draws anew in every row group, at the source's widths,
    # and has no nulls
    t0, t1 = pf.read_row_group(0), pf.read_row_group(1)
    for c in config["other_columns"]:
        assert t0[c["name"]].null_count == 0
        assert not t0[c["name"]].equals(t1[c["name"]]), c["name"]
        if c["type"] == "string":
            assert set(np.char.str_len(
                t0[c["name"]].to_numpy(zero_copy_only=False).astype(str))
            ) == {c["width_bytes"]}


@pytest.mark.parametrize("column,distinct", [
    ("l_quantity", 50), ("l_discount", 11), ("l_tax", 9),
    ("l_shipdate", 2526), ("l_returnflag", 3), ("l_linestatus", 2)])
def test_read_columns_keep_cell_twos_domains_in_every_row_group(
        column, distinct, generated):
    """Every value planted in every row group that can hold the domain:
    the decode programs are keyed by a row group's dictionary sizes, so a
    seed compiles no program of its own."""
    import pyarrow.parquet as pq

    _, paths = generated
    for key in ("a", "c"):
        pf = pq.ParquetFile(paths[key])
        for rg in (0, 1):
            values = pf.read_row_group(rg, columns=[column])[column]
            assert len(values.unique()) == distinct


def test_the_read_columns_are_cell_twos_with_and_without_the_filler(
        generated):
    import pyarrow.parquet as pq

    bench, paths = generated
    q = bench["queries"][0]
    seven = pq.ParquetFile(paths["read_only"])
    assert seven.schema_arrow.names == [
        n for n in RECORD if n in q.READS]
    full = pq.ParquetFile(paths["a"]).read(columns=list(q.READS))
    assert full.equals(seven.read(columns=list(q.READS)))
    # and they are the values cell 2's generator gives the seed
    assert full.equals(pq.ParquetFile(paths["cell2"]).read(
        columns=list(q.READS)))
    assert q.reference(paths["a"]) == q.reference(paths["read_only"]) \
        == q.reference(paths["cell2"])
    assert [r[:2] for r in q.reference(paths["a"])] == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]


def test_float32_control_is_not_correct_for_the_full_width_cell(tmp_path):
    """The control at a size a test run can hold, on the seven read columns
    (the same with or without the filler, see above): the reference in
    float32 row by row fails the float limit alone, the float64 reference
    passes against itself, the blocked control reads lower."""
    bench = loader.load_cell(CELL)
    q = bench["queries"][0]
    path = bench["generator"].generate(
        dict(bench["config"], write_other_columns=False), 2**31 + 5,
        str(tmp_path), 2_000_000, 1 << 20)
    want = q.reference(path)
    names = bench["query_names"]
    c = compare.compare_window([(0, q.reference(path, "float32"))], [want],
                               [q], names)
    assert c["rows_wrong"][0] == 0 and c["exact_wrong"][0] == 0
    assert c["order_wrong"][0] == 0
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
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_in_the_timed_sizes_splits_runs_both_exchanges(
        trace, capsys, splits_as_timed):
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
    # what the cell states of its steady state
    assert result["window"]["compiles"] == 0
    for number in ("rows_wrong", "exact_wrong", "order_wrong",
                   "placement_wrong"):
        assert result["compared"][number] == {"value": 0.0, "limit": 0.0}
    err = result["compared"]["float_rel_err"]
    assert err["value"] <= err["limit"] == 2e-5
    assert "first query:" in out.err and "window:" in out.err
    # every drain (the first query, two warm-ups, the window's queries and
    # the placement check after it) reduces the hash exchange's partitions
    # in one adaptive read and both of the range exchange's
    drains = 4 + result["attempted"]
    assert splits_as_timed == {"hash": drains, "range": 2 * drains}


def _alter_count(rows):
    rows = list(rows)
    rows[0] = rows[0][:-1] + (rows[0][-1] + 1,)
    return rows


def _alter_float(rows):
    rows = list(rows)  # a float sum off by a thousandth of itself
    rows[0] = rows[0][:2] + (rows[0][2] * (1 + 1e-3),) + rows[0][3:]
    return rows


def _drop_row(rows):
    return list(rows)[1:]


def _swap_rows(rows):
    rows = list(rows)
    rows[0], rows[1] = rows[1], rows[0]
    return rows


@pytest.mark.parametrize("fault,number", [
    (_alter_count, "exact_wrong"), (_alter_float, "float_rel_err"),
    (_drop_row, "rows_wrong"), (_swap_rows, "order_wrong")])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        fault, number, monkeypatch, splits_as_timed):
    """One answer of the window altered at the columnar-to-row boundary
    (every later one too), on the plan with both exchanges: the rows of
    a range partition, two of the four each, are altered before
    ``collect()`` strings the partitions together in order."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    real = ColumnarBatch.to_rows
    state = {"calls": 0}

    def broken(self):
        rows = real(self)
        state["calls"] += 1  # two range partitions a collect
        return fault(rows) if state["calls"] > 6 and len(rows) > 1 else rows

    monkeypatch.setattr(ColumnarBatch, "to_rows", broken)
    result = bench_run.execute(lib.rehearse_args(CELL), _devices())
    assert splits_as_timed["hash"] >= 4 and splits_as_timed["range"] >= 8
    assert result["answers_correct"] is False and result["correct"] is False
    c = result["compared"][number]
    assert c["value"] > c["limit"]
    others = {"exact_wrong", "float_rel_err", "rows_wrong",
              "order_wrong"} - {number}
    if number != "rows_wrong":  # a missing row is not held to an order
        for other in others:
            o = result["compared"][other]
            assert o["value"] <= o["limit"], other


# ---------------------------------------------------------------------------
# the readers, on planes built by hand
# ---------------------------------------------------------------------------
def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _planes(counts=True, sort_across=True):
    """A slice of 100 ms, two queries. A query: two ``PARTIAL`` merges (of
    16 and of 13 partials, 10 ms each with 4 ms of ``merge.concat``
    nested), the ``FINAL`` merge of the one exchanged batch, and, where
    the plan sorts across partitions, the range exchange's ``sample`` span
    (3 ms, the ``d2h`` of its pull, 1 ms, nested in it) before its ``map``. ``counts``
    off is the parent's program: the merges carry no ``partials`` and the
    sampling runs under no span."""
    agg, ex = "TpuHashAggregateExec", "TpuShuffleExchangeExec"
    events = [("bench.slice", 0, 100 * MS, {})]

    def c(**kw):
        return kw if counts else {}

    for qid, q0 in ((7, 0), (8, 50)):
        events += [("bench.query", q0 * MS, 50 * MS, {}),
                   _span("TpuSession.query", q0, 49, query=qid)]
        for s0, n in ((q0 + 1, 16), (q0 + 13, 13)):
            events += [
                _span(agg + ".merge", s0, 10, query=qid, mode="partial",
                      **c(partials=n)),
                _span(agg + ".merge.concat", s0 + 1, 4, query=qid)]
        events += [
            _span(ex + ".map", q0 + 25, 4, query=qid, partitions=2,
                  **c(kind="hash")),
            _span(agg + ".merge", q0 + 30, 2, query=qid, mode="final",
                  **c(partials=1))]
        if sort_across:
            if counts:
                events += [
                    _span(ex + ".sample", q0 + 33, 3, query=qid, samples=4,
                          inputs=1, bounds=1),
                    _span(ex + ".d2h", q0 + 34, 1, query=qid, bytes=512)]
            events.append(_span(ex + ".map", q0 + 37, 4, query=qid,
                                partitions=2, **c(kind="range")))
    return [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": events}]}]


def _ctx(planes):
    return {"trace": {"busy_s": 0.06, "window_s": 0.1, "queries": 2,
                      "query_indices": [0, 0], "chips_traced": 1},
            "trace_programs": TP.reduce_programs(planes),
            "peaks": loader.load_peaks("TPU v5 lite"),
            "config": {"rows": 1000}, "queries": [], "counters": {}}


@pytest.mark.parametrize("planes,want", [
    (_planes(), {"range_sample_ms_per_query": 3.0 - 1.0,
                 "merge_partials_per_query": 30.0}),
    # one partition into the sort (cell 2's plan): no bounds are sampled
    (_planes(sort_across=False), {"range_sample_ms_per_query": None,
                                  "merge_partials_per_query": 30.0}),
    # the parent's program: no span around the sampling, no count
    (_planes(counts=False), {"range_sample_ms_per_query": None,
                             "merge_partials_per_query": None}),
], ids=["both_exchanges", "one_partition", "parent"])
def test_readers_on_planes_built_by_hand(planes, want):
    readers = loader.load_metrics()
    ctx = _ctx(planes)
    for name, value in want.items():
        got = readers[name].read(ctx)
        assert got == (pytest.approx(value) if value is not None
                       else None), name


def test_the_new_spans_counts_reach_the_reduction():
    """``samples``, ``inputs`` and ``bounds`` on ``.sample``, ``kind``
    beside ``partitions`` on ``.map``; the sampling's pull is a part of
    its span (``phase("d2h")`` under the exchange), so the span's self
    time is the gather's dispatch and the host's sort."""
    reduced = TP.reduce_programs(_planes())
    rec = reduced["spans"]["TpuShuffleExchangeExec.sample"]
    assert rec["count"] == 2
    assert rec["counts"]["samples"] == 8 and rec["counts"]["bounds"] == 2
    assert rec["total_s"] == pytest.approx(0.006)
    assert rec["self_s"] == pytest.approx(0.004)
    assert reduced["spans"]["TpuShuffleExchangeExec.map"]["values"][
        "kind"] == {"hash": 2, "range": 2}


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_is_silent_where_there_is_nothing_to_read(name):
    reader = loader.load_metrics()[name]
    assert reader.read({"trace": None, "counters": {}}) is None
    no_names = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.slice", 0, 100 * MS, {})]}]}]
    assert reader.read(_ctx(no_names)) is None
