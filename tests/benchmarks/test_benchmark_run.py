"""``benchmarks/run.py`` driven in-process on the CPU: each cell's
rehearsal, what it refuses, the planted faults that have to turn ``correct``
false, and that a new configuration, cell, query and per-layer metric are
new files only."""
import json
import os
import re

import pytest

import benchmark_testlib as lib
import loader
import run as bench_run

CELLS = ["store_sales.quantity_report", "lineitem.q1"]


def _devices():
    import jax

    return jax.devices()


@pytest.mark.parametrize("cell", CELLS)
def test_benchmark_json_names_the_files(cell):
    """BENCHMARK.json and the files the harness finds by name agree."""
    spec = lib.load_spec()
    entry = next(w for w in spec["workloads"] if w["name"] == cell)
    bench = loader.load_cell(cell)
    assert bench["cell"]["config"] == entry["config"]
    assert bench["cell"]["traffic"] == entry["traffic"]
    assert bench["config"]["chips"] == entry["chips"] == 1
    cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
    assert cfg["file"] == f"benchmarks/configs/{entry['config']}.json"
    assert sorted(cfg["reduced"]) == sorted(bench["config"]["reduced"])
    readers = loader.load_metrics()
    for m in spec["per_layer"]:
        assert m["name"] in readers and readers[m["name"]].UNIT == m["unit"]
    assert spec["command"] == ["python3", "benchmarks/run.py"]
    assert bench["cell"]["why"] == entry["why"]
    # PERF.md section 2 derives the bounds: its table and BENCHMARK.json
    # state one set
    with open(os.path.join(lib.ROOT, "PERF.md")) as f:
        perf = f.read()
    for m in spec["end_to_end"]:
        row = re.search(r"^\| `%s` \|[^|]*\| ([0-9.]+) \|" % m["name"], perf,
                        re.M)
        assert row and float(row.group(1)) == m["bound"], m["name"]


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_the_cell_and_prints_no_device_metric(
        cell, trace, capsys):
    rc = bench_run.main(["--workload", cell, "--seed", str(2**31 + 7),
                         "--seconds", "0.3", "--trace", str(trace),
                         "--rehearse"])
    assert rc == 0
    out = capsys.readouterr()
    result = json.loads(out.out.strip().splitlines()[-1])
    assert result["correct"] is False and result["rehearsal"] is True
    assert result["metrics"] == {}
    assert "busy_s" not in result["device"]
    assert result["answers_correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert list(result)[-1] == "compared"
    assert result["compared"]["rows_wrong"] == {"value": 0.0, "limit": 0.0}
    err = result["compared"]["float_rel_err"]
    assert err["value"] <= err["limit"] < 1e-3
    # the numbers compared, each beside its limit, end standard error
    tail = out.err.strip().splitlines()[-6:]
    assert tail[-1].startswith("correct: False")
    assert any(line.startswith("compared float_rel_err:") for line in tail)


def test_refuses_a_platform_that_is_not_the_tpu(capsys):
    rc = bench_run.main(["--workload", CELLS[0], "--seconds", "0.1"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""  # no result line
    assert "not 'tpu'" in out.err


def test_unknown_cell_and_unknown_device_kind_are_errors(capsys):
    assert bench_run.main(["--workload", "no.such.cell", "--rehearse"]) != 0
    assert capsys.readouterr().out == ""
    with pytest.raises(loader.BenchmarkError, match="not in benchmarks/peaks"):
        loader.load_peaks("TPU v9 imaginary")
    assert loader.load_peaks("TPU v5 lite")["hbm_GB/s"] == 819
    # off rehearsal the table is asked before anything is measured
    with pytest.raises(loader.BenchmarkError, match="peaks.json"):
        args = lib.rehearse_args(CELLS[0])
        args.rehearse = False
        bench_run.execute(args, _devices())


# -- planted faults: the rest of a run, the timed path broken underneath ----
def _alter_count(rows):
    rows = list(rows)
    rows[0] = rows[0][:-1] + (rows[0][-1] + 1,)
    return rows


def _alter_float(rows):
    # a float sum off by a thousandth of itself: 50 times Q1's limit
    rows = list(rows)
    j = 1 if len(rows[0]) == 4 else 3
    rows[0] = rows[0][:j] + (rows[0][j] * (1 + 1e-3),) + rows[0][j + 1:]
    return rows


def _drop_row(rows):
    return list(rows)[1:]


def _swap_rows(rows):
    rows = list(rows)
    rows[0], rows[1] = rows[1], rows[0]
    return rows


@pytest.mark.parametrize("cell,fault,number", [
    (CELLS[0], _alter_count, "exact_wrong"),
    (CELLS[0], _alter_float, "float_rel_err"),
    (CELLS[0], _drop_row, "rows_wrong"),
    (CELLS[1], _alter_count, "exact_wrong"),
    (CELLS[1], _alter_float, "float_rel_err"),
    (CELLS[1], _swap_rows, "order_wrong"),
])
def test_an_answer_altered_where_it_is_produced_is_not_correct(
        cell, fault, number, monkeypatch):
    """One answer of the window altered at the columnar-to-row boundary
    (every later one too): the comparison has to say so."""
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    real = ColumnarBatch.to_rows
    state = {"calls": 0}

    def broken(self):
        rows = real(self)
        state["calls"] += 1
        # leave the first query and the warm-up alone: the window's answers
        # are what is compared
        return fault(rows) if state["calls"] > 3 and len(rows) > 1 else rows

    monkeypatch.setattr(ColumnarBatch, "to_rows", broken)
    result = bench_run.execute(lib.rehearse_args(cell), _devices())
    assert result["answers_correct"] is False and result["correct"] is False
    c = result["compared"][number]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("when", ["warm-up", "window"])
def test_more_compile_misses_than_the_cell_states_is_an_error(
        when, monkeypatch):
    """Cell 1 states a steady state that compiles nothing: a miss in the
    last warm-up query, or inside the window, ends the run with no result."""
    state = {"calls": 0}
    real = bench_run.Driver.compiles

    def counted(self):
        # run.py reads the counter before and after each warm-up query (6
        # reads for 3 queries), then at the window's two ends
        state["calls"] += 1
        late = state["calls"] >= (6 if when == "warm-up" else 8)
        return real(self) + (1 if late else 0)

    monkeypatch.setattr(bench_run.Driver, "compiles", counted)
    with pytest.raises(loader.BenchmarkError, match="compile miss"):
        bench_run.execute(lib.rehearse_args(CELLS[0]), _devices())


def test_a_query_that_raises_counts_as_failed(monkeypatch):
    from spark_rapids_tpu.columnar.batch import ColumnarBatch

    real = ColumnarBatch.to_rows
    state = {"calls": 0}

    def broken(self):
        state["calls"] += 1
        if state["calls"] > 4:
            raise RuntimeError("planted")
        return real(self)

    monkeypatch.setattr(ColumnarBatch, "to_rows", broken)
    result = bench_run.execute(lib.rehearse_args(CELLS[0], seconds=5.0),
                               _devices())
    assert result["failed"] == bench_run.MAX_FAILURES_IN_A_ROW
    assert result["attempted"] == result["failed"] + 1
    assert result["answers_correct"] is False


# -- a new configuration, cell, query and metric are new files only --------
def test_new_config_cell_query_and_metric_are_new_files_only(tmp_path):
    """The harness's side of the open door (the contract's side, without
    the engine: ``test_benchmark_contract.py``): the grown copy passes
    every rule, and ``run.py`` answers the new cell from it."""
    import benchmark_contract as contract

    root = lib.copy_benchmarks(tmp_path)
    before = lib.file_mtimes(root)
    lib.add_dummy_files(root)
    contract.check_contract(lib.grown_spec(lib.load_spec()), root)
    assert lib.DUMMY_METRIC in loader.load_metrics(root)
    result = bench_run.execute(lib.rehearse_args(lib.DUMMY_CELL, trace=1),
                               _devices(), bench_root=root)
    assert result["answers_correct"] is True and result["attempted"] >= 2
    # and the cells that were there still load from the copy, untouched
    assert loader.load_cell(CELLS[0], root)["config"]["rows"] == 28_800_991
    assert lib.touched_since(before) == []
