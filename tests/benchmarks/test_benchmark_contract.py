"""``BENCHMARK.json`` held to the files and to the contract's rules, by cases
generated from ``BENCHMARK.json`` itself: one a cell, a configuration and a
per-layer metric, so that what a later PR lists is tested by being listed
and no test here has to change. Then the proof that the door is open: the
contract grown by a configuration, a cell after the four-chip one and a
metric with a ``workloads`` list of its own passes every rule; the chips
rule holds both ways; and each rule can fail. No engine import, no
compile."""
import pytest

import benchmark_contract as contract
import benchmark_testlib as lib
import loader

SPEC = lib.load_spec()


@pytest.fixture(scope="module")
def readers():
    return loader.load_metrics()


@pytest.mark.parametrize("cell", [w["name"] for w in SPEC["workloads"]])
def test_cell_of_the_contract(cell):
    contract.check_cell(SPEC, cell, lib.BENCH)


@pytest.mark.parametrize("config", [c["name"] for c in SPEC["configs"]])
def test_configuration_of_the_contract(config):
    contract.check_config(SPEC, config, lib.BENCH)


@pytest.mark.parametrize("metric", [m["name"] for m in SPEC["per_layer"]])
def test_per_layer_metric_of_the_contract(metric, readers):
    contract.check_metric(SPEC, metric, lib.BENCH, readers)


def test_the_contract_as_a_whole(readers):
    contract.check_names(SPEC, readers)
    contract.check_chips_rule(SPEC)
    assert SPEC["command"] == ["python3", "benchmarks/run.py"]
    assert SPEC["paths"] == ["benchmarks", "tests/benchmarks"]


# -- the door is open: a grown contract passes ------------------------------
def test_a_grown_contract_passes_every_rule_and_edits_no_file(tmp_path):
    root = lib.copy_benchmarks(tmp_path)
    before = lib.file_mtimes(root)
    lib.add_dummy_files(root)
    grown = lib.grown_spec(SPEC)
    # appended after everything that was there, the four-chip cell too
    names = [w["name"] for w in grown["workloads"]]
    assert names.index(lib.DUMMY_CELL) > names.index(contract.MESH4["cell"])
    assert grown["configs"][:len(SPEC["configs"])] == SPEC["configs"]
    metric = contract.entry_of(grown["per_layer"], lib.DUMMY_METRIC, "metric")
    assert metric["workloads"] == [lib.DUMMY_CELL]
    # its cell joined, at their end, the lists that name one-chip cells only
    joined = [(m, was) for m, was in zip(grown["per_layer"],
                                         SPEC["per_layer"]) if m != was]
    assert joined
    for m, was in joined:
        assert m == dict(was, workloads=was["workloads"] + [lib.DUMMY_CELL])
        assert contract.MESH4["cell"] not in was["workloads"]
    contract.check_contract(grown, root)
    # the files that were there: none written; five are new
    assert lib.touched_since(before) == []
    assert len(lib.file_mtimes(root)) == len(before) + 5


@pytest.mark.parametrize("chips,allowed", [
    ([1, 1, 4], True),            # the accepted benchmark
    ([1, 1, 4, 1], True),         # a one-chip cell after the four-chip one
    ([1, 1, 4, 1, 4], True),      # 2 of 5
    ([1, 1, 4, 4], True),         # 2 of 4: the half
    ([1, 1, 4, 4, 4], False),     # 3 of 5: over it
    ([4], True),                  # one always may
    ([4, 4], False),
    ([1] * 12 + [4] * 12, True),
    ([1] * 13 + [4] * 12, False),  # 25 cells
])
def test_four_chip_cells_are_at_most_half_of_the_cells(chips, allowed):
    spec = {"workloads": [{"name": f"cell{i}", "chips": c}
                          for i, c in enumerate(chips)]}
    if allowed:
        contract.check_chips_rule(spec)
    else:
        with pytest.raises(contract.ContractError, match=r"cells"):
            contract.check_chips_rule(spec)


@pytest.fixture(scope="module")
def grown_root(tmp_path_factory):
    root = lib.copy_benchmarks(tmp_path_factory.mktemp("grown"))
    lib.add_dummy_files(root)
    return root


def _entry(spec, key, name):
    return contract.entry_of(spec[key], name, key)


def _chips_disagree(spec):
    _entry(spec, "workloads", lib.DUMMY_CELL)["chips"] = 4
    return "cell dummy.sum: chips 4 in BENCHMARK.json, 1 in configs/"


def _lists_no_cell(spec):
    _entry(spec, "per_layer", lib.DUMMY_METRIC)["workloads"].append("gone")
    return "metric dummy_metric: lists 'gone', which is no cell"


def _mesh_metric_lists_a_one_chip_cell(spec):
    _entry(spec, "per_layer", "exchange_roofline")["workloads"].append(
        lib.DUMMY_CELL)
    return "exchange_roofline: of layer mesh, lists the one-chip cell dummy"


def _scan_metric_lists_a_resident_cell(spec):
    _entry(spec, "per_layer", "scan_cache_hit_share")["workloads"].append(
        contract.MESH4["cell"])
    return "scan_cache_hit_share: of layer scan, lists store_sales_sf100"


def _why_differs_from_the_file(spec):
    _entry(spec, "workloads", lib.DUMMY_CELL)["why"] = "another"
    return "cell dummy.sum: why is 'another' in BENCHMARK.json and 'a test'"


def _reader_that_is_not_listed(spec):
    spec["per_layer"].remove(_entry(spec, "per_layer", lib.DUMMY_METRIC))
    return "readers that BENCHMARK.json does not list: dummy_metric"


def _configuration_no_cell_uses(spec):
    _entry(spec, "workloads", lib.DUMMY_CELL)["config"] = next(
        c["name"] for c in spec["configs"] if c["name"] != lib.DUMMY_CONFIG)
    return "configuration dummy_table: no cell uses it"


@pytest.mark.parametrize("break_it", [
    _chips_disagree, _lists_no_cell, _mesh_metric_lists_a_one_chip_cell,
    _scan_metric_lists_a_resident_cell, _why_differs_from_the_file,
    _reader_that_is_not_listed, _configuration_no_cell_uses],
    ids=lambda f: f.__name__.strip("_"))
def test_a_broken_contract_fails_the_rule_that_names_its_fault(
        break_it, grown_root):
    spec = lib.grown_spec(SPEC)
    contract.check_contract(spec, grown_root)
    says = break_it(spec)
    with pytest.raises(contract.ContractError, match=says):
        contract.check_contract(spec, grown_root)


def test_a_reader_that_returns_zero_for_nothing_is_refused(tmp_path):
    root = lib.copy_benchmarks(tmp_path)
    lib.add_dummy_files(root)
    with open(f"{root}/metrics/{lib.DUMMY_METRIC}.py", "w") as f:
        f.write(f"NAME = '{lib.DUMMY_METRIC}'\nUNIT = 'count'\n"
                "def read(ctx):\n"
                "    return ctx['counters'].get('window_queries', 0)\n")
    with pytest.raises(contract.ContractError, match="returns 0, not None"):
        contract.check_metric(lib.grown_spec(SPEC), lib.DUMMY_METRIC, root)
