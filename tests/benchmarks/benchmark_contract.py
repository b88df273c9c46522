"""What every ``BENCHMARK.json`` of this repo must satisfy, read from the
contract itself: one function a rule, each taking the parsed spec and a
benchmark root (``benchmarks/`` or a grown copy of it), so that appending a
configuration, a cell, a per-layer metric or a name to a metric's
``workloads`` list is data and meets no test that has to change. A rule
that does not hold raises ``ContractError`` and says which.

No rule states what the contract contains today but ``check_mesh4_cell``:
PR 30's cell is present with the values it was accepted with, wherever it
stands."""
import re

import benchmark_testlib  # noqa: F401  (puts benchmarks/ on the path)
import loader

#: a name of the contract: at most 64 of these, neither ``.`` nor ``-`` first
PLAIN_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
MAX_CELLS = 24
#: PR 30's cell, as accepted
MESH4 = {"cell": "store_sales_sf100.cached_report.mesh4",
         "config": "tpcds_sf100_store_sales_mesh4",
         "traffic": "cached_report", "chips": 4,
         "queries": ["store_sales_cached_quantity_report"],
         "reduced": ["columns"], "compile_misses_per_query_at_most": 0}


class ContractError(AssertionError):
    """``BENCHMARK.json`` and the files it names break a rule."""


def hold(ok, message):
    if not ok:
        raise ContractError(message)


def entry_of(entries, name, kind):
    found = [e for e in entries if e["name"] == name]
    hold(len(found) == 1,
         f"{len(found)} {kind} entries are named {name!r}, not one")
    return found[0]


def _plain(kind, name):
    hold(isinstance(name, str) and PLAIN_NAME.match(name),
         f"{kind} {name!r} is not a plain name of at most 64 characters")


def _one_line(kind, text):
    hold(isinstance(text, str) and 1 <= len(text) <= 200
         and "\n" not in text and "\t" not in text,
         f"{kind} is not one line of 1 to 200 characters: {text!r}")


def check_cell(spec, name, root):
    """The entry, the workload file and the configuration's file agree,
    and the workload file states its steady state and its generator."""
    entry = entry_of(spec["workloads"], name, "workload")
    for key in ("name", "config", "traffic"):
        _plain(f"cell {name}: {key}", entry[key])
    _one_line(f"cell {name}: why", entry["why"])
    hold(entry["chips"] in (1, 4), f"cell {name}: chips {entry['chips']!r}")
    entry_of(spec["configs"], entry["config"], "configuration")
    pairs = [(w["config"], w["traffic"]) for w in spec["workloads"]]
    hold(pairs.count((entry["config"], entry["traffic"])) == 1,
         f"cell {name}: its configuration and traffic appear twice")
    bench = loader.load_cell(name, root)  # finds every file, or raises
    cell = bench["cell"]
    for key in ("name", "config", "traffic", "why"):
        hold(cell[key] == entry[key],
             f"cell {name}: {key} is {entry[key]!r} in BENCHMARK.json and "
             f"{cell[key]!r} in workloads/{name}.json")
    hold(bench["config"]["chips"] == entry["chips"],
         f"cell {name}: chips {entry['chips']} in BENCHMARK.json, "
         f"{bench['config']['chips']} in configs/{entry['config']}.json")
    misses = cell.get("compile_misses_per_query_at_most")
    hold(isinstance(misses, int) and misses >= 0,
         f"cell {name}: workloads/{name}.json does not state "
         "compile_misses_per_query_at_most")
    hold(cell["loop"] == "closed" and cell["clients"] == 1,
         f"cell {name}: not one closed-loop client")


def check_config(spec, name, root):
    """The entry and ``configs/<name>.json`` state one source and one list
    of cuts, the generator is beside it, and some cell runs it."""
    entry = entry_of(spec["configs"], name, "configuration")
    _plain("configuration", name)
    hold(entry["file"] == f"benchmarks/configs/{name}.json",
         f"configuration {name}: file is {entry['file']!r}")
    data = loader.load_json("config", "configs", name, root)
    hold(hasattr(loader.load_module("config", "configs", name, root),
                 "generate"), f"configs/{name}.py has no generate()")
    for key in ("name", "source", "reduced"):
        hold(data[key] == entry[key],
             f"configuration {name}: {key} is {entry[key]!r} in "
             f"BENCHMARK.json and {data[key]!r} in its file")
    _one_line(f"configuration {name}: source", entry["source"])
    _one_line(f"configuration {name}: why", entry["why"])
    hold(len(entry["reduced"]) <= 16, f"configuration {name}: reduced")
    for key in entry["reduced"]:
        _plain(f"configuration {name}: reduced key", key)
    hold(any(w["config"] == name for w in spec["workloads"]),
         f"configuration {name}: no cell uses it")


def check_chips_rule(spec):
    """1 to 24 cells, and of them at most half, rounded down, on four
    chips; one always may."""
    cells = len(spec["workloads"])
    hold(1 <= cells <= MAX_CELLS, f"{cells} cells, not 1 to {MAX_CELLS}")
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    hold(four <= max(1, cells // 2),
         f"{four} four-chip cells of {cells}: at most "
         f"{max(1, cells // 2)} may ask for four chips")


def check_metric(spec, name, root, readers=None):
    """A per-layer metric has a reader of its name and unit that returns
    nothing where there is nothing to read, moves an end-to-end metric,
    and lists (where it lists any) cells that are there, that report that
    metric, and in which its layer has something to read."""
    entry = entry_of(spec["per_layer"], name, "per-layer metric")
    _plain("metric", name)
    readers = loader.load_metrics(root) if readers is None else readers
    hold(name in readers, f"metric {name}: no metrics/*.py has that NAME")
    reader = readers[name]
    hold(reader.UNIT == entry["unit"],
         f"metric {name}: unit {entry['unit']!r} in BENCHMARK.json, "
         f"{reader.UNIT!r} in its reader")
    hold(entry["better"] in ("lower", "higher"), f"metric {name}: better")
    moved = [m for m in spec["end_to_end"] if m["name"] == entry["moves"]]
    hold(len(moved) == 1,
         f"metric {name}: moves {entry['moves']!r}, no end-to-end metric")
    got = reader.read({"trace": None, "counters": {}})
    hold(got is None, f"metric {name}: its reader returns {got!r}, not "
         "None, where there is nothing to read")
    if "workloads" not in entry:
        return
    listed = entry["workloads"]
    hold(listed, f"metric {name}: an empty workloads list")
    cells = {w["name"]: w for w in spec["workloads"]}
    for cell in listed:
        hold(cell in cells, f"metric {name}: lists {cell!r}, which is no "
             "cell of BENCHMARK.json")
        hold(cell in moved[0].get("workloads", cells),
             f"metric {name}: {cell} does not report {entry['moves']}")
        if entry["layer"] == "mesh":
            hold(cells[cell]["chips"] == 4, f"metric {name}: of layer mesh, "
                 f"lists the one-chip cell {cell}")
        if entry["layer"] == "scan":
            config = loader.load_json("config", "configs",
                                      cells[cell]["config"], root)
            hold("residency" not in config.get("guarantees", {}),
                 f"metric {name}: of layer scan, lists {cell}, whose "
                 "window reads no file (guarantees.residency)")


def check_names(spec, readers):
    """No two entries of a kind share a name, and every reader under
    ``metrics/`` is listed: ``run.py`` reports each one it finds."""
    for key in ("configs", "workloads"):
        names = [e["name"] for e in spec[key]]
        hold(len(set(names)) == len(names), f"{key}: a name twice")
    metrics = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    hold(len(set(metrics)) == len(metrics), "two metrics share a name")
    unlisted = sorted(set(readers) - {m["name"] for m in spec["per_layer"]})
    hold(not unlisted, f"readers that BENCHMARK.json does not list: "
         f"{', '.join(unlisted)}")


def check_mesh4_cell(spec, root):
    """PR 30's cell and configuration are present, wherever they stand,
    with what they were accepted with."""
    entry = entry_of(spec["workloads"], MESH4["cell"], "workload")
    for key in ("config", "traffic", "chips"):
        hold(entry[key] == MESH4[key], f"{MESH4['cell']}: {key} moved")
    bench = loader.load_cell(MESH4["cell"], root)
    hold(bench["query_names"] == MESH4["queries"], "its queries moved")
    hold(bench["cell"]["compile_misses_per_query_at_most"]
         == MESH4["compile_misses_per_query_at_most"],
         f"{MESH4['cell']} states another steady state")
    config = entry_of(spec["configs"], MESH4["config"], "configuration")
    hold(config["reduced"] == MESH4["reduced"], "its cuts moved")


def check_contract(spec, root):
    """Every rule, over every entry of ``spec``."""
    readers = loader.load_metrics(root)
    check_names(spec, readers)
    check_chips_rule(spec)
    for config in spec["configs"]:
        check_config(spec, config["name"], root)
    for cell in spec["workloads"]:
        check_cell(spec, cell["name"], root)
    for metric in spec["per_layer"]:
        check_metric(spec, metric["name"], root, readers)
    check_mesh4_cell(spec, root)
