"""Shared by the benchmark's tests: put ``benchmarks/`` on the path the way
``run.py`` does, load the contract, and grow a copy of the benchmark by one
configuration, cell, query and per-layer metric the way a later PR would:
new files and appended entries only. No JAX topology call, no engine import
at import time."""
import copy
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

DUMMY_CONFIG = "dummy_table"
DUMMY_CELL = "dummy.sum"
DUMMY_METRIC = "dummy_metric"


def rehearse_args(workload, seed=11, seconds=0.2, trace=0):
    import argparse

    return argparse.Namespace(workload=workload, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)


def load_spec(root=ROOT):
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def copy_benchmarks(tmp_path):
    """A copy of ``benchmarks/`` without what runs leave behind; the path
    is a benchmark root for ``loader`` and ``run.execute``."""
    root = str(tmp_path / "benchmarks")
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        ".cache", ".scratch", "__pycache__"))
    return root


def file_mtimes(root):
    return {os.path.join(d, f): os.path.getmtime(os.path.join(d, f))
            for d, _, fs in os.walk(root) for f in fs}


def touched_since(before):
    """The files of ``file_mtimes``' answer that were written since."""
    return [p for p, t in before.items() if os.path.getmtime(p) != t]


def add_dummy_files(root):
    """What a PR that brings a configuration, a query, a cell and a
    per-layer metric adds under ``benchmarks/``: five new files."""
    with open(os.path.join(root, "configs", DUMMY_CONFIG + ".json"),
              "w") as f:
        json.dump({
            "name": DUMMY_CONFIG, "source": "a test", "chips": 1,
            "rows": 4096, "row_group_rows": 1024,
            "columns": [{"name": "k", "type": "int32", "width_bytes": 4},
                        {"name": "v", "type": "int32", "width_bytes": 4}],
            "conf": {}, "reduced": [], "assumed": [],
            "rehearse": {"rows": 4096, "row_group_rows": 1024}}, f)
    with open(os.path.join(root, "configs", DUMMY_CONFIG + ".py"), "w") as f:
        f.write(
            "import numpy as np\n"
            "def generate(config, seed, out_dir, rows, row_group):\n"
            "    import pyarrow as pa\n"
            "    from datagen import write_parquet\n"
            "    rng = np.random.default_rng(seed)\n"
            "    t = pa.table({'k': pa.array(rng.integers(0, 5, rows,"
            " dtype=np.int32)), 'v': pa.array(rng.integers(0, 9, rows,"
            " dtype=np.int32))})\n"
            "    return write_parquet(t, out_dir, 'dummy.parquet',"
            " row_group)\n")
    with open(os.path.join(root, "queries", "dummy_sum.py"), "w") as f:
        f.write(
            "COLUMNS = ('k', 's')\nKEYS = (0,)\nEXACT = (1,)\nFLOAT = ()\n"
            "ORDERED = False\nFLOAT_LIMIT = 0.0\n"
            "def frame(sess, data_dir):\n"
            "    from spark_rapids_tpu.expr import aggregates as A\n"
            "    from spark_rapids_tpu.expr.expressions import col\n"
            "    return (sess.read.parquet(data_dir).group_by('k')"
            ".agg(A.agg(A.Sum(col('v')), 's')))\n"
            "def reference(path, float_dtype='float64'):\n"
            "    import pandas as pd\n"
            "    g = pd.read_parquet(path).groupby('k').v.sum()\n"
            "    return [(int(k), int(s)) for k, s in g.items()]\n"
            "def needed_bytes(config):\n"
            "    return int(config['rows']) * 8\n"
            "def rows_scanned(config):\n"
            "    return int(config['rows'])\n")
    with open(os.path.join(root, "workloads", DUMMY_CELL + ".json"),
              "w") as f:
        json.dump({"name": DUMMY_CELL, "config": DUMMY_CONFIG,
                   "traffic": "sum", "queries": ["dummy_sum"],
                   "loop": "closed", "clients": 1,
                   "compile_misses_per_query_at_most": 0,
                   "why": "a test"}, f)
    with open(os.path.join(root, "metrics", DUMMY_METRIC + ".py"), "w") as f:
        f.write(f"NAME = '{DUMMY_METRIC}'\nUNIT = 'count'\n"
                "def read(ctx):\n"
                "    return ctx['counters'].get('window_queries')\n")


def grown_spec(spec):
    """``spec`` as that PR leaves it: its configuration, its one-chip cell
    and its metric (which lists its own cell) appended after everything
    that is there, and its cell's name added to every list that names
    one-chip cells only."""
    spec = copy.deepcopy(spec)
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}
    for metric in spec["per_layer"]:
        if {chips[c] for c in metric.get("workloads", ())} == {1}:
            metric["workloads"].append(DUMMY_CELL)
    spec["configs"].append({
        "name": DUMMY_CONFIG, "source": "a test", "reduced": [],
        "file": f"benchmarks/configs/{DUMMY_CONFIG}.json", "why": "a test"})
    spec["workloads"].append({
        "name": DUMMY_CELL, "config": DUMMY_CONFIG, "traffic": "sum",
        "chips": 1, "why": "a test"})
    spec["per_layer"].append({
        "name": DUMMY_METRIC, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry and program cache",
        "moves": "rows_per_s", "workloads": [DUMMY_CELL]})
    return spec
