"""Find a cell's files by name. Nothing here knows a cell, a configuration,
a query or a metric: each is a file of its own under ``benchmarks/``, so a
later PR adds one by adding files and edits none.

    workloads/<cell>.json     the traffic mix: config, queries, loop, clients
    configs/<config>.json     the deployment: schema, rows, conf, guarantees
    configs/<config>.py       its seeded generator: generate(config, seed, ...)
    queries/<query>.py        frame(sess, dir), reference(path), needed_bytes
    metrics/<metric>.py       NAME, UNIT, read(ctx) -> number or None
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from types import ModuleType
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


class BenchmarkError(Exception):
    """The benchmark cannot run as asked; run.py exits non-zero and prints
    no result line."""


def _check_name(kind: str, name: str) -> str:
    if not _NAME.match(name or ""):
        raise BenchmarkError(f"{kind} name {name!r} is not a plain name")
    return name


def load_json(kind: str, directory: str, name: str, root: str = HERE) -> dict:
    path = os.path.join(root, directory, _check_name(kind, name) + ".json")
    if not os.path.isfile(path):
        have = sorted(f[:-5] for f in os.listdir(os.path.join(root, directory))
                      if f.endswith(".json"))
        raise BenchmarkError(
            f"no {kind} {name!r} ({os.path.relpath(path, root)} is not "
            f"there); there are: {', '.join(have)}")
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, directory: str, name: str,
                root: str = HERE) -> ModuleType:
    path = os.path.join(root, directory, _check_name(kind, name) + ".py")
    if not os.path.isfile(path):
        raise BenchmarkError(
            f"no {kind} {name!r}: {os.path.relpath(path, root)} is not there")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks_{directory}_{name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(name: str, root: str = HERE) -> dict:
    """The cell's traffic mix with its configuration, the configuration's
    generator and the query modules it names."""
    cell = load_json("workload", "workloads", name, root)
    config = load_json("config", "configs", cell["config"], root)
    generator = load_module("config", "configs", cell["config"], root)
    queries = [load_module("query", "queries", q, root)
               for q in cell["queries"]]
    if not queries:
        raise BenchmarkError(f"workload {name!r} names no query")
    if cell.get("loop") != "closed" or int(cell.get("clients", 0)) != 1:
        raise BenchmarkError(
            f"workload {name!r}: the generator drives one closed-loop "
            f"client; got loop={cell.get('loop')!r} "
            f"clients={cell.get('clients')!r}")
    return {"name": name, "cell": cell, "config": config,
            "generator": generator, "queries": queries,
            "query_names": list(cell["queries"])}


def load_metrics(root: str = HERE) -> Dict[str, ModuleType]:
    """Every per-layer metric reader under ``metrics/``, by its NAME."""
    out: Dict[str, ModuleType] = {}
    directory = os.path.join(root, "metrics")
    for f in sorted(os.listdir(directory)):
        if not f.endswith(".py") or f.startswith("_"):
            continue
        m = load_module("metric", "metrics", f[:-3], root)
        out[_check_name("metric", m.NAME)] = m
    return out


def load_peaks(device_kind: str, root: str = HERE) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    with open(os.path.join(root, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise BenchmarkError(
            f"device kind {device_kind!r} is not in benchmarks/peaks.json "
            f"(known: {', '.join(sorted(table))}); add its published peaks "
            "with their source")
    return table[device_kind]

