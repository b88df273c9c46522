"""The suite's collection order (``placement.order``) held to the rules of
pytest-xdist's own schedulers: the ordered list of a suite shaped like
this one is handed to ``LoadScheduling`` and ``LoadFileScheduling`` with
stand-in workers, and what each worker is sent first is read back. A
change of xdist's chunk rule, or a compile-ask or harness file missing
from ``placement``'s tuples, fails here."""
import glob
import os
import re
import types

import pytest
from xdist.scheduler.load import LoadScheduling
from xdist.scheduler.loadfile import LoadFileScheduling

from placement import COMPILE_ASKS, HARNESS_RUNS, load_chunk, name_of, order

HERE = os.path.dirname(os.path.abspath(__file__))
#: tests a compile-ask file holds
ASK_TESTS = {"test_tpu_compile_sf100.py": 1, "test_tpu_compile.py": 14,
             "test_tpu_compile_mesh4.py": 1, "test_tpu_compile_full.py": 6,
             "test_tpu_compile_lineitem_full.py": 6,
             "test_tpu_compile_merge.py": 2}
#: tests a file that runs the benchmark's harness holds
HARNESS_TESTS = {"test_lineitem_full.py": 35, "test_store_sales_full.py": 35,
                 "test_cached_report_mesh4.py": 28,
                 "test_benchmark_run.py": 18,
                 "test_scan_native_plan_share.py": 5,
                 "test_decode_gathers.py": 4}


def suite(files=66, tests=1761, drop=()):
    """Node ids in collection order (by path) of a suite of ``files`` files
    and ``tests`` tests, the compile asks and the harness files among them,
    less the files ``drop`` names (deselected)."""
    others = files - len(ASK_TESTS) - len(HARNESS_TESTS)
    sizes = [1 + i * 37 % 57 for i in range(others)]
    sizes[0] += (tests - sum(ASK_TESTS.values())
                 - sum(HARNESS_TESTS.values()) - sum(sizes))
    paths = {f"tests/{name}": n for name, n in ASK_TESTS.items()}
    paths.update((f"tests/benchmarks/{name}", n)
                 for name, n in HARNESS_TESTS.items())
    paths.update((f"tests/test_f{i:02d}.py", n) for i, n in enumerate(sizes))
    return [f"{path}::test_{k}" for path in sorted(paths)
            if name_of(path) not in drop for k in range(paths[path])]


class Worker:
    """What a scheduler asks of a worker."""

    def __init__(self, i):
        self.gateway = types.SimpleNamespace(id=f"gw{i}")
        self.sent = []
        self.shutting_down = False

    def send_runtest_some(self, indices):
        self.sent.append(list(indices))

    def shutdown(self):
        self.shutting_down = True


def first_sends(scheduler_type, collection, workers):
    """The first batch of node ids each worker is sent."""
    config = types.SimpleNamespace(
        getvalue={"tx": [f"{workers}*popen"]}.get,
        getoption={"maxschedchunk": None}.get,
        option=types.SimpleNamespace(loadscopereorder=False))
    sched = scheduler_type(config)
    nodes = [Worker(i) for i in range(workers)]
    for node in nodes:
        sched.add_node(node)
    for node in nodes:
        sched.add_node_collection(node, collection)
    sched.schedule()
    return [[collection[i] for i in node.sent[0]] for node in nodes]


def family(nodeid):
    return name_of(nodeid) if name_of(nodeid) in COMPILE_ASKS else None


def test_every_compile_ask_file_has_a_place():
    files = {os.path.basename(p)
             for p in glob.glob(os.path.join(HERE, "test_tpu_compile*.py"))}
    assert files == set(COMPILE_ASKS) == set(ASK_TESTS)


def test_every_file_that_runs_the_harness_has_a_place():
    imports_run = re.compile(r"^(import run\b|from run import)", re.M)
    files = set()
    for path in glob.glob(os.path.join(HERE, "**", "test_*.py"),
                          recursive=True):
        with open(path) as f:
            if imports_run.search(f.read()):
                files.add(os.path.basename(path))
    assert files == set(HARNESS_RUNS) == set(HARNESS_TESTS)


@pytest.mark.parametrize("workers,drop", [
    (6, ()), (8, ()), (6, ("test_tpu_compile_sf100.py",))])
def test_load_gives_each_compile_ask_family_a_worker_of_its_own(
        workers, drop):
    collection = suite(drop=drop)
    ordered = order(collection, "load", workers)
    assert sorted(ordered) == sorted(collection)
    chunk = load_chunk(len(collection), workers)
    assert chunk == max(len(collection) // workers // 4, 2)
    sends = first_sends(LoadScheduling, ordered, workers)
    assert [len(s) for s in sends] == [chunk] * workers
    held = [{family(t) for t in s} - {None} for s in sends]
    assert all(len(h) <= 1 for h in held), held
    # every family whole, at the head of its worker's first chunk, the
    # longest first
    heads = []
    for s in sends:
        fams = [family(t) for t in s]
        if fams[0] is None:
            assert set(fams) == {None}
            continue
        n = ASK_TESTS[fams[0]]
        assert fams[:n] == [fams[0]] * n and not any(fams[n:])
        heads.append(fams[0])
    assert heads == [name for name in COMPILE_ASKS if name not in drop]
    # every harness file whole in one worker's first chunk
    for name, n in HARNESS_TESTS.items():
        holders = [s for s in sends if any(name_of(t) == name for t in s)]
        assert len(holders) == 1, name
        assert sum(name_of(t) == name for t in holders[0]) == n, name


def test_loadfile_hands_the_compile_asks_out_first():
    collection = suite()
    ordered = order(collection, "loadfile", 6)
    assert [family(t) for t in ordered[:30]] == [
        name for name in COMPILE_ASKS for _ in range(ASK_TESTS[name])]
    firsts = [{name_of(t) for t in s}
              for s in first_sends(LoadFileScheduling, ordered, 6)]
    assert firsts == [{name} for name in COMPILE_ASKS]


def test_without_load_the_order_is_the_same_as_under_loadfile():
    collection = suite()
    assert order(collection, "no", 1) == order(collection, "loadfile", 6)
