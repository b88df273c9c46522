"""The order the suite is collected in, which is the order pytest-xdist
hands tests to its workers: ``conftest.py`` sorts the items by
``order`` and ``test_collection_order.py`` holds the order to xdist's own
scheduler.

The compile asks (``test_tpu_compile*.py``, helpers in
``tpu_compile_asks.py``) set the run's wall: one program family a file,
minutes of one worker each, against seconds for most other files. Each
family must start at once, on a worker of its own, and stay whole on that
worker, so that its module fixture (the captured programs) is built once.

The files that run the benchmark's harness (``benchmarks/run.py``) share
the checkout's ``benchmarks/.cache``: a cell's data directory there is
deleted and written anew by every run of the cell, so two workers running
one cell at once read each other's half-written or deleted files, and the
run reports wrong answers. Each such file stays whole on one worker.
"""
import collections

#: The compile-ask files, longest first: seconds of one worker in a cold
#: run of the suite under six workers on an 8-core host (``--durations=0``,
#: two runs): sf100's ask 734-822 (marked slow), the smoke query's 433-508,
#: the four-chip ask 316-383, the full-width asks 111-119, the merge asks
#: 83-86, the lineitem asks 30-32. A new compile ask gets a file of its own
#: and its place here.
COMPILE_ASKS = (
    "test_tpu_compile_sf100.py", "test_tpu_compile.py",
    "test_tpu_compile_mesh4.py", "test_tpu_compile_full.py",
    "test_tpu_compile_merge.py", "test_tpu_compile_lineitem_full.py",
)
#: The files that import the benchmark's harness (``import run``), most
#: tests first.
HARNESS_RUNS = (
    "test_lineitem_full.py", "test_store_sales_full.py",
    "test_cached_report_mesh4.py", "test_benchmark_run.py",
    "test_scan_native_plan_share.py", "test_decode_gathers.py",
)


def file_of(nodeid):
    return nodeid.split("::", 1)[0]


def name_of(nodeid):
    return file_of(nodeid).rsplit("/", 1)[-1]


def load_chunk(n_tests, workers, maxschedchunk=None):
    """How many tests each worker's first chunk holds under ``--dist
    load``, by the rule of ``xdist.scheduler.load.LoadScheduling.schedule``:
    a quarter of a worker's share, at most ``--maxschedchunk``, at least 2;
    None where the tests are too few for chunks and go out one by one."""
    if n_tests < 2 * workers:
        return None
    share = n_tests // workers // 4
    return max(min(share, maxschedchunk or n_tests), 2)


def order(nodeids, dist, workers, maxschedchunk=None):
    """``nodeids`` in the order to collect them in, for xdist's ``dist``
    mode and ``workers`` workers.

    The compile-ask files first, longest first, then the other files most
    tests first. Under ``loadfile`` a file is one worker's, so the asks go
    out first, a worker each. Under ``load`` the scheduler sends each
    worker one chunk of consecutive tests and never moves a test it has
    sent: every compile-ask family goes at the head of a chunk of its own,
    every harness file whole into the room a chunk has left (the shortest
    families' first), and the other tests fill the gaps."""
    rank = {name: i for i, name in enumerate(COMPILE_ASKS)}
    tests_in = collections.Counter(file_of(t) for t in nodeids)
    ordered = sorted(nodeids, key=lambda t: (
        rank.get(name_of(t), len(rank)), -tests_in[file_of(t)]))
    chunk = load_chunk(len(ordered), workers, maxschedchunk)
    if dist != "load" or chunk is None:
        return ordered
    whole, rest = {}, []
    for t in ordered:
        name = name_of(t)
        if name in rank or name in HARNESS_RUNS:
            whole.setdefault(name, []).append(t)
        else:
            rest.append(t)
    families = [whole[name] for name in COMPILE_ASKS if name in whole]
    heads = [list(family) for family in families[:workers]]
    heads += [[] for _ in range(workers - len(heads))]
    late = families[workers:]
    for name in HARNESS_RUNS:
        if name not in whole:
            continue
        group = whole[name]
        room = [h for h in reversed(heads) if len(h) + len(group) <= chunk]
        if room:
            room[0] += group
        else:
            late.append(group)
    placed = []
    for head in heads:
        placed += head
        fill = -len(placed) % chunk
        placed += rest[:fill]
        del rest[:fill]
    return placed + sum(late, []) + rest
