"""The readers of the host's CPU and the scan's queue waits on planes built
by hand: ``scan_host_cpu_ms_per_query`` (``cpu_us`` on the scan's file
spans), ``scan_queue_ms_per_query`` (``queued_us`` on the scan's pool
tasks) and ``host_cpu_ms_per_query`` (``process_cpu_us`` on the query),
per query, and nothing from a program whose spans carry no such count."""
import pytest

import benchmark_testlib as lib  # noqa: F401  (puts benchmarks/ on the path)
import loader
import trace_programs as TP

MS = 1e6  # ns
SCAN = "TpuFileSourceScanExec"
NAMES = ("scan_host_cpu_ms_per_query", "scan_queue_ms_per_query",
         "host_cpu_ms_per_query")


def _span(name, start_ms, dur_ms, **stats):
    return (name, start_ms * MS, dur_ms * MS, stats)


def _planes(counted=True):
    """A slice of 100 ms with two queries. Query 7 scans a file: the
    consumer reads it, plans one chunk itself and waits for the pool's two,
    and decodes a column on the host; query 8 is served by the cache.
    ``counted`` False leaves out what the program's newer spans carry."""
    def span(name, start_ms, dur_ms, query, **counts):
        if not counted:
            counts = {}
        return _span(name, start_ms, dur_ms, query=query, **counts)

    main = [
        ("bench.slice", 0, 100 * MS, {}),
        ("bench.query", 0, 50 * MS, {}),
        ("bench.query", 50 * MS, 50 * MS, {}),
        span("TpuSession.query", 0, 48, 7, cpu_us=3_000,
             process_cpu_us=30_000),
        span("TpuSession.plan", 0, 2, 7, cpu_us=1_500),
        span(SCAN + ".decode", 2, 30, 7, cpu_us=2_000),
        span(SCAN + ".read_file", 3, 2, 7, cpu_us=1_000),
        span(SCAN + ".plan_wait", 5, 10, 7, cpu_us=100),
        span(SCAN + ".page_plan", 15, 4, 7, cpu_us=2_000),
        span(SCAN + ".host_decode", 19, 9, 7, cpu_us=4_000),
        span(SCAN + ".upload", 20, 4, 7, cpu_us=500, bytes=1000),
        span("TpuSession.query", 50, 49, 8, cpu_us=9_000,
             process_cpu_us=50_000),
        span(SCAN + ".cache_lookup", 55, 1, 8, cpu_us=300, hits=4,
             lookups=4),
    ]
    pool = [
        span(SCAN + ".page_plan", 5, 4, 7, cpu_us=3_000, queued_us=500),
        span(SCAN + ".page_plan", 9, 5, 7, cpu_us=1_000, queued_us=1_500),
    ]
    return [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": main},
        {"name": "srtpu-pqdec_0", "events": pool}]}]


def _ctx(planes):
    return {"trace": {"busy_s": 0.01, "window_s": 0.1, "queries": 2},
            "trace_programs": TP.reduce_programs(planes), "counters": {}}


#: two queries: read_file 1 + page_plan 2 + 3 + 1 + host_decode 4 ms of
#: CPU; 0.5 + 1.5 ms queued; 30 + 50 ms of the process
WANT = {"scan_host_cpu_ms_per_query": 5.5,
        "scan_queue_ms_per_query": 1.0,
        "host_cpu_ms_per_query": 40.0}


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_its_count_per_query(name):
    reader = loader.load_metrics()[name]
    assert reader.UNIT == "ms"
    assert reader.read(_ctx(_planes())) == pytest.approx(WANT[name])
    # no trace, no reduction: nothing, and nothing raised
    assert reader.read({"trace": None, "counters": {}}) is None
    ctx = _ctx(_planes())
    ctx["trace_programs"] = None
    assert reader.read(ctx) is None


@pytest.mark.parametrize("name", NAMES)
def test_metric_reads_nothing_where_the_spans_carry_no_count(name):
    """The parent's program: the same spans, none with the count."""
    assert loader.load_metrics()[name].read(_ctx(_planes(False))) is None


def test_the_scans_cpu_is_within_its_self_time():
    readers = loader.load_metrics()
    ctx = _ctx(_planes())
    wall = readers["scan_host_ms_per_query"].read(ctx)
    # read_file 2, page_plan 4 + 4 + 5, host_decode 9 less its upload 4
    assert wall == pytest.approx(10.0)
    assert readers["scan_host_cpu_ms_per_query"].read(ctx) <= wall


def test_a_slice_served_by_the_cache_reads_no_scan_count():
    """Query 8's half alone: no file span and no pool task, so neither scan
    reader finds a count, while the query's process CPU is there."""
    planes = _planes()
    host = planes[0]
    host["lines"][0]["events"] = [
        ev for ev in host["lines"][0]["events"]
        if ev[0].startswith("bench.") or ev[3].get("query") == 8]
    host["lines"][1]["events"] = []
    ctx = _ctx(planes)
    readers = loader.load_metrics()
    assert readers["scan_host_cpu_ms_per_query"].read(ctx) is None
    assert readers["scan_queue_ms_per_query"].read(ctx) is None
    assert readers["host_cpu_ms_per_query"].read(ctx) == pytest.approx(25.0)
