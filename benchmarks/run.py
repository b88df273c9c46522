#!/usr/bin/env python3
"""The benchmark: one cell through ``TpuSession`` DataFrame -> ``collect()``.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process; it touches JAX once and starts no child that does (the ``g++``
build of ``native/libsrtpu.so`` is the only child). A platform other than
``tpu`` is an error unless ``--rehearse`` (tiny rows, CPU, ``correct: false``
and never a device metric). A run: build native; generate the cell's table
from ``--seed``; open one ``TpuSession`` with the configuration's conf; the
first query (timed: the compile cache is read or filled there) and the
warm-up queries; then the window: one closed-loop client, the cell's queries
in round-robin, each ``frame(...).collect()`` to host rows, until
``--seconds`` have passed. After the window every collected answer is held to
the plain reference. The last line of standard output is the one JSON object
of the contract; README.md says what is in it.

The harness finds the cell, its configuration, its queries and the per-layer
metrics by name (loader.py) and is never edited to add one.
"""
from __future__ import annotations

import time

_T_START = time.perf_counter()  # set-up counts from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, HERE):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import compare  # noqa: E402
import loader  # noqa: E402
import stats  # noqa: E402
from loader import BenchmarkError  # noqa: E402

#: a window stops early after this many queries in a row raised
MAX_FAILURES_IN_A_ROW = 3
#: queries before the window, the first (timed apart) among them
WARMUP_QUERIES = 3
#: queries of the window that a ``--trace 1`` run wraps in the profiler
TRACED_QUERIES = 2
TRACE_CONF = "spark.rapids.tpu.sql.trace.enabled"


def say(msg: str) -> None:
    """Progress goes to standard error; standard output carries the one
    result line."""
    sys.stderr.write(msg + "\n")
    sys.stderr.flush()


# ---------------------------------------------------------------------------
# set-up
# ---------------------------------------------------------------------------
def find_devices(chips: int, rehearse: bool):
    """jax.devices() before any engine import; refuse a platform that is
    not the TPU and a host with fewer chips than the cell asks for."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if rehearse:
        return devices
    if platform != "tpu":
        raise BenchmarkError(
            f"JAX found platform {platform!r} ({devices[0].device_kind} "
            f"x{len(devices)}), not 'tpu'; the benchmark measures nothing "
            "off the chip (--rehearse is the CPU dry run)")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX shows {len(devices)}")
    return devices


def use_compile_cache() -> str:
    """The engine's one compile-cache helper (JAX_COMPILATION_CACHE_DIR,
    else the FIXED ``<checkout>/.jax_compile_cache``: the path is part of
    the cache's key), and every program kept, however quick its compile,
    so that only the first run of a cell in a checkout compiles."""
    import jax

    from spark_rapids_tpu import envinfo

    path = envinfo.use_compile_cache()
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def build_native() -> str:
    """Rebuild native/libsrtpu.so BEFORE the engine loads it (the .so is
    git-ignored: a checkout has none). A missing host decoder is said, not
    fatal: the engine's numpy decode serves."""
    sys.path.insert(0, os.path.join(ROOT, "native"))
    try:
        from build import build  # type: ignore[import-not-found]
    except ImportError as e:
        return f"build FAILED: {e}"
    finally:
        sys.path.pop(0)
    try:
        return "built " + os.path.relpath(build(force=False), ROOT)
    except subprocess.CalledProcessError as e:
        return ("build FAILED: "
                + (e.stderr or b"").decode(errors="replace").strip()[-500:])
    except OSError as e:  # no g++ on this machine
        return f"build FAILED: {e}"


def make_data(bench: dict, seed: int, rehearse: bool,
              bench_root: str) -> tuple:
    """The cell's table, made anew from the seed in a fixed directory of
    the checkout. Returns (directory, path, rows, row group rows)."""
    config = bench["config"]
    size = config["rehearse"] if rehearse else config
    rows, row_group = int(size["rows"]), int(size["row_group_rows"])
    data_dir = os.path.join(bench_root, ".cache", "data", config["name"])
    shutil.rmtree(data_dir, ignore_errors=True)
    path = bench["generator"].generate(
        config, seed, data_dir, rows, row_group)
    return data_dir, path, rows, row_group


# ---------------------------------------------------------------------------
# the timed path
# ---------------------------------------------------------------------------
class Driver:
    """One closed-loop client over the cell's queries."""

    def __init__(self, sess, queries: list, data_dir: str):
        from spark_rapids_tpu.exec import base as XB

        self.sess = sess
        self.queries = queries
        self.data_dir = data_dir
        self.counter = XB.COMPILE_COUNTER
        self.next = 0
        #: (index of the query, start, seconds, rows or None, fell back)
        self.done: list = []
        self.errors: list = []

    def compiles(self) -> int:
        return self.counter.snapshot()[0]

    def one(self) -> float:
        """Run the next query of the round-robin to host rows; record it."""
        qi = self.next % len(self.queries)
        self.next += 1
        t0 = time.perf_counter()
        try:
            rows = self.queries[qi].frame(self.sess, self.data_dir).collect()
        except Exception:  # the engine failed the query: count it, go on
            dt = time.perf_counter() - t0
            self.errors.append(traceback.format_exc())
            self.done.append((qi, t0, dt, None, False))
            return dt
        dt = time.perf_counter() - t0
        fell_back = bool(self.sess.plan_fallbacks())
        self.done.append((qi, t0, dt, rows, fell_back))
        return dt

    def failures_in_a_row(self) -> int:
        n = 0
        for rec in reversed(self.done):
            if rec[3] is not None:
                break
            n += 1
        return n


def run_window(driver: Driver, seconds: float, tracer=None,
               traced_queries: int = 0) -> dict:
    """The measured window. With a tracer, its first ``traced_queries``
    queries run inside the profiler's trace (the steady slice)."""
    first = len(driver.done)
    compiles0 = driver.compiles()
    slice_info = None
    t_open = time.perf_counter()
    if tracer is not None:
        slice_info = tracer.run_slice(driver, traced_queries)
    while (time.perf_counter() - t_open < seconds
           and driver.failures_in_a_row() < MAX_FAILURES_IN_A_ROW):
        driver.one()
    window_s = time.perf_counter() - t_open
    return {"records": driver.done[first:], "window_s": window_s,
            "compiles": driver.compiles() - compiles0, "slice": slice_info}


def placement_check(sess, platform: str) -> dict:
    """Where the answer was made, once, after the window: the plan's root
    is a device subtree, its final batch's planes and every array the
    process still holds live on ``platform``."""
    import jax

    from spark_rapids_tpu.exec.transitions import ColumnarToRowExec

    root = sess.last_executed_plan
    if not isinstance(root, ColumnarToRowExec):
        return {"ok": False, "why": "plan root is not a device subtree"}
    planes = 0
    for batch in root.tpu_child.execute_columnar():
        for c in batch.columns:
            for plane in (c.data, c.validity, c.offsets, c.chars):
                if plane is None or not hasattr(plane, "devices"):
                    continue
                planes += 1
                if any(d.platform != platform for d in plane.devices()):
                    return {"ok": False,
                            "why": f"a result plane is not on {platform}"}
    live = 0
    for a in jax.live_arrays():
        live += 1
        if any(d.platform != platform for d in a.devices()):
            return {"ok": False, "why": f"a live array is not on {platform}"}
    return {"ok": True, "result_planes": planes, "live_arrays": live}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def execute(args, devices=None, bench_root: str = HERE) -> dict:
    """One run. Returns the result object; ``answers_correct`` in it is the
    comparison's verdict before a rehearsal masks ``correct``. Tests hand in
    ``devices`` to skip the look for a chip, and point ``bench_root`` — the
    directory whose workloads/, configs/, queries/ and metrics/ are read —
    at a copy with files added."""
    bench = loader.load_cell(args.workload, bench_root)
    config = bench["config"]
    if devices is None:
        devices = find_devices(int(config["chips"]), args.rehearse)
    metric_readers = loader.load_metrics(bench_root)
    platform = devices[0].platform
    kind = devices[0].device_kind
    peaks = None if args.rehearse else loader.load_peaks(kind, bench_root)

    say("native: " + build_native())
    # the system under test, before any work is spent on data: a checkout
    # without the engine ends here, with no result
    say("compile cache: " + use_compile_cache())
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.sql import TpuSession

    t0 = time.perf_counter()
    data_dir, path, rows, row_group = make_data(bench, args.seed,
                                                args.rehearse, bench_root)
    say(f"data: {config['name']} seed {args.seed}: {rows} rows in row "
        f"groups of {row_group}, {os.path.getsize(path)} parquet bytes, "
        f"{time.perf_counter() - t0:.1f}s to make")
    sized = dict(config, rows=rows, row_group_rows=row_group)
    scanned = [q.rows_scanned(sized) for q in bench["queries"]]

    conf = dict(config["conf"])
    tracer = None
    if args.trace:
        import trace_reduce

        conf[TRACE_CONF] = True  # names what the host did in a device gap
        tracer = trace_reduce.Tracer(
            os.path.join(bench_root, ".cache", "trace", bench["name"]))
    sess = TpuSession(conf)
    driver = Driver(sess, bench["queries"], data_dir)

    # first query (the compile cache is read or filled here), then warm-up
    c0 = driver.compiles()
    first_query_s = driver.one()
    say(f"first query: {first_query_s:.3f}s, {driver.compiles() - c0} "
        "compile miss(es)")
    warm_compiles = 0
    for _ in range(WARMUP_QUERIES - 1):
        c0 = driver.compiles()
        dt = driver.one()
        warm_compiles = driver.compiles() - c0
        say(f"warm-up query: {dt:.3f}s, {warm_compiles} compile miss(es)")
    if driver.errors:
        raise BenchmarkError("a warm-up query failed:\n" + driver.errors[-1])
    # what the cell states of its steady state: fewer misses are a gain,
    # more mean that something would compile inside the window
    may_compile = int(bench["cell"]["compile_misses_per_query_at_most"])
    if warm_compiles > may_compile:
        raise BenchmarkError(
            f"the last warm-up query had {warm_compiles} compile miss(es); "
            f"workloads/{bench['name']}.json allows {may_compile} a query: "
            "the window would compile")
    spilled0 = BufferCatalog.get().metrics.spilled_bytes

    setup_s = time.perf_counter() - _T_START
    window = run_window(driver, args.seconds, tracer, TRACED_QUERIES)
    records = window["records"]
    if window["compiles"] > may_compile * len(records):
        raise BenchmarkError(
            f"{window['compiles']} compile miss(es) inside a window of "
            f"{len(records)} queries; workloads/{bench['name']}.json allows "
            f"{may_compile} a query")
    stats_now = devices[0].memory_stats() or {}
    spilled = BufferCatalog.get().metrics.spilled_bytes - spilled0
    say(f"window: {len(records)} queries in {window['window_s']:.3f}s, "
        f"{window['compiles']} compile miss(es) inside it "
        f"(last warm-up query: {warm_compiles}), {spilled} bytes spilled")
    say("query seconds, sorted: "
        + " ".join(f"{r[2]:.3f}" for r in sorted(records, key=lambda r: r[2])))
    for err in driver.errors[-1:]:
        say("a query failed:\n" + err)

    ok = [r for r in records if r[3] is not None and not r[4]]
    failed = len(records) - len(ok)
    placement = (placement_check(sess, platform) if ok
                 else {"ok": False, "why": "no query completed"})
    say(f"placement: {placement}")

    # the references, once the window has closed and the peak has been read
    t0 = time.perf_counter()
    want = [None] * len(bench["queries"])
    for qi in sorted({r[0] for r in ok}):
        want[qi] = bench["queries"][qi].reference(path)
    compared = compare.compare_window(
        [(r[0], r[3]) for r in ok], want, bench["queries"],
        bench["query_names"])
    compared["placement_wrong"] = (0.0 if placement["ok"] else 1.0, 0.0)
    say(f"reference and comparison: {time.perf_counter() - t0:.1f}s")
    answers_correct = bool(ok) and failed == 0 and compare.all_within(compared)

    result = {
        "correct": answers_correct and not args.rehearse,
        "attempted": len(records),
        "failed": failed,
        "metrics": {},
        "device": {"platform": platform, "kind": kind,
                   "count": len(devices),
                   "memory_peak_bytes": stats_now.get("peak_bytes_in_use")},
    }
    durations = [r[2] for r in records]
    if args.trace:
        reduced = tracer.reduce(window["slice"])
        say(f"trace: device busy {reduced['busy_s']:.4f}s of a slice of "
            f"{reduced['window_s']:.4f}s over {reduced['queries']} queries "
            f"on {reduced['chips_traced']} chip(s)")
    if args.rehearse:
        result["rehearsal"] = True  # and no metric: none is the chip's
    elif not args.trace:
        result["metrics"] = {
            "rows_per_s": {"unit": "rows/s", "value": stats.rows_per_second(
                [scanned[r[0]] for r in ok], window["window_s"])},
            "query_p95_s": {"unit": "s", "value":
                            stats.percentile_nearest_rank(durations, 95)},
            "peak_hbm_share": {"unit": "%", "value": 100.0
                               * stats_now["peak_bytes_in_use"]
                               / stats_now["bytes_limit"]},
            "setup_s": {"unit": "s", "value": setup_s},
        }
    else:
        ctx = {
            "trace": reduced, "peaks": peaks, "config": sized,
            "queries": bench["queries"],
            "counters": {"first_query_s": first_query_s,
                         "window_compiles": window["compiles"],
                         "spilled_bytes": spilled,
                         "window_queries": len(records)},
        }
        for name, reader in metric_readers.items():
            value = reader.read(ctx)
            if value is not None:
                result["metrics"][name] = {"unit": reader.UNIT,
                                           "value": value}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["answers_correct"] = answers_correct
    result["window"] = {"queries": len(records),
                        "seconds": window["window_s"],
                        "compiles": window["compiles"],
                        "query_mean_s": (sum(durations) / len(durations)
                                         if durations else None)}
    # last in the line, and the last lines of standard error: each number
    # compared beside its limit
    result["compared"] = {k: {"value": v, "limit": lim}
                          for k, (v, lim) in compared.items()}
    sess.close()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=19)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at tiny rows: correct is false and no "
                    "device metric is printed; never on the chip")
    args = ap.parse_args(argv)
    try:
        result = execute(args)
    except BenchmarkError as e:
        say(f"benchmarks/run.py: {e}")
        return 1
    for name, c in result["compared"].items():
        say(f"compared {name}: {c['value']!r} (limit {c['limit']!r})")
    say(f"correct: {result['correct']} (attempted {result['attempted']}, "
        f"failed {result['failed']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
