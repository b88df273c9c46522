#!/usr/bin/env python3
"""First-contact smoke: ONE store_sales-like parquet query through
``TpuSession`` on the chip.

    python chip_smoke.py            # one TPU chip, 28,800,991 rows (TPC-DS SF10)
    python chip_smoke.py --mesh 4   # ONLY the shuffle.mode=ici phase, 4 chips
    python chip_smoke.py --rehearse # CPU dry run at 1 << 16 rows (never on chip)

One process owns the chip: the script starts no child that touches JAX
(the only child is the g++ build of native/libsrtpu.so). Without
``--rehearse`` a platform other than ``tpu`` is an error — there is no CPU
continuation. Every phase either passes or raises; the last stdout line
is the one JSON object the driver reads.

These are first-run observations, not a benchmark: no speed-up is stated.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))

#: TPC-DS store_sales at scale factor 10 (TPC-DS spec v3 table 3-2)
FULL_ROWS = 28_800_991
#: the size of bench.py's parquet shape — no chip run goes below it
MIN_CHIP_ROWS = 1 << 23
REHEARSE_ROWS = 1 << 16
ROW_GROUP = 1 << 21
DATE_CUT = 2_452_015
#: relative tolerance on the float sum vs pandas. Started at 1e-9 and
#: widened to 1e-6 for a reason found BEFORE the first chip run: the query
#: opts into variableFloatAgg, under which AUTO on the TPU resolves the
#: MATMUL tier, and MATMUL sums a double as a (hi, lo) float32 limb pair
#: accumulated in f32 inside each row block (ops/bucket_reduce.py) — every
#: block partial carries one f32 rounding, 2^-24 = 6e-8. Forced MATMUL on
#: the CPU backend at 1M rows measured 3.8e-8 against pandas where SORT and
#: SCATTER measured 8e-16 (PR 23). 1e-6 leaves ~16x room over 2^-24 and is
#: still orders below what one wrong or missing row would move a group.
FLOAT_RTOL = 1e-6

CONF = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def device_phase(rehearse: bool):
    """jax.devices() before any engine import; refuse a non-TPU platform."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" and not rehearse:
        sys.stderr.write(
            f"chip_smoke: JAX found platform {platform!r} "
            f"({devices[0].device_kind} x{len(devices)}), not 'tpu'; this "
            "script measures nothing off the chip (use --rehearse for a "
            "CPU dry run)\n")
        raise SystemExit(1)
    return devices


def device_line(devices) -> None:
    import importlib.metadata as md

    import jax
    import jaxlib

    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.memory.catalog import derive_hbm_budget

    try:
        libtpu = md.version("libtpu")
    except md.PackageNotFoundError:
        libtpu = None
    stats = devices[0].memory_stats() or {}
    say("device: " + json.dumps({
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "bytes_limit": stats.get("bytes_limit"),
        "derived_hbm_budget": derive_hbm_budget(RapidsConf(CONF)),
    }))


def build_native() -> None:
    """Rebuild native/libsrtpu.so from native/src BEFORE the engine loads
    it (the .so is git-ignored: a checkout has none). A missing host
    decoder is reported, not fatal — the numpy decode serves."""
    sys.path.insert(0, os.path.join(HERE, "native"))
    try:
        from build import build  # type: ignore[import-not-found]
    finally:
        sys.path.pop(0)
    try:
        out = build(force=True)
    except subprocess.CalledProcessError as e:
        say("native: build FAILED: "
            + (e.stderr or b"").decode(errors="replace").strip()[-2000:])
        return
    except OSError as e:  # no g++ on this machine
        say(f"native: build FAILED: {e}")
        return
    say(f"native: built {os.path.relpath(out, HERE)} from native/src")


def make_data(data_dir: str, rows: int, seed: int, row_group: int) -> str:
    """The four store_sales columns of bench.py's parquet shape at their
    widths and distributions, one file of ceil(rows / row_group) row
    groups (several, so the scan is multi-batch: the decode pipeline and
    the multi-batch aggregate merge both run)."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.parquet as pq

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed)
    prices = np.round(rng.uniform(1.0, 100.0, 9750), 2)
    table = pa.table({
        "ss_item_sk": pa.array(
            rng.integers(1, 18_001, rows).astype(np.int32)),
        "ss_quantity": pa.array(
            rng.integers(1, 101, rows).astype(np.int32)),
        "ss_wholesale_cost": pa.array(
            prices[rng.integers(0, 9750, rows)]),
        "ss_sold_date_sk": pa.array(
            (2_450_815 + rng.integers(0, 2400, rows)).astype(np.int32)),
    })
    path = os.path.join(data_dir, "store_sales.parquet")
    pq.write_table(table, path, row_group_size=row_group)
    n_rg = pq.ParquetFile(path).metadata.num_row_groups
    say(f"data: {rows} rows, {n_rg} row group(s) of <= {row_group}, "
        f"{os.path.getsize(path)} parquet bytes, seed {seed}, "
        f"{time.perf_counter() - t0:.1f}s to make")
    return path


def frame(sess, data_dir: str):
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit

    return (
        sess.read.parquet(data_dir)
        .where(E.GreaterThanOrEqual(col("ss_sold_date_sk"), lit(DATE_CUT)))
        .group_by("ss_quantity")
        .agg(A.agg(A.Sum(col("ss_wholesale_cost")), "s"),
             A.agg(A.Sum(col("ss_quantity")), "q"),
             A.agg(A.Count(col("ss_item_sk")), "c")))


def pandas_answer(path: str):
    """The plain reference: the same query in pandas on the same file."""
    import pandas as pd

    pdf = pd.read_parquet(path)
    f = pdf[pdf["ss_sold_date_sk"] >= DATE_CUT]
    g = f.groupby("ss_quantity").agg(
        s=("ss_wholesale_cost", "sum"), q=("ss_quantity", "sum"),
        c=("ss_item_sk", "count"))
    return [(int(k), float(r.s), int(r.q), int(r.c))
            for k, r in g.iterrows()]


def check_rows(got, want, label: str) -> float:
    """Keys, integer sum and count exact; float sum within FLOAT_RTOL.
    Returns the worst relative error seen on the float sum."""
    got = sorted(got)
    want = sorted(want)
    if len(got) != len(want):
        raise AssertionError(
            f"{label}: {len(got)} groups, pandas has {len(want)}")
    worst = 0.0
    for g, w in zip(got, want):
        if (g[0], g[2], g[3]) != (w[0], w[2], w[3]):
            raise AssertionError(f"{label}: integer mismatch {g} vs {w}")
        rel = abs(g[1] - w[1]) / max(abs(w[1]), 1e-300)
        worst = max(worst, rel)
    if not worst <= FLOAT_RTOL:
        raise AssertionError(
            f"{label}: float sum off by {worst:.3e} relative "
            f"(tolerance {FLOAT_RTOL:.0e})")
    return worst


def fallback_reasons(sess) -> list:
    """Every fallback in the tagged plan (the walk behind the session's
    own plan_tagged event): per-operator CPU fallback is this engine's
    design, so ONE reason means the path did not run on the device."""
    if sess.overrides.last_meta is None:
        raise AssertionError("planner left no tagged plan (last_meta)")
    return sess.plan_fallbacks()


def walk_execs(node):
    yield node
    for c in getattr(node, "children", ()) or ():
        yield from walk_execs(c)


def assert_on_platform(arrays, platform: str, what: str) -> int:
    n = 0
    for a in arrays:
        for d in a.devices():
            if d.platform != platform:
                raise AssertionError(
                    f"{what}: array {a.shape} {a.dtype} lives on "
                    f"{d.platform}, not {platform}")
        n += 1
    return n


def batch_arrays(batch):
    for c in batch.columns:
        for plane in (c.data, c.validity, c.offsets, c.chars):
            if plane is not None and hasattr(plane, "devices"):
                yield plane


def assert_plan_on_device(sess, platform: str) -> None:
    from spark_rapids_tpu.exec.transitions import ColumnarToRowExec

    reasons = fallback_reasons(sess)
    if reasons:
        raise AssertionError(f"plan has CPU fallbacks: {reasons}")
    root = sess.last_executed_plan
    if not isinstance(root, ColumnarToRowExec):
        raise AssertionError(
            "plan root is not a device subtree:\n" + root.tree_string())
    say("plan:\n" + root.tree_string())
    # the final device batch (re-executed outside the timed calls) and
    # every array the process still holds: scan-cache planes, aggregate
    # state, constants
    import jax

    n = 0
    for b in root.tpu_child.execute_columnar():
        n += assert_on_platform(batch_arrays(b), platform, "result batch")
    live = assert_on_platform(jax.live_arrays(), platform, "live array")
    say(f"placement: {n} result plane(s) and {live} live array(s), "
        f"all on {platform}")


# ---------------------------------------------------------------------------
# the one-chip path
# ---------------------------------------------------------------------------
def run_single(data_dir: str, path: str, devices) -> None:
    from spark_rapids_tpu import native
    from spark_rapids_tpu.exec import base as XB
    from spark_rapids_tpu.exec.aggregate import TpuHashAggregateExec
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.sql import TpuSession

    platform = devices[0].platform
    sess = TpuSession(CONF)
    say("explain:\n" + str(frame(sess, data_dir).explain()))
    rows = None
    last_misses = None
    for i in range(3):
        c0, sites0 = XB.COMPILE_COUNTER.snapshot()
        t0 = time.perf_counter()
        rows = frame(sess, data_dir).collect()
        dt = time.perf_counter() - t0
        c1, sites1 = XB.COMPILE_COUNTER.snapshot()
        last_misses = c1 - c0
        by_site = {k: v - sites0.get(k, 0) for k, v in sites1.items()
                   if v - sites0.get(k, 0)}
        say(f"run {i + 1}: collect() {dt:.3f}s wall, {last_misses} compile "
            f"miss(es) {by_site}, {len(rows)} group(s)")
    if last_misses != 0:
        raise AssertionError(
            f"third run still compiled {last_misses} program(s)")

    assert_plan_on_device(sess, platform)
    aggs = [e for e in walk_execs(sess.last_executed_plan.tpu_child)
            if isinstance(e, TpuHashAggregateExec)]
    if not aggs or aggs[0]._strategy_choice is None:
        raise AssertionError("no aggregate strategy was resolved")
    strategy, reason = aggs[0]._strategy_choice
    say(f"agg strategy: {strategy} ({reason})")
    stats = devices[0].memory_stats() or {}
    m = BufferCatalog.get().metrics
    say("memory: " + json.dumps({
        "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_in_use": stats.get("bytes_in_use"),
        "catalog_peak_device_bytes": m.peak_device_bytes,
        "spills_device_to_host": m.device_to_host,
        "spills_host_to_disk": m.host_to_disk,
        "spilled_bytes": m.spilled_bytes, "unspills": m.unspills}))
    say(f"native decoder served: {native.available()}"
        + ("" if native.available()
           else f" (load error: {native.load_error()})"))
    say(sess.explain_metrics())

    worst = check_rows(rows, pandas_answer(path), "one chip")
    say(f"answer: {len(rows)} groups equal pandas — keys, sum(ss_quantity) "
        f"and count exact; sum(ss_wholesale_cost) worst relative error "
        f"{worst:.3e} (tolerance {FLOAT_RTOL:.0e})")


# ---------------------------------------------------------------------------
# --mesh N: the exchange across chips, and nothing else
# ---------------------------------------------------------------------------
def run_mesh(data_dir: str, path: str, devices, n_mesh: int) -> None:
    from spark_rapids_tpu.exec import mesh as XM
    from spark_rapids_tpu.sql import TpuSession

    platform = devices[0].platform
    if len(devices) < n_mesh:
        raise AssertionError(
            f"--mesh {n_mesh} needs {n_mesh} devices, JAX shows "
            f"{len(devices)}")
    # one split per row group: any target below a row group's size does
    # it (the default 2 GB coalescing target would pack the file into one
    # split -> one partition -> no mesh stage)
    base = {**CONF,
            "spark.rapids.tpu.shuffle.mode": "ici",
            "spark.rapids.tpu.sql.reader.batchSizeBytes": 1}

    # observe where the staged input of the REAL run lands: record the
    # device set of every staged plane as the stage hands it over
    staged_devices: list = []
    inner = XM.TpuMeshAggregateExec._stage_child

    def spying_stage_child(self, child):
        staged = inner(self, child)
        staged_devices.append((
            staged.source,
            [sorted(s.device.id for s in a.addressable_shards)
             for a in staged.cols]))
        return staged

    results = {}
    with mock.patch.object(
            XM.TpuMeshAggregateExec, "_stage_child", spying_stage_child):
        for width in (n_mesh, 1):
            staged_devices.clear()
            sess = TpuSession(
                {**base, "spark.rapids.tpu.mesh.devices": width})
            t0 = time.perf_counter()
            rows = frame(sess, data_dir).collect()
            dt = time.perf_counter() - t0
            plan = sess.last_executed_plan.tree_string()
            say(f"mesh={width}: collect() {dt:.3f}s wall (compile "
                f"included), {len(rows)} group(s)\n{plan}")
            reasons = fallback_reasons(sess)
            if reasons:
                raise AssertionError(f"mesh={width}: fallbacks {reasons}")
            if f"TpuMeshAggregateExec(mesh={width}" not in plan:
                raise AssertionError(
                    f"mesh={width}: no TpuMeshAggregateExec(mesh={width} "
                    "in the plan")
            if not staged_devices:
                raise AssertionError(f"mesh={width}: nothing was staged")
            for source, per_plane in staged_devices:
                if source != "sharded_scan":
                    raise AssertionError(
                        f"mesh={width}: stage fed by {source!r}, not the "
                        "sharded scan")
                for ids in per_plane:
                    if len(set(ids)) != width:
                        raise AssertionError(
                            f"mesh={width}: a staged plane lies on "
                            f"device(s) {ids}, not {width} distinct")
            say(f"mesh={width}: {sum(len(p) for _, p in staged_devices)} "
                f"staged plane(s), each on {width} distinct {platform} "
                f"device(s): {staged_devices[0][1][0]}")
            results[width] = sorted(rows)

    want = pandas_answer(path)
    for width in (n_mesh, 1):
        worst = check_rows(results[width], want, f"mesh={width}")
        say(f"answer mesh={width}: equal pandas, float sum worst relative "
            f"error {worst:.3e} (tolerance {FLOAT_RTOL:.0e})")
    check_rows(results[n_mesh], results[1], f"mesh={n_mesh} vs mesh=1")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=19)
    ap.add_argument("--rows", type=int, default=None,
                    help=f"cut the table (never below {MIN_CHIP_ROWS} on "
                    f"the chip); default {FULL_ROWS}")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="run ONLY the shuffle.mode=ici phase on N chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU dry run at a tiny size; never on the chip")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    if args.rehearse and args.mesh:
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count="
                f"{args.mesh}").strip()
    devices = device_phase(args.rehearse)

    rows = args.rows or (REHEARSE_ROWS if args.rehearse else FULL_ROWS)
    if not args.rehearse and rows < MIN_CHIP_ROWS:
        raise SystemExit(
            f"chip_smoke: --rows {rows} is below {MIN_CHIP_ROWS}")
    if rows != FULL_ROWS:
        say(f"CUT: {rows} rows instead of {FULL_ROWS}"
            + (" (rehearsal)" if args.rehearse else ""))

    build_native()
    from spark_rapids_tpu.envinfo import use_compile_cache

    say(f"compile cache: {use_compile_cache()}")
    device_line(devices)

    with tempfile.TemporaryDirectory(prefix="srtpu_smoke_") as data_dir:
        path = make_data(data_dir, rows, args.seed,
                         rows // 4 if args.rehearse else ROW_GROUP)
        if args.mesh:
            run_mesh(data_dir, path, devices, args.mesh)
        else:
            run_single(data_dir, path, devices)

    say(f"total: {time.perf_counter() - t_start:.1f}s")
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if args.rehearse:
        say(json.dumps({"ok": False, "rehearsal": True, "device": device}))
    else:
        say(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
