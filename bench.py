"""Benchmark suite: six query shapes, TPU engine vs vectorized CPU (pandas
stands in for per-core CPU Spark, the reference's own comparison basis).

Shapes (mirroring the reference's benchmark coverage, docs/benchmarks.md):
  agg      TPC-DS q5-class: filter -> project -> groupby aggregate
  sort     global sort by long key with payload
  join     fact x dim inner hash join
  window   partitioned running aggregate + row_number
  string   LIKE filter + upper/substring projection (TPCx-BB-ish)
  parquet  parquet scan -> aggregate through the full session/planner path

Prints ONE JSON line: the geometric-mean speedup across shapes, with a
per-shape breakdown and an achieved-HBM-bandwidth roofline figure for the
bandwidth-bound agg shape. ``vs_baseline`` divides the geomean by the
reference's "4x typical" GPU-vs-CPU claim (docs/FAQ.md:60-66; BASELINE.md).

Usage: python bench.py [--scale F] [--iters K] [--shapes a,b,...]
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time

import numpy as np

# v5e HBM bandwidth (public spec) for the roofline figure
HBM_GBPS = 819.0


def _timeit(fn, iters):
    fn()  # warm (compile)
    times = []
    for _ in range(max(iters, 3)):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]  # median: the host is a shared machine


def _dev_batch(arrays, schema, n, masks=None):
    """Vectorized numpy -> device ColumnarBatch (no per-row python)."""
    import jax.numpy as jnp

    from spark_rapids_tpu.columnar import ColumnarBatch, DeviceColumn
    from spark_rapids_tpu.utils.bucketing import bucket_rows

    cap = bucket_rows(n)
    cols = []
    for i, (f, x) in enumerate(zip(schema.fields, arrays)):
        valid = np.zeros(cap, dtype=bool)
        valid[:n] = True if masks is None or masks[i] is None else masks[i]
        d = np.zeros(cap, dtype=x.dtype)
        d[:n] = np.where(valid[:n], x, np.zeros(1, x.dtype))
        cols.append(DeviceColumn(f.dataType, n, jnp.asarray(d), jnp.asarray(valid)))
    return ColumnarBatch(cols, schema, n)


def _dev_string_col(pool, idx, n, dtype):
    """Dict-encoded string column from a pool + index array — the layout a
    dictionary-encoding scan hands the engine for low-cardinality columns
    (parquet PLAIN_DICTIONARY pages arrive exactly like this; see
    docs/compatibility.md). Same logical values as the expanded layout;
    string kernels run once over the pool, rows carry int32 codes."""
    from spark_rapids_tpu.columnar.column import dict_column_from_parts
    from spark_rapids_tpu.utils.bucketing import bucket_rows

    cap = bucket_rows(n)
    pool_b = np.array([s.encode("utf-8") for s in pool], dtype=object)
    uniq, inv = np.unique(pool_b, return_inverse=True)
    codes = np.zeros(cap, np.int32)
    codes[:n] = inv[idx]
    lens = np.array([len(b) for b in uniq], np.int64)
    doff = np.zeros(len(uniq) + 1, np.int32)
    np.cumsum(lens, out=doff[1:])
    pool_concat = b"".join(uniq)
    dch = (np.frombuffer(pool_concat, np.uint8).copy() if pool_concat
           else np.zeros(1, np.uint8))
    valid = np.zeros(cap, bool)
    valid[:n] = True
    total = int(lens[codes[:n]].sum())
    return dict_column_from_parts(
        n, codes, doff, dch, valid,
        mat_cap=bucket_rows(max(1, total), 128),
        max_len=int(lens.max()) if lens.size else 0,
        unique=True, dtype=dtype)


def _consume(exec_):
    return [b.to_rows() for b in exec_.execute_columnar()]


def _mem_snapshot():
    """(scan-cache hits, misses) before a shape runs — the deltas give
    the per-shape hit rate (the cache is a process singleton). Also
    rebases the BufferCatalog's peak watermark to the CURRENT level so
    the value read after the shape is THIS shape's peak, not a hungrier
    earlier shape's (the watermark is a monotonic process-wide max;
    bench owns the process, so resetting it between shapes is safe).
    The obs tpu_program_temp_bytes high-water gauge gets the same
    per-shape rebase — a scrape during shape N must report shape N's
    compile peaks, not the run's."""
    from spark_rapids_tpu import obs as _obs
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.memory import ledger as _ledger
    from spark_rapids_tpu.memory.catalog import BufferCatalog

    cat = BufferCatalog.get()
    cat.metrics.peak_device_bytes = cat.device_bytes
    # arm the HBM ledger for the shape window (the FORCE_HARVEST
    # pattern) so the json carries per-op attribution without standing
    # up the whole events/obs plane; per-op peaks rebase like the
    # watermark so the read-back is THIS shape's figure
    _ledger.force_arm()
    cat.ledger.rebase_peaks()
    reg = _obs.active()
    if reg is not None:
        reg.rebase_gauge("tpu_program_temp_bytes")
    inst = DeviceScanCache._instance
    return (inst.hits, inst.misses) if inst is not None else (0, 0)


def _mem_stats(before):
    """Per-shape memory-pressure block for the BENCH json: the
    BufferCatalog's peak device-byte watermark over THIS shape's window
    (rebased in _mem_snapshot — how close the shape got to the spill
    budget; the perf trajectory should track memory pressure, not just
    time) and the scan-cache hit rate over the shape's own accesses
    (None when the shape never touched the cache)."""
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.memory.catalog import BufferCatalog

    h0, m0 = before
    cat = BufferCatalog.get()
    # read the ledger's shape-window attribution BEFORE _mem_snapshot
    # rebases it for the next shape
    peaks = {op: b for op, b in cat.ledger.op_peaks().items() if b > 0}
    owner_top = max(peaks.items(), key=lambda kv: kv[1]) if peaks else None
    leaked = cat.ledger.stats()["leaked_live"]
    h1, m1 = _mem_snapshot()
    seen = (h1 - h0) + (m1 - m0)
    return {
        "peak_device_bytes": cat.metrics.peak_device_bytes,
        # per-op decomposition of that peak (the HBM ledger, force-armed
        # per shape): who held the bytes, the single largest owner, and
        # the leak sentinel's tally — leaked_buffers must be 0 and
        # tpu_profile --diff gates per-op growth
        "hbm_peak_by_op": peaks,
        "hbm_owner_top": list(owner_top) if owner_top else None,
        "leaked_buffers": leaked,
        "scan_cache_hit_rate": (
            round((h1 - h0) / seen, 3) if seen else None),
        "scan_cache_bytes": (
            DeviceScanCache._instance.stats()["bytes"]
            if DeviceScanCache._instance is not None else 0),
    }


def _device_time(exec_, iters=4):
    """Device-side wallclock of one query, net of the host link.

    Dispatch is async on TPU; a blocking collect pays (queue wait + link
    round trip). Timing 1 run vs ``iters`` back-to-back runs and taking
    the slope isolates the device time — the same idea as CUDA-event
    timing in the reference's NVTX benches (NvtxWithMetrics.scala)."""
    _consume(exec_)  # warm
    t0 = time.perf_counter()
    _consume(exec_)
    t1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    outs = None
    for _ in range(iters):
        outs = list(exec_.execute_columnar())  # async dispatch, no fetch
    for b in outs:
        b.to_rows()  # ONE blocking fetch: waits for all queued runs
    tn = time.perf_counter() - t0
    return max((tn - t1) / (iters - 1), 1e-9)


def _xla_stats(cost_snapshot, device_ms, peak_gbps=HBM_GBPS):
    """Per-shape compiler-reported roofline block: ``xla_bytes_accessed``
    sums cost_analysis 'bytes accessed' over the distinct XLA programs
    the shape compiled (each dispatches once per query run, so the sum
    is one run's compiler-reported traffic), and ``hbm_frac_xla`` is
    that traffic / device time / peak — the XLA-measured twin of the
    layout-derived hbm_frac_device; the two bound the true utilization.
    Degrades to None when the backend reported no byte costs or the
    device slope was noise."""
    from spark_rapids_tpu import xla_cost

    recs = xla_cost.records_since(cost_snapshot)
    xb = sum(r["bytes_accessed"] for r in recs
             if r.get("bytes_accessed") is not None)
    # peak temp across the shape's programs: the materialized-
    # intermediate watermark the radix/pallas lowerings exist to shrink
    temps = [r["temp_bytes"] for r in recs
             if r.get("temp_bytes") is not None]
    out = {"xla_bytes_accessed": int(xb) if xb else None,
           "xla_peak_temp_bytes": int(max(temps)) if temps else None,
           "hbm_frac_xla": None}
    if xb and device_ms and device_ms >= 0.1:
        gbps = xb / (device_ms / 1e3) / 1e9
        out["hbm_frac_xla"] = round(gbps / peak_gbps, 4)
    return out


def byte_amplification(xla_bytes, layout_bound):
    """XLA-reported bytes-accessed over the analyzer's layout bound —
    the FIRST-CLASS trended number of the round-12 kernel rewrite (the
    r09 agg shape sat at ~25x; a lowering sized to the layout approaches
    1). None when either input is missing/zero, so shapes without a
    harvest or a static forecast degrade instead of faking a ratio.
    Shared with tools/tpu_profile.py --diff, which BACKFILLS it when
    diffing older BENCH jsons that carry both inputs."""
    if not xla_bytes or not layout_bound:
        return None
    return round(xla_bytes / layout_bound, 2)


def _hlo_stats(hlo_snapshot):
    """Per-shape per-fusion attribution block (hlo.py, harvested under
    the same FORCE_HARVEST warm-up as _xla_stats): ``hlo_top_fusion_
    bytes`` is the largest single-fusion byte attribution across the
    shape's compiled programs — the instruction the roofline push must
    shrink — and ``hlo_scatter_count`` the scatter-classified
    instructions across those programs (the amplification idiom; the
    --diff gate flags any same-strategy increase). Both None when no
    program was harvested (warm caches or unparseable dialect)."""
    from spark_rapids_tpu import hlo

    recs = hlo.records_since(hlo_snapshot)
    if not recs:
        return {"hlo_top_fusion_bytes": None, "hlo_scatter_count": None}
    top = 0
    scat = 0
    for r in recs:
        scat += r.get("scatter_count") or 0
        for f in r.get("top_fusions") or []:
            top = max(top, f.get("bytes") or 0)
    return {"hlo_top_fusion_bytes": top or None, "hlo_scatter_count": scat}


def _strategy_of(exec_, attr):
    found = []

    def walk(node):
        c = getattr(node, attr, None)
        if c is not None:
            found.append(c[0])
        for k in getattr(node, "children", ()):
            walk(k)

    walk(exec_)
    return found[0] if found else None


def _agg_strategy_of(exec_):
    """The aggregation strategy the plan's aggregate exec(s) resolved at
    execution (conf sql.agg.strategy; exec/aggregate.resolved_strategy) —
    None for shapes without a grouped aggregate. Emitted per shape so a
    BENCH diff shows not just THAT a shape regressed but which lowering
    it was running."""
    return _strategy_of(exec_, "_strategy_choice")


def _join_strategy_of(exec_):
    """The join probe lowering the plan's join exec(s) resolved (conf
    sql.join.strategy; exec/join.resolved_strategy) — None for shapes
    without an equi-join. The --diff gates waive same-shape comparisons
    when either strategy field flipped (a deliberate lowering change
    owns its byte/temp/fusion profile)."""
    return _strategy_of(exec_, "_join_strategy_choice")


def _dev_stats(exec_, bytes_read, tpu_t):
    """Per-shape device_ms + HBM roofline block: ``bytes_read`` is what
    the query must stream from HBM at least once; wallclock includes the
    host-link round trip, device time isolates the kernels (see
    _device_time). Emitted for EVERY shape so per-shape regressions (e.g.
    parquet decode vs upload vs compute) show up in the JSON, not just
    the agg headline."""
    dev_t = _device_time(exec_)
    gbps = bytes_read / tpu_t / 1e9
    # static forecast of the HBM bytes this plan touches, from the plan
    # analyzer (plugin/plananalysis.py) — emitted next to the measured
    # roofline so BENCH rounds can track forecast accuracy over time
    from spark_rapids_tpu.plugin.plananalysis import predict_exec_hbm

    out = {"hbm_gbps": round(gbps, 1),
           "hbm_frac": round(gbps / HBM_GBPS, 3),
           "device_ms": round(dev_t * 1e3, 3),
           "predicted_hbm_bytes": predict_exec_hbm(exec_),
           "agg_strategy": _agg_strategy_of(exec_),
           "join_strategy": _join_strategy_of(exec_)}
    if dev_t >= 1e-4:
        dev_gbps = bytes_read / dev_t / 1e9
        out["hbm_gbps_device"] = round(dev_gbps, 1)
        out["hbm_frac_device"] = round(dev_gbps / HBM_GBPS, 3)
    else:
        # slope below 0.1ms is measurement noise (cached/near-instant
        # runs); a roofline figure from it would be fiction
        out["hbm_gbps_device"] = None
        out["hbm_frac_device"] = None
    return out


# ---------------------------------------------------------------------------
# shapes
# ---------------------------------------------------------------------------
def shape_agg(scale, iters, conf, T, E, A, X):
    n = int((1 << 26) * scale)
    rng = np.random.default_rng(42)
    k = rng.integers(0, 64, n).astype(np.int32)
    a = rng.integers(-(10**6), 10**6, n).astype(np.int64)
    b = rng.normal(size=n)
    b_null = rng.random(n) < 0.05

    import pandas as pd

    pdf = pd.DataFrame({"k": k, "a": a, "b": np.where(b_null, np.nan, b)})

    def cpu():
        f = pdf[pdf["a"] >= 0]
        return f.assign(a2=f["a"] * 2).groupby("k").agg(
            s=("a2", "sum"), m=("b", "mean"), c=("b", "count"))

    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.expr.expressions import col, lit

    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)
    batch = _dev_batch(
        [k, a, np.where(b_null, 0.0, b)], schema, n,
        masks=[None, None, ~b_null])
    scan = X.InMemoryScanExec(conf, [[batch]], schema)
    filt = X.TpuFilterExec(conf, E.GreaterThanOrEqual(col("a"), lit(0)), scan)
    proj = X.TpuProjectExec(
        conf, [col("k"), E.Alias(E.Multiply(col("a"), lit(2)), "a2"), col("b")],
        filt)
    agg = X.TpuHashAggregateExec(
        conf, [col("k")],
        [A.agg(A.Sum(col("a2")), "s"), A.agg(A.Average(col("b")), "m"),
         A.agg(A.Count(col("b")), "c")], proj)

    cpu_t = _timeit(cpu, max(1, iters // 2))
    tpu_t = _timeit(lambda: _consume(agg), iters)
    bytes_read = n * (4 + 8 + 8 + 3)  # k + a + b + 3 validity masks
    return cpu_t, tpu_t, _dev_stats(agg, bytes_read, tpu_t)


def shape_sort(scale, iters, conf, T, E, A, X):
    """Global ORDER BY ... LIMIT 1000 — how TPC-DS sort queries actually
    end (the reference's harness also collects only the final small result
    to the driver, BenchUtils.scala:693)."""
    n = int((1 << 23) * scale)
    rng = np.random.default_rng(7)
    key = rng.integers(-(2**40), 2**40, n)
    pay = rng.integers(0, 1000, n).astype(np.int32)

    import pandas as pd

    pdf = pd.DataFrame({"key": key, "pay": pay})

    def cpu():
        return pdf.sort_values("key").head(1000)

    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.basic import TpuLocalLimitExec
    from spark_rapids_tpu.exec.sort import TpuSortExec
    from spark_rapids_tpu.expr.expressions import col

    schema = schema_of(key=T.LONG, pay=T.INT)
    batch = _dev_batch([key, pay], schema, n)
    scan = X.InMemoryScanExec(conf, [[batch]], schema)
    srt = TpuSortExec(conf, [col("key")], [(True, True)], scan)
    lim = TpuLocalLimitExec(conf, 1000, srt)

    def tpu():
        return _consume(lim)

    cpu_t = _timeit(cpu, max(1, iters // 2))
    tpu_t = _timeit(tpu, iters)
    bytes_read = n * (8 + 4 + 2)  # key + pay + validity masks
    return cpu_t, tpu_t, _dev_stats(lim, bytes_read, tpu_t)


def shape_join(scale, iters, conf, T, E, A, X):
    n = int((1 << 23) * scale)
    d = 100_000
    rng = np.random.default_rng(11)
    fk = rng.integers(0, d, n).astype(np.int64)
    fv = rng.integers(0, 100, n).astype(np.int64)
    dk = np.arange(d, dtype=np.int64)
    dv = rng.integers(0, 10**6, d).astype(np.int64)

    import pandas as pd

    fact = pd.DataFrame({"fk": fk, "fv": fv})
    dim = pd.DataFrame({"dk": dk, "dv": dv})

    def cpu():
        return fact.merge(dim, left_on="fk", right_on="dk", how="inner")

    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.join import TpuShuffledHashJoinExec
    from spark_rapids_tpu.expr.expressions import col

    fs = schema_of(fk=T.LONG, fv=T.LONG)
    ds = schema_of(dk=T.LONG, dv=T.LONG)
    fb = _dev_batch([fk, fv], fs, n)
    db = _dev_batch([dk, dv], ds, d)
    join = TpuShuffledHashJoinExec(
        conf, X.InMemoryScanExec(conf, [[fb]], fs),
        X.InMemoryScanExec(conf, [[db]], ds),
        [col("fk")], [col("dk")], "inner")
    # TPC-DS q24/q72 shape: the join feeds an aggregate (results stay on
    # device; a driver-side collect of the raw 8M-row join would measure
    # the host link, not the engine)
    agg = X.TpuHashAggregateExec(
        conf, [col("fv")],
        [A.agg(A.Sum(col("dv")), "s"), A.agg(A.Count(None), "c")], join)

    def cpu_agg():
        j = cpu()
        return j.groupby("fv").agg(s=("dv", "sum"), c=("dv", "count"))

    def tpu():
        return _consume(agg)

    cpu_t = _timeit(cpu_agg, max(1, iters // 2))
    tpu_t = _timeit(tpu, iters)
    bytes_read = n * (8 + 8 + 2) + d * (8 + 8 + 2)  # fact + dim cols
    return cpu_t, tpu_t, _dev_stats(agg, bytes_read, tpu_t)


def shape_window(scale, iters, conf, T, E, A, X):
    n = int((1 << 23) * scale)
    rng = np.random.default_rng(13)
    k = rng.integers(0, 64, n).astype(np.int32)
    ts = rng.permutation(n).astype(np.int64)
    v = rng.integers(0, 1000, n).astype(np.int64)

    import pandas as pd

    pdf = pd.DataFrame({"k": k, "ts": ts, "v": v})

    def cpu():
        s = pdf.sort_values(["k", "ts"])
        out = s.assign(rs=s.groupby("k")["v"].cumsum(),
                       rn=s.groupby("k").cumcount() + 1)
        return out[out["rn"] <= 3]

    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.window import TpuWindowExec
    from spark_rapids_tpu.expr import windows as W
    from spark_rapids_tpu.expr.expressions import col, lit

    schema = schema_of(k=T.INT, ts=T.LONG, v=T.LONG)
    batch = _dev_batch([k, ts, v], schema, n)
    spec = W.WindowSpec(
        partition_by=(col("k"),), order_by=(col("ts"),),
        orders=((True, True),))
    wexprs = [
        W.WindowExpression(A.Sum(col("v")), spec, "rs"),
        W.WindowExpression(W.RowNumber(), spec, "rn"),
    ]
    wx = TpuWindowExec(conf, wexprs, X.InMemoryScanExec(conf, [[batch]], schema))
    # top-3-per-group tail (TPC-DS q67 pattern): the window output feeds a
    # rank filter, so the collect is small
    filt = X.TpuFilterExec(conf, E.LessThanOrEqual(col("rn"), lit(3)), wx)

    def tpu():
        return _consume(filt)

    cpu_t = _timeit(cpu, max(1, iters // 2))
    tpu_t = _timeit(tpu, iters)
    bytes_read = n * (4 + 8 + 8 + 3)  # k + ts + v + validity masks
    return cpu_t, tpu_t, _dev_stats(filt, bytes_read, tpu_t)


def shape_string(scale, iters, conf, T, E, A, X):
    n = int((1 << 22) * scale)
    rng = np.random.default_rng(17)
    pool = [
        "alpha-001", "beta-smallX", "gamma", "delta-verylongvalue-0042",
        "epsilon-X", "zeta", "eta-middling", "theta-X-suffix", "iota",
        "kappa-longish-string", "", "lambda-Xx", "mu-0", "nu-tail",
    ] * 4
    idx = rng.integers(0, len(pool), n)
    v = rng.integers(0, 1000, n).astype(np.int64)

    import pandas as pd

    pdf = pd.DataFrame({"s": pd.Series([pool[i] for i in idx], dtype=object),
                        "v": v})

    def cpu():
        f = pdf[pdf["s"].str.contains("X", regex=False)]
        f = f.assign(u=f["s"].str.upper().str.slice(0, 6),
                     ln=f["s"].str.len())
        return (f["u"].str.len().sum(), f["ln"].sum(), len(f), f["v"].sum())

    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.expr.expressions import col, lit

    schema = schema_of(s=T.STRING, v=T.LONG)
    scol = _dev_string_col(pool, idx, n, T.STRING)
    vb = _dev_batch([v], schema_of(v=T.LONG), n)
    batch = ColumnarBatch([scol, vb.columns[0]], schema, n)
    scan = X.InMemoryScanExec(conf, [[batch]], schema)
    filt = X.TpuFilterExec(conf, E.Contains(col("s"), lit("X")), scan)
    proj = X.TpuProjectExec(
        conf,
        [E.Alias(E.Substring(E.Upper(col("s")), lit(1), lit(6)), "u"),
         E.Alias(E.Length(col("s")), "ln"), col("v")],
        filt)
    # TPCx-BB-style tail: the string pipeline feeds a grand aggregate so
    # the collect is one row (string kernels still do all the work)
    agg = X.TpuHashAggregateExec(
        conf, [],
        [A.agg(A.Sum(E.Length(col("u"))), "ul"), A.agg(A.Sum(col("ln")), "l"),
         A.agg(A.Count(None), "c"), A.agg(A.Sum(col("v")), "sv")], proj)

    def tpu():
        return _consume(agg)

    cpu_t = _timeit(cpu, max(1, iters // 2))
    tpu_t = _timeit(tpu, iters)
    # dict-encoded column: int32 codes + validity per row + the pool
    pool_bytes = sum(len(s.encode("utf-8")) for s in set(pool))
    bytes_read = n * (4 + 1 + 8 + 1) + pool_bytes
    return cpu_t, tpu_t, _dev_stats(agg, bytes_read, tpu_t)


def shape_parquet(scale, iters, conf_dict, T, E, A, X):
    """TPC-DS store_sales-like scan -> filter -> aggregate through the
    session/planner path. Column distributions mirror TPC-DS (dimension
    keys, bounded quantities, discrete price points): parquet dictionary-
    encodes them, and the TPU-side page decoder (io/parquet_device.py)
    uploads the encoded pages and expands on device — the same division
    of labor as the reference's GPU decode (GpuParquetScan.scala:1157)."""
    n = int((1 << 23) * scale)
    rng = np.random.default_rng(19)
    import pyarrow as pa
    import pyarrow.parquet as pq

    tmpd = tempfile.mkdtemp(prefix="srtpu_bench_")
    prices = np.round(rng.uniform(1.0, 100.0, 9750), 2)
    t = pa.table({
        "ss_item_sk": pa.array(
            rng.integers(1, 18_001, n).astype(np.int32)),
        "ss_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        "ss_wholesale_cost": pa.array(prices[rng.integers(0, 9750, n)]),
        "ss_sold_date_sk": pa.array(
            (2_450_815 + rng.integers(0, 2400, n)).astype(np.int32)),
    })
    path = os.path.join(tmpd, "t.parquet")
    pq.write_table(t, path, row_group_size=1 << 21)

    import pandas as pd

    def cpu():
        pdf = pd.read_parquet(path)
        f = pdf[pdf["ss_sold_date_sk"] >= 2_452_015]
        return f.groupby("ss_quantity").agg(
            s=("ss_wholesale_cost", "sum"), c=("ss_item_sk", "count"))

    from spark_rapids_tpu.expr.expressions import col, lit
    from spark_rapids_tpu.sql import TpuSession

    sess = TpuSession(conf_dict)

    def frame():
        df = sess.read.parquet(tmpd)
        return (
            df.where(E.GreaterThanOrEqual(col("ss_sold_date_sk"),
                                          lit(2_452_015)))
            .group_by("ss_quantity")
            .agg(A.agg(A.Sum(col("ss_wholesale_cost")), "s"),
                 A.agg(A.Count(col("ss_item_sk")), "c")))

    def tpu():
        return frame().collect()

    cpu_t = _timeit(cpu, max(1, iters // 2))
    tpu_t = _timeit(tpu, iters)
    # device timing runs the planned TPU subtree directly (scan cache
    # keeps decode warm across iterations, matching the wallclock runs)
    plan = sess._execute(frame().node)
    dev_exec = getattr(plan, "tpu_child", None)
    # decoded column bytes the query streams (4 int32-ish cols + validity)
    bytes_read = n * (4 + 4 + 8 + 4 + 4)
    extra = (_dev_stats(dev_exec, bytes_read, tpu_t)
             if dev_exec is not None else {})
    return cpu_t, tpu_t, extra


SHAPES = {
    "agg": shape_agg,
    "sort": shape_sort,
    "join": shape_join,
    "window": shape_window,
    "string": shape_string,
    "parquet": shape_parquet,
}


# ---------------------------------------------------------------------------
# mesh lane (--mesh N): the six shapes as SPMD plans over an N-device mesh,
# measured against the SAME plan on a 1-device mesh. Writes real per-shape
# numbers (tpu_ms incl. sharded ingestion, the SPMD program's dispatch->
# ready time, per-chip completion lanes) plus scaling efficiency and the
# per-shard plananalysis forecast cross-check — the MULTICHIP_*.json
# payload, replacing the old dry-run ok flag.
#
# Scaling efficiency definitions (both reported; docs/tuning.md):
#   scaling_efficiency_raw = (t_1dev / t_Ndev) / N        — the textbook
#     strong-scaling number. On the XLA-CPU host-device fallback, N
#     virtual devices timeshare os.cpu_count() cores, so raw efficiency
#     is bounded by cores/N no matter how good the program is.
#   scaling_efficiency     = (t_1dev / t_Ndev) / min(N, host_parallelism)
#     — normalizes out the emulation: how much of the parallelism the
#     backend ACTUALLY has does the SPMD program capture. On a real
#     N-chip TPU host_parallelism >= N and the two definitions coincide.
# ---------------------------------------------------------------------------
def _stage_mesh_env(n: int) -> None:
    """Force an n-device virtual CPU mesh BEFORE jax initializes (same
    contract as the dryrun/conftest: the flag only works pre-backend)."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def _mesh_stages_of(root):
    from spark_rapids_tpu.plugin.plananalysis import _mesh_stages_of as f

    return f(root)


def _np_shard_parts(arrays, masks, n, n_parts):
    """Split columns into n_parts contiguous chunks of (data, valid)."""
    per = (n + n_parts - 1) // n_parts
    parts = []
    for p in range(n_parts):
        lo, hi = p * per, min((p + 1) * per, n)
        cols = []
        for x, m in zip(arrays, masks):
            d = x[lo:hi]
            v = (np.ones(hi - lo, bool) if m is None else m[lo:hi])
            cols.append((d, v))
        parts.append((cols, hi - lo))
    return parts


def _run_mesh_plan(root, iters):
    """Materialize the plan ``iters`` times (stages reset between runs so
    staging + SPMD execution both re-happen; compiled programs stay
    cached). Returns (median wall s, median max-per-chip ns, per-chip ns
    of the median run, stages)."""
    stages = _mesh_stages_of(root)

    def once():
        for st in stages:
            st.reset_for_rerun()
        t0 = time.perf_counter()
        for p in range(root.num_partitions):
            for _ in root.execute_partition(p):
                pass
        wall = time.perf_counter() - t0
        chips = []
        for st in stages:
            chips = st.mesh_actuals.get("per_chip_ns") or chips
        return wall, chips

    once()  # warm: compile
    runs = [once() for _ in range(max(iters, 3))]
    runs.sort(key=lambda r: r[0])
    wall, chips = runs[len(runs) // 2]
    exec_ns = max(chips) if chips else 0
    return wall, exec_ns, chips, stages


def _mesh_shape_result(build, conf_n, conf_1, n_dev, iters):
    """Measure one mesh shape at N devices and 1 device; cross-check the
    per-shard forecast on the N-device plan."""
    from spark_rapids_tpu.plugin.plananalysis import (
        cross_check_mesh,
        forecast_mesh,
    )

    root_n = build(conf_n)
    wall_n, exec_n, chips, stages = _run_mesh_plan(root_n, iters)
    fc = forecast_mesh(root_n)
    violations = cross_check_mesh(root_n)
    root_1 = build(conf_1)
    wall_1, exec_1, _, _ = _run_mesh_plan(root_1, iters)
    host_par = min(n_dev, os.cpu_count() or 1)
    speedup = (exec_1 / exec_n) if exec_n else None
    out = {
        "tpu_ms": round(wall_n * 1e3, 1),
        "tpu_ms_1dev": round(wall_1 * 1e3, 1),
        "device_ms": round(exec_n / 1e6, 3),
        "device_ms_1dev": round(exec_1 / 1e6, 3),
        "per_chip_device_ms": [round(c / 1e6, 3) for c in chips],
        "speedup_vs_1dev": round(speedup, 3) if speedup else None,
        "scaling_efficiency": (
            round(speedup / host_par, 3) if speedup else None),
        "scaling_efficiency_raw": (
            round(speedup / n_dev, 3) if speedup else None),
        "mesh_lowered": bool(stages),
        "mesh_stages": [s.node_name for s in stages],
        "sharded_scan": any(
            (s.mesh_actuals.get("staging") or {}).get("source")
            == "sharded_scan"
            or (s.mesh_actuals.get("staging_left") or {}).get("source")
            == "sharded_scan"
            for s in stages),
        "forecast_violations": violations,
        "forecast": fc,
    }
    return out


def mesh_shape_agg(scale, conf, n_dev, T, E, A, X):
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.mesh import TpuMeshAggregateExec
    from spark_rapids_tpu.exec.scan import MeshShardedScanExec
    from spark_rapids_tpu.expr.expressions import col, lit

    n = int((1 << 26) * scale)
    rng = np.random.default_rng(42)
    k = rng.integers(0, 64, n).astype(np.int32)
    a = rng.integers(-(10**6), 10**6, n).astype(np.int64)
    b = rng.normal(size=n)
    b_null = rng.random(n) < 0.05
    schema = schema_of(k=T.INT, a=T.LONG, b=T.DOUBLE)
    parts = _np_shard_parts(
        [k, a, np.where(b_null, 0.0, b)], [None, None, ~b_null], n, n_dev)

    def build(conf):
        scan = MeshShardedScanExec(conf, parts, schema)
        filt = X.TpuFilterExec(
            conf, E.GreaterThanOrEqual(col("a"), lit(0)), scan)
        proj = X.TpuProjectExec(
            conf,
            [col("k"), E.Alias(E.Multiply(col("a"), lit(2)), "a2"),
             col("b")], filt)
        return TpuMeshAggregateExec(
            conf, [col("k")],
            [A.agg(A.Sum(col("a2")), "s"), A.agg(A.Average(col("b")), "m"),
             A.agg(A.Count(col("b")), "c")], proj)

    return build


def mesh_shape_sort(scale, conf, n_dev, T, E, A, X):
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.mesh import TpuMeshSortExec
    from spark_rapids_tpu.exec.scan import MeshShardedScanExec

    n = int((1 << 23) * scale)
    rng = np.random.default_rng(7)
    key = rng.integers(-(2**40), 2**40, n)
    pay = rng.integers(0, 1000, n).astype(np.int32)
    schema = schema_of(key=T.LONG, pay=T.INT)
    parts = _np_shard_parts([key, pay], [None, None], n, n_dev)

    def build(conf):
        scan = MeshShardedScanExec(conf, parts, schema)
        return TpuMeshSortExec(conf, [0], [(True, True)], scan)

    return build


def mesh_shape_join(scale, conf, n_dev, T, E, A, X):
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.mesh import TpuMeshHashJoinExec
    from spark_rapids_tpu.exec.scan import MeshShardedScanExec

    n = int((1 << 23) * scale)
    d = 100_000
    rng = np.random.default_rng(11)
    fk = rng.integers(0, d, n).astype(np.int64)
    fv = rng.integers(0, 100, n).astype(np.int64)
    dk = np.arange(d, dtype=np.int64)
    dv = rng.integers(0, 10**6, d).astype(np.int64)
    fs = schema_of(fk=T.LONG, fv=T.LONG)
    ds = schema_of(dk=T.LONG, dv=T.LONG)
    fparts = _np_shard_parts([fk, fv], [None, None], n, n_dev)
    dparts = _np_shard_parts([dk, dv], [None, None], d, n_dev)

    def build(conf):
        return TpuMeshHashJoinExec(
            conf, MeshShardedScanExec(conf, fparts, fs),
            MeshShardedScanExec(conf, dparts, ds), [0], [0])

    return build


def mesh_shape_window(scale, conf, n_dev, T, E, A, X):
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.mesh import TpuMeshWindowExec
    from spark_rapids_tpu.exec.scan import MeshShardedScanExec
    from spark_rapids_tpu.expr import windows as W
    from spark_rapids_tpu.expr.expressions import col

    n = int((1 << 23) * scale)
    rng = np.random.default_rng(13)
    k = rng.integers(0, 64, n).astype(np.int32)
    ts = rng.permutation(n).astype(np.int64)
    v = rng.integers(0, 1000, n).astype(np.int64)
    schema = schema_of(k=T.INT, ts=T.LONG, v=T.LONG)
    parts = _np_shard_parts([k, ts, v], [None] * 3, n, n_dev)
    spec = W.WindowSpec(
        partition_by=(col("k"),), order_by=(col("ts"),),
        orders=((True, True),))
    wexprs = [
        W.WindowExpression(A.Sum(col("v")), spec, "rs"),
        W.WindowExpression(W.RowNumber(), spec, "rn"),
    ]

    def build(conf):
        scan = MeshShardedScanExec(conf, parts, schema)
        return TpuMeshWindowExec(conf, wexprs, scan)

    return build


def mesh_shape_string(scale, conf, n_dev, T, E, A, X):
    """String group-by over the mesh: the byte-plane exchange carries the
    string GROUP KEY (dict columns materialize at staging). The string
    kernels run in the chain below the stage (host-fed: strings gate the
    sharded scan), the aggregate + exchange are the SPMD program."""
    from spark_rapids_tpu.columnar import ColumnarBatch
    from spark_rapids_tpu.columnar.batch import schema_of
    from spark_rapids_tpu.exec.mesh import TpuMeshAggregateExec
    from spark_rapids_tpu.expr.expressions import col, lit

    n = int((1 << 22) * scale)
    rng = np.random.default_rng(17)
    pool = [
        "alpha-001", "beta-smallX", "gamma", "delta-verylongvalue-0042",
        "epsilon-X", "zeta", "eta-middling", "theta-X-suffix", "iota",
        "kappa-longish-string", "", "lambda-Xx", "mu-0", "nu-tail",
    ] * 4
    idx = rng.integers(0, len(pool), n)
    v = rng.integers(0, 1000, n).astype(np.int64)
    schema = schema_of(s=T.STRING, v=T.LONG)
    per = (n + n_dev - 1) // n_dev
    partitions = []
    for p in range(n_dev):
        lo, hi = p * per, min((p + 1) * per, n)
        if lo >= hi:
            partitions.append([])
            continue
        scol = _dev_string_col(pool, idx[lo:hi], hi - lo, T.STRING)
        vb = _dev_batch([v[lo:hi]], schema_of(v=T.LONG), hi - lo)
        partitions.append(
            [ColumnarBatch([scol, vb.columns[0]], schema, hi - lo)])

    def build(conf):
        scan = X.InMemoryScanExec(conf, partitions, schema)
        filt = X.TpuFilterExec(conf, E.Contains(col("s"), lit("X")), scan)
        return TpuMeshAggregateExec(
            conf, [col("s")],
            [A.agg(A.Count(None), "c"), A.agg(A.Sum(col("v")), "sv")],
            filt)

    return build


def mesh_shape_parquet(scale, conf, n_dev, T, E, A, X):
    """The full product path: session-planned parquet scan -> filter ->
    grouped aggregate lowering to ONE SPMD program fed by the sharded
    parquet scan (row groups round-robined across shards, host decode
    overlapping per-shard staged uploads)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = int((1 << 23) * scale)
    rng = np.random.default_rng(19)
    tmpd = tempfile.mkdtemp(prefix="srtpu_meshbench_")
    prices = np.round(rng.uniform(1.0, 100.0, 9750), 2)
    t = pa.table({
        "ss_item_sk": pa.array(rng.integers(1, 18_001, n).astype(np.int32)),
        "ss_quantity": pa.array(rng.integers(1, 101, n).astype(np.int32)),
        "ss_wholesale_cost": pa.array(prices[rng.integers(0, 9750, n)]),
        "ss_sold_date_sk": pa.array(
            (2_450_815 + rng.integers(0, 2400, n)).astype(np.int32)),
    })
    path = os.path.join(tmpd, "t.parquet")
    # 2 row groups per shard so the round-robin has real work to spread
    pq.write_table(t, path, row_group_size=max(n // (2 * n_dev), 1))

    from spark_rapids_tpu.expr.expressions import col, lit
    from spark_rapids_tpu.sql import TpuSession

    def build(conf):
        # one scan split per row group: the default coalescing byte
        # target would pack the whole file into a single partition and
        # the planner would never see a mesh-eligible multi-split scan
        sess = TpuSession({
            **conf._values,
            "spark.rapids.tpu.sql.reader.batchSizeBytes": 1,
        })
        df = (
            sess.read.parquet(tmpd)
            .where(E.GreaterThanOrEqual(col("ss_sold_date_sk"),
                                        lit(2_452_015)))
            .group_by("ss_quantity")
            .agg(A.agg(A.Sum(col("ss_wholesale_cost")), "s"),
                 A.agg(A.Count(col("ss_item_sk")), "c")))
        plan = sess._execute(df.node)
        return getattr(plan, "tpu_child", plan)

    return build


MESH_SHAPES = {
    "agg": mesh_shape_agg,
    "sort": mesh_shape_sort,
    "join": mesh_shape_join,
    "window": mesh_shape_window,
    "string": mesh_shape_string,
    "parquet": mesh_shape_parquet,
}


def run_mesh_lane(args) -> None:
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec import (
        InMemoryScanExec,
        TpuFilterExec,
        TpuHashAggregateExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E

    class X:
        pass

    X.InMemoryScanExec = InMemoryScanExec
    X.TpuFilterExec = TpuFilterExec
    X.TpuProjectExec = TpuProjectExec
    X.TpuHashAggregateExec = TpuHashAggregateExec

    n_dev = args.mesh
    import jax

    avail = len(jax.devices())
    if avail < n_dev:
        print(json.dumps({"metric": "mesh_scaling", "ok": False,
                          "error": f"need {n_dev} devices, have {avail}"}))
        sys.exit(1)
    base = {
        "spark.rapids.tpu.shuffle.mode": "ici",
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    }
    bench_logger = None
    if args.event_log:
        # same contract as the normal lane: exec-direct shapes emit
        # through the installed logger (per-chip '[chip k]' lanes ride
        # in it), the session-path shape picks the dir up from conf
        from spark_rapids_tpu import events as EV

        base["spark.rapids.tpu.eventLog.dir"] = args.event_log
        bench_logger = EV.EventLogger(RapidsConf(base))
        EV.install(bench_logger)
    conf_n = RapidsConf({**base, "spark.rapids.tpu.mesh.devices": n_dev})
    conf_1 = RapidsConf({**base, "spark.rapids.tpu.mesh.devices": 1})
    per_shape = {}
    total_violations = []
    for name in (s.strip() for s in args.shapes.split(",")):
        build = MESH_SHAPES[name](args.scale, conf_n, n_dev, T, E, A, X)
        r = _mesh_shape_result(build, conf_n, conf_1, n_dev, args.iters)
        per_shape[name] = r
        total_violations.extend(r["forecast_violations"])
        print(f"{name}: tpu={r['tpu_ms']}ms (1dev {r['tpu_ms_1dev']}ms) "
              f"spmd={r['device_ms']}ms (1dev {r['device_ms_1dev']}ms) "
              f"eff={r['scaling_efficiency']} "
              f"(raw {r['scaling_efficiency_raw']}) "
              f"violations={len(r['forecast_violations'])}",
              file=sys.stderr)
    if bench_logger is not None:
        from spark_rapids_tpu import events as EV

        trace_path = os.path.join(
            args.event_log, f"mesh-trace-{os.getpid()}.json")
        EV.export_chrome_trace(bench_logger.records(), trace_path)
        print(f"perfetto trace: {trace_path}", file=sys.stderr)
    speeds = [r["speedup_vs_1dev"] for r in per_shape.values()
              if r["speedup_vs_1dev"]]
    geo = (math.exp(sum(math.log(s) for s in speeds) / len(speeds))
           if speeds else None)
    host_par = min(n_dev, os.cpu_count() or 1)
    backend = jax.devices()[0].platform
    from spark_rapids_tpu import envinfo

    print(json.dumps({
        "metric": "mesh_scaling",
        "env": envinfo.environment_info(),
        "n_devices": n_dev,
        "backend": backend + (
            "-host-fallback" if backend == "cpu" else ""),
        "host_parallelism": host_par,
        "scale": args.scale,
        "per_shape": per_shape,
        "agg_scaling_efficiency": (per_shape.get("agg") or {}).get(
            "scaling_efficiency"),
        "geomean_speedup_vs_1dev": round(geo, 3) if geo else None,
        "forecast_violations": total_violations,
        "ok": not total_violations,
    }))


def run_serve_lane(args) -> None:
    """Serving throughput lane (--serve NxM): N sessions on N threads
    each submit M queries through the QueryScheduler against a budget
    sized to ~half the thread count's forecasts — so admission genuinely
    arbitrates — and the SAME workload is also submitted one-at-a-time
    from a single thread. Reports queries/sec and p50/p95 latency for
    both; the acceptance bar is concurrent qps > serialized qps (the
    device never idles between queries)."""
    import threading

    import pyarrow as _pa
    import pyarrow.parquet as _pq

    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr.expressions import col
    from spark_rapids_tpu.memory.catalog import BufferCatalog
    from spark_rapids_tpu.serve import QueryScheduler, SharedPlanCache
    from spark_rapids_tpu.sql import TpuSession

    try:
        n_threads, n_queries = (int(x) for x in args.serve.split("x"))
    except ValueError:
        raise SystemExit(f"--serve takes N_THREADSxM_QUERIES (e.g. 4x8), "
                         f"got {args.serve!r}")
    # parquet group-by workload: every query pays a host decode (GIL-
    # free native work) plus device compute, so the scheduler's phase
    # split has something real to overlap — query B's decode against
    # query A's device phase. The scan cache is OFF: a served fleet of
    # distinct user queries does not hit one warm file.
    n_rows = max(1 << 15, int(1_600_000 * args.scale))
    n_variants = 4
    tmpd = tempfile.mkdtemp(prefix="srtpu_serve_bench_")
    rng = np.random.default_rng(11)
    for v in range(n_variants):
        d = os.path.join(tmpd, f"v{v}")
        os.makedirs(d)
        _pq.write_table(_pa.table({
            "k": _pa.array(rng.integers(0, 64, n_rows).astype("int32")),
            "v": _pa.array(
                rng.integers(0, 100000, n_rows).astype("int64"))}),
            os.path.join(d, "t.parquet"),
            row_group_size=max(4096, n_rows // 8))
    settings = {
        "spark.rapids.tpu.serve.enabled": True,
        "spark.rapids.tpu.scan.deviceCache.enabled": False,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
        # serving tunes the semaphore up: admission bounds memory, the
        # permits bound compute concurrency (the reference runs
        # concurrentGpuTasks=2 for the same reason)
        "spark.rapids.tpu.sql.concurrentTpuTasks":
            max(2, min(n_threads, os.cpu_count() or 2)),
    }
    if args.event_log:
        settings["spark.rapids.tpu.eventLog.dir"] = args.event_log

    def query(sess, i):
        d = os.path.join(tmpd, f"v{i % n_variants}")
        return (sess.read.parquet(d).group_by("k")
                .agg(A.agg(A.Sum(col("v")), "sv"),
                     A.agg(A.Min(col("v")), "mn"),
                     A.agg(A.Max(col("v")), "mx")).collect())

    # size the budget from the workload's own forecast: room for about
    # half the threads, so the run exercises queueing without rejects
    probe = TpuSession(settings)
    query(probe, 0)
    an = probe.last_analysis
    forecast = an.peak_hbm if an is not None else None
    budget = (int(forecast * max(2.0, n_threads / 2))
              if forecast else 0)
    if budget:
        settings["spark.rapids.tpu.memory.hbm.budgetBytes"] = budget
    conf = RapidsConf(settings)
    BufferCatalog.reset(conf)
    QueryScheduler.reset(conf)
    SharedPlanCache.reset()

    warm = TpuSession(settings)
    for i in range(n_variants):
        query(warm, i)  # compile each distinct shape once (steady state)

    total = n_threads * n_queries

    # serialized one-at-a-time submission of the same workload
    ser_lat = []
    sess = TpuSession(settings)
    t0 = time.perf_counter()
    for i in range(total):
        q0 = time.perf_counter()
        query(sess, i)
        ser_lat.append(time.perf_counter() - q0)
    serialized_s = time.perf_counter() - t0

    # concurrent: N sessions on N threads
    lat = []
    errors = []
    lock = threading.Lock()

    def worker(ti):
        try:
            s = TpuSession(settings)
            for qi in range(n_queries):
                q0 = time.perf_counter()
                query(s, ti * n_queries + qi)
                with lock:
                    lat.append(time.perf_counter() - q0)
        except Exception as e:  # pragma: no cover
            with lock:
                errors.append(repr(e))

    threads = [threading.Thread(target=worker, args=(ti,))
               for ti in range(n_threads)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    concurrent_s = time.perf_counter() - t0

    def pct(xs, p):
        xs = sorted(xs)
        return xs[min(len(xs) - 1, int(p * len(xs)))] * 1e3 if xs else None

    st = QueryScheduler.instance().stats()
    qps = total / concurrent_s if concurrent_s else None
    ser_qps = total / serialized_s if serialized_s else None
    serve = {
        "threads": n_threads,
        "queries_per_thread": n_queries,
        "total_queries": total,
        "rows_per_query": n_rows,
        "scale": args.scale,
        "qps": round(qps, 2) if qps else None,
        "p50_ms": round(pct(lat, 0.5), 1) if lat else None,
        "p95_ms": round(pct(lat, 0.95), 1) if lat else None,
        "serialized_qps": round(ser_qps, 2) if ser_qps else None,
        "serialized_p50_ms": round(pct(ser_lat, 0.5), 1),
        "speedup_vs_serialized": (round(qps / ser_qps, 3)
                                  if qps and ser_qps else None),
        "budget_bytes": budget or None,
        "forecast_bytes": forecast,
        "admitted": st["admitted"], "queued": st["queued"],
        "rejected": st["rejected"],
        "bypass_admissions": st["bypass_admissions"],
        "peak_active": st["peak_active"],
        "peak_inflight_forecast": st["peak_inflight_forecast"],
        "errors": errors,
        # the HBM ledger's verdict on the stress (armed whenever the
        # lane ran with --event_log): nothing may outlive its query
        "leaked_buffers": BufferCatalog.get().ledger.stats()[
            "leaked_live"],
        # the zero-violation contract: every query completed, nothing
        # rejected, no bypass, no leaked buffers, and the summed
        # admitted forecasts never exceeded the budget
        "ok": not errors and st["rejected"] == 0
              and st["bypass_admissions"] == 0
              and BufferCatalog.get().ledger.stats()["leaked_live"] == 0
              and (st["peak_inflight_forecast"] <= budget
                   if budget else True),
    }
    from spark_rapids_tpu import envinfo

    print(json.dumps({
        "metric": "serve_throughput",
        "env": envinfo.environment_info(),
        # empty per_shape marks this as a bench-family json so
        # tpu_profile --diff routes it through diff_bench's serve gates
        "per_shape": {},
        "serve": serve,
    }))


# ---------------------------------------------------------------------------
# Cold-start lane: the serving-restart story in numbers. Each shape runs
# THREE times in FRESH subprocesses — (1) AOT program cache off: the
# full compile bill a restarted server pays today (compile_s_cold);
# (2) cache on over an empty directory: same bill + the store cost,
# populating the cache; (3) cache on over the now-warm directory:
# compile_s_warm, which the ROADMAP 5(a) exit criterion demands be
# ~zero (target warm_ratio <= 0.1; tpu_profile --diff gates the
# structural failures — warm compile misses, a ratio collapsed past
# 0.5, grown compile_s_warm vs the old round). Compile seconds come
# from the harvested xla_cost records (trace_ms + compile_ms per
# program — the same figures the roofline report sums), so cold and
# warm measure the identical definition.
# ---------------------------------------------------------------------------
def run_cold_start_child(args) -> None:
    """One shape, once, in this (fresh) process; prints one JSON line
    with the compile bill actually paid. SRTPU_AOT_DIR (set by the
    parent lane) turns the program cache on."""
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec import (
        InMemoryScanExec,
        TpuFilterExec,
        TpuHashAggregateExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.exec import base as EB
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E

    class X:
        pass

    X.InMemoryScanExec = InMemoryScanExec
    X.TpuFilterExec = TpuFilterExec
    X.TpuProjectExec = TpuProjectExec
    X.TpuHashAggregateExec = TpuHashAggregateExec

    conf_dict = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    aot_dir = os.environ.get("SRTPU_AOT_DIR", "")
    if aot_dir:
        conf_dict["spark.rapids.tpu.aotCache.dir"] = aot_dir
    conf = RapidsConf(conf_dict)
    if aot_dir:
        from spark_rapids_tpu.serve import program_cache

        program_cache.install(conf)
    xla_cost.FORCE_HARVEST = True
    name = args.cold_start_child
    fn = SHAPES[name]
    t0 = time.perf_counter()
    _cpu_t, tpu_t, _extra = fn(
        args.scale, 1, conf_dict if name == "parquet" else conf,
        T, E, A, X)
    wall_s = time.perf_counter() - t0
    recs = xla_cost.records_since(0)
    from spark_rapids_tpu import envinfo

    print(json.dumps({
        "shape": name,
        "env": envinfo.environment_info(),
        "compile_s": round(sum(
            (r.get("trace_ms") or 0) + (r.get("compile_ms") or 0)
            for r in recs) / 1e3, 3),
        "compile_miss": EB.COMPILE_COUNTER.total,
        "from_cache": sum(1 for r in recs if r.get("from_cache")),
        "programs": len(recs),
        "tpu_ms": round(tpu_t * 1e3, 1),
        "wall_s": round(wall_s, 3),
    }))


def _cold_start_spawn(name: str, args, aot_dir: str) -> dict:
    import subprocess

    env = dict(os.environ)
    if aot_dir:
        env["SRTPU_AOT_DIR"] = aot_dir
    else:
        env.pop("SRTPU_AOT_DIR", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__),
         "--cold-start-child", name, "--scale", str(args.scale)],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.abspath(__file__)))
    if proc.returncode != 0:
        raise RuntimeError(
            f"cold-start child {name} failed:\n{proc.stderr[-2000:]}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def run_cold_start_lane(args) -> None:
    """The parent NEVER touches jax: a chip belongs to one process at a
    time, so a parent that had asked ``jax.devices()`` would hold it and
    every child would fail or hang. ``env`` comes from the first child."""
    import shutil

    cache_dir = args.cold_start_dir
    if not cache_dir:
        # a FIXED path inside the checkout (the path is part of the
        # persistent-cache key), emptied when the lane starts
        cache_dir = os.path.join(
            os.path.dirname(os.path.abspath(__file__)),
            ".jax_compile_cache", "cold_start_aot")
        shutil.rmtree(cache_dir, ignore_errors=True)
        os.makedirs(cache_dir)
    env = None
    results = {}
    for name in (s.strip() for s in args.shapes.split(",")):
        cold = _cold_start_spawn(name, args, "")
        if env is None:
            env = cold["env"]
            print("env: " + json.dumps(env), file=sys.stderr)
        seed = _cold_start_spawn(name, args, cache_dir)
        warm = _cold_start_spawn(name, args, cache_dir)
        ratio = (round(warm["compile_s"] / cold["compile_s"], 4)
                 if cold["compile_s"] else None)
        results[name] = {
            "compile_s_cold": cold["compile_s"],
            "compile_s_seed": seed["compile_s"],
            "compile_s_warm": warm["compile_s"],
            "warm_ratio": ratio,
            "compile_miss_cold": cold["compile_miss"],
            "compile_miss_warm": warm["compile_miss"],
            "from_cache_warm": warm["from_cache"],
            "programs": cold["programs"],
            "tpu_ms_cold": cold["tpu_ms"],
            "tpu_ms_warm": warm["tpu_ms"],
        }
        print(
            f"{name}: compile cold={cold['compile_s']:.2f}s "
            f"warm={warm['compile_s']:.2f}s"
            + (f" (ratio {ratio})" if ratio is not None else "")
            + f" misses {cold['compile_miss']}->{warm['compile_miss']}"
            f" from_cache={warm['from_cache']}",
            file=sys.stderr)
    print(json.dumps({
        "metric": "cold_start_compile_seconds",
        "unit": f"s (fresh subprocess per lane; scale={args.scale})",
        "env": env,
        "cache_dir": cache_dir,
        "cold_start": results,
    }))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0)
    ap.add_argument("--iters", type=int, default=3)
    ap.add_argument("--shapes", type=str, default=",".join(SHAPES))
    ap.add_argument(
        "--mesh", type=int, default=0,
        help="run the six shapes as SPMD plans over an N-device mesh and "
             "report per-chip times + scaling efficiency vs a 1-device "
             "mesh (the MULTICHIP_*.json payload); forces an N-device "
             "virtual CPU mesh when no multi-chip accelerator is up")
    ap.add_argument(
        "--serve", type=str, default="",
        help="run the concurrent-serving lane instead of the shapes: "
             "N_THREADSxM_QUERIES (e.g. 4x8) submitted through the "
             "QueryScheduler under a budget sized to force queueing; "
             "prints queries/sec + p50/p95 latency vs serialized "
             "one-at-a-time submission (the BENCH json's 'serve' lane)")
    ap.add_argument(
        "--cold-start", action="store_true",
        help="run the cold-start lane instead of the shapes: each shape "
             "three times in FRESH subprocesses (AOT program cache off / "
             "populating / warm — spark.rapids.tpu.aotCache.dir) and "
             "report compile_s_cold vs compile_s_warm per shape (the "
             "BENCH json's 'cold_start' lane; the ROADMAP 5a target is "
             "warm/cold <= 0.1 with zero warm compile misses — "
             "tpu_profile --diff gates misses, a >0.5 ratio collapse, "
             "and compile_s_warm growth)")
    ap.add_argument(
        "--cold-start-dir", type=str, default="",
        help="reuse this AOT cache directory for the cold-start lane "
             "(default: a fresh temp dir, so 'warm' means warmed by the "
             "lane's own populating run)")
    ap.add_argument(
        "--cold-start-child", type=str, default="",
        help=argparse.SUPPRESS)  # internal: one fresh-process shape run
    ap.add_argument(
        "--donation", type=str, default="on", choices=("on", "off"),
        help="buffer donation at the analyzer-certified compile sites "
             "(plugin/donation.py). 'on' (default) also keeps the "
             "InMemoryScan host-resident so fresh per-execute uploads "
             "are exclusive and donatable; 'off' disables donation "
             "engine-wide — diff the two runs' donated_bytes / "
             "xla_peak_temp_bytes per shape to price the feature")
    ap.add_argument(
        "--event-log", type=str, default="",
        help="directory for a structured JSONL event log of the bench run "
             "(spark.rapids.tpu.eventLog.dir); inspect it offline with "
             "tools/tpu_profile.py, or --diff the emitted BENCH json "
             "against a previous round's")
    args = ap.parse_args()

    if args.cold_start_child:
        run_cold_start_child(args)
        return

    if args.cold_start:
        run_cold_start_lane(args)
        return

    if args.serve:
        run_serve_lane(args)
        return

    if args.mesh:
        # device-count flag must land before jax creates its CPU backend
        _stage_mesh_env(args.mesh)
        run_mesh_lane(args)
        return

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec import (
        InMemoryScanExec,
        TpuFilterExec,
        TpuHashAggregateExec,
        TpuProjectExec,
    )
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E

    class X:
        pass

    X.InMemoryScanExec = InMemoryScanExec
    X.TpuFilterExec = TpuFilterExec
    X.TpuProjectExec = TpuProjectExec
    X.TpuHashAggregateExec = TpuHashAggregateExec

    # order-insensitive float aggregation, as the reference's own benchmark
    # runs enable (spark.rapids.sql.variableFloatAgg.enabled)
    conf_dict = {"spark.rapids.tpu.sql.variableFloatAgg.enabled": True}
    if args.donation == "on":
        # hostResident makes every scan execute upload FRESH planes the
        # scan marks exclusive — without it the shapes' device-resident
        # scan batches are shared across iters and never donate
        conf_dict["spark.rapids.tpu.sql.inMemoryScan.hostResident"] = True
    else:
        conf_dict["spark.rapids.tpu.sql.donation.enabled"] = False
    # compiled-program cost plane: harvest XLA's own bytes/flops at every
    # compile miss (warm-up only — the timed iterations compile nothing)
    # so each shape reports hbm_frac_xla, the compiler-reported twin of
    # the layout-derived hbm_frac_device; the two bound the truth
    from spark_rapids_tpu import envinfo, hlo, xla_cost
    from spark_rapids_tpu.plugin import donation as _donation

    xla_cost.FORCE_HARVEST = True
    # environment provenance: stamped into the BENCH json top level (and
    # printed up front) so a later --diff can warn when two rounds came
    # from different hardware — the CPU-fallback-vs-device confusion
    # every round since r06 has had to caveat in prose
    env = envinfo.environment_info()
    print("env: " + envinfo.describe(env), file=sys.stderr)
    bench_logger = None
    if args.event_log:
        # event-log the whole bench: the session-path shapes pick the dir
        # up from conf, the exec-direct shapes from the installed logger
        from spark_rapids_tpu import events as EV

        conf_dict["spark.rapids.tpu.eventLog.dir"] = args.event_log
        bench_logger = EV.EventLogger(RapidsConf(conf_dict))
        EV.install(bench_logger)
    conf = RapidsConf(conf_dict)
    # hbm_frac_xla and hbm_frac_device must share ONE peak so the two
    # estimates bound the truth: the calibrated roofline conf when
    # declared, else the same v5e spec figure hbm_frac_device uses
    peak_gbps = conf.get(xla_cost.ROOFLINE_PEAK_HBM_GBPS) or HBM_GBPS
    xla_cost.set_conf_peaks(conf)

    results = {}
    details = {}
    extras = {}
    for name in (s.strip() for s in args.shapes.split(",")):
        fn = SHAPES[name]
        carg = conf_dict if name == "parquet" else conf
        mem_before = _mem_snapshot()
        cost_before = xla_cost.snapshot()
        hlo_before = hlo.snapshot()
        don_before = _donation.snapshot_counters()
        cpu_t, tpu_t, extra = fn(args.scale, args.iters, carg, T, E, A, X)
        don_delta = _donation.counters_since(don_before)
        extra["donated_bytes"] = sum(don_delta.values())
        if don_delta:
            extra["donated_bytes_by_site"] = don_delta
        extra.update(_mem_stats(mem_before))
        extra.update(_xla_stats(cost_before, extra.get("device_ms"),
                                peak_gbps))
        extra.update(_hlo_stats(hlo_before))
        extra["byte_amplification"] = byte_amplification(
            extra.get("xla_bytes_accessed"),
            extra.get("predicted_hbm_bytes"))
        sp = cpu_t / tpu_t
        results[name] = sp
        details[name] = {"speedup": round(sp, 2),
                         "cpu_ms": round(cpu_t * 1e3, 1),
                         "tpu_ms": round(tpu_t * 1e3, 1), **extra}
        extras.update({f"{name}_{k}": v for k, v in extra.items()})
        print(
            f"{name}: cpu={cpu_t*1e3:.1f}ms tpu={tpu_t*1e3:.1f}ms "
            f"speedup={sp:.2f}x {extra or ''}",
            file=sys.stderr,
        )

    if bench_logger is not None:
        # keep the Perfetto trace as an artifact NEXT TO the JSONL log:
        # "open the bench run with a trace on the agg and parquet shapes"
        # is now one --event-log flag instead of a manual export ritual
        from spark_rapids_tpu import events as EV

        trace_path = os.path.join(
            args.event_log, f"bench-trace-{os.getpid()}.json")
        EV.export_chrome_trace(bench_logger.records(), trace_path)
        print(f"perfetto trace: {trace_path}", file=sys.stderr)

    geomean = math.exp(sum(math.log(s) for s in results.values())
                       / len(results))
    # headline: the GEOMEAN speedup across all shapes (the honest figure;
    # per-shape breakdown — incl. device_ms/HBM roofline for EVERY shape —
    # rides along in per_shape). ``vs_baseline`` divides by the
    # reference's "4x typical" GPU-vs-CPU claim (docs/FAQ.md:60-66).
    # NOTE: every shape collects only its final small result (a host
    # round trip per pulled plane is the expensive direction) — exactly how the
    # reference's own harness measures (BenchUtils.scala:693 collects the
    # query result, and TPC-DS queries end in aggregates/limits).
    print(json.dumps({
        "metric": "query_shape_speedup_vs_cpu_geomean",
        "value": round(geomean, 3),
        "unit": f"x (pipeline wallclock; scale={args.scale})",
        "vs_baseline": round(geomean / 4.0, 3),
        "geomean_all_shapes": round(geomean, 3),
        "donation": args.donation,
        "env": env,
        "per_shape": details,
        **extras,
    }))


if __name__ == "__main__":
    main()
