"""Round-6 mesh SPMD tests: whole-plan absorption, the sharded scan, the
mesh window stage, per-shard plananalysis forecasts + cross-check, and the
conf-validated mesh builder.

Everything differential: mesh outputs compare against the single-device /
python oracle, and the forecast cross-check must report ZERO violations on
every materialized stage.
"""
import os
import sys
import tempfile

import numpy as np
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.plugin.plananalysis import (
    cross_check_mesh,
    forecast_mesh,
)
from spark_rapids_tpu.sql import TpuSession

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

ICI = {"spark.rapids.tpu.shuffle.mode": "ici",
       "spark.rapids.tpu.sql.autoBroadcastJoinThreshold": -1}

N_DEV = 8


def _conf(extra=None):
    return RapidsConf({**ICI, **(extra or {})})


def _mesh_stages(root):
    from spark_rapids_tpu.plugin.plananalysis import _mesh_stages_of

    return _mesh_stages_of(root)


def _rows(root):
    out = []
    for p in range(root.num_partitions):
        for b in root.execute_partition(p):
            out.extend(b.to_rows())
    return out


# ---------------------------------------------------------------------------
# sharded scan + whole-plan absorption
# ---------------------------------------------------------------------------
def _agg_plan(conf, parts, schema):
    from spark_rapids_tpu.exec import TpuFilterExec, TpuProjectExec
    from spark_rapids_tpu.exec.mesh import TpuMeshAggregateExec
    from spark_rapids_tpu.exec.scan import MeshShardedScanExec

    scan = MeshShardedScanExec(conf, parts, schema)
    filt = TpuFilterExec(conf, E.GreaterThanOrEqual(col("a"), lit(0)), scan)
    proj = TpuProjectExec(
        conf,
        [col("k"), E.Alias(E.Multiply(col("a"), lit(2)), "a2")], filt)
    return TpuMeshAggregateExec(
        conf, [col("k")],
        [A.agg(A.Sum(col("a2")), "s"), A.agg(A.Count(None), "c")], proj)


def _agg_data(n=4000, n_parts=N_DEV, seed=0):
    from spark_rapids_tpu.columnar.batch import schema_of

    rng = np.random.default_rng(seed)
    k = rng.integers(0, 23, n).astype(np.int32)
    a = rng.integers(-100, 100, n).astype(np.int64)
    schema = schema_of(k=T.INT, a=T.LONG)
    per = (n + n_parts - 1) // n_parts
    parts = []
    for p in range(n_parts):
        lo, hi = p * per, min((p + 1) * per, n)
        parts.append((
            [(k[lo:hi], np.ones(hi - lo, bool)),
             (a[lo:hi], np.ones(hi - lo, bool))], hi - lo))
    return parts, schema, k, a


def _agg_oracle(k, a):
    want = {}
    for kk, aa in zip(k, a):
        if aa < 0:
            continue
        s, c = want.get(int(kk), (0, 0))
        want[int(kk)] = (s + 2 * int(aa), c + 1)
    return sorted((kk, s, c) for kk, (s, c) in want.items())


def test_sharded_scan_whole_plan_agg_differential():
    """scan -> filter -> project -> mesh aggregate as ONE SPMD program fed
    by the sharded scan: results match the python oracle, the chain was
    absorbed, the staging took the no-host-gather path, and the per-shard
    forecast cross-check holds exactly."""
    parts, schema, k, a = _agg_data()
    plan = _agg_plan(_conf(), parts, schema)
    got = sorted(tuple(r) for r in _rows(plan))
    assert got == _agg_oracle(k, a)
    (stage,) = _mesh_stages(plan)
    act = stage.mesh_actuals["staging"]
    assert act["source"] == "sharded_scan"
    fc = forecast_mesh(plan)
    st = fc["stages"][0]
    assert st["staging"]["absorbed_steps"] == [
        "TpuFilterExec", "TpuProjectExec"]
    assert st["staging"]["source"] == "sharded_scan"
    assert cross_check_mesh(plan) == []


def test_whole_plan_off_restores_host_staging():
    """wholePlan.enabled=false: the chain executes on the default device
    and staging gathers through the host — same results."""
    parts, schema, k, a = _agg_data(seed=3)
    conf = _conf(
        {"spark.rapids.tpu.shuffle.mesh.wholePlan.enabled": False})
    plan = _agg_plan(conf, parts, schema)
    got = sorted(tuple(r) for r in _rows(plan))
    assert got == _agg_oracle(k, a)
    (stage,) = _mesh_stages(plan)
    assert stage.mesh_actuals["staging"]["source"] == "host"
    assert cross_check_mesh(plan) == []  # forecast mirrors the host path


def test_agg_exchange_cap_retry_still_correct():
    """More groups per shard than the starting exchange capacity: the
    stage must retry with a doubled cap (observable as extra compiled
    programs within the forecast bound) and still produce exact results."""
    from spark_rapids_tpu.columnar.batch import schema_of

    n = 4096
    rng = np.random.default_rng(7)
    # ~600 distinct groups per shard > the 128-row starting cap
    k = rng.integers(0, 5000, n).astype(np.int32)
    a = rng.integers(0, 100, n).astype(np.int64)
    schema = schema_of(k=T.INT, a=T.LONG)
    per = n // N_DEV
    parts = [
        ([(k[p * per:(p + 1) * per], np.ones(per, bool)),
          (a[p * per:(p + 1) * per], np.ones(per, bool))], per)
        for p in range(N_DEV)
    ]
    conf = _conf(
        {"spark.rapids.tpu.shuffle.mesh.aggExchangeCapacity": 128})
    plan = _agg_plan(conf, parts, schema)
    got = sorted(tuple(r) for r in _rows(plan))
    assert got == _agg_oracle(k, a)
    (stage,) = _mesh_stages(plan)
    assert stage.mesh_actuals["programs"] >= 2  # at least one retry
    assert stage.mesh_actuals["exchange_cap"] > 128
    assert cross_check_mesh(plan) == []


def test_parquet_sharded_scan_through_session():
    """The full product path: a session-planned parquet scan -> filter ->
    grouped aggregate lowers to a mesh stage fed by the sharded parquet
    scan (row groups round-robined onto shards)."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    n = 4000
    rng = np.random.default_rng(11)
    q = rng.integers(1, 11, n).astype(np.int32)
    c = rng.integers(0, 50, n).astype(np.int64)
    d = rng.integers(0, 100, n).astype(np.int32)
    tmpd = tempfile.mkdtemp(prefix="srtpu_meshpq_")
    t = pa.table({"q": pa.array(q), "c": pa.array(c), "d": pa.array(d)})
    pq.write_table(t, os.path.join(tmpd, "t.parquet"),
                   row_group_size=n // 16)
    # split per row group (the default 2GB coalescing target would pack
    # this small file into ONE split -> single partition -> no mesh)
    s = TpuSession({**ICI,
                    "spark.rapids.tpu.sql.reader.batchSizeBytes": 2048})
    df = (s.read.parquet(tmpd)
          .where(E.GreaterThanOrEqual(col("d"), lit(50)))
          .group_by("q")
          .agg(A.agg(A.Sum(col("c")), "s"), A.agg(A.Count(None), "n")))
    got = sorted(df.collect())
    want = {}
    for qq, cc, dd in zip(q, c, d):
        if dd < 50:
            continue
        sv, nv = want.get(int(qq), (0, 0))
        want[int(qq)] = (sv + int(cc), nv + 1)
    assert got == sorted((qq, sv, nv) for qq, (sv, nv) in want.items())
    plan = s.last_executed_plan.tree_string()
    assert "TpuMeshAggregateExec" in plan, plan
    root = s.last_executed_plan
    stages = _mesh_stages(root)
    assert stages, plan
    assert stages[0].mesh_actuals["staging"]["source"] == "sharded_scan"
    assert cross_check_mesh(root) == []


def test_mesh_window_differential():
    """The mesh window stage (hash exchange on the partition keys + the
    single-device window body per shard) matches the gather-everything
    single-partition path row for row."""
    from spark_rapids_tpu.expr import windows as W

    n = 1000
    rng = np.random.default_rng(13)
    data = {
        "k": [int(x) for x in rng.integers(0, 17, n)],
        "ts": [int(x) for x in rng.permutation(n)],
        "v": [int(x) for x in rng.integers(0, 50, n)],
    }
    schema = T.StructType([
        T.StructField("k", T.INT), T.StructField("ts", T.LONG),
        T.StructField("v", T.LONG)])

    def query(s):
        spec = W.WindowSpec(
            partition_by=(col("k"),), order_by=(col("ts"),),
            orders=((True, True),))
        return s.create_dataframe(
            data, schema, num_partitions=N_DEV).with_windows(
            W.WindowExpression(A.Sum(col("v")), spec, "rs"),
            W.WindowExpression(W.RowNumber(), spec, "rn"))

    s_mesh = TpuSession(ICI)
    got = sorted(query(s_mesh).collect())
    assert "TpuMeshWindowExec" in s_mesh.last_executed_plan.tree_string()
    s_host = TpuSession({"spark.rapids.tpu.shuffle.mode": "host"})
    want = sorted(query(s_host).collect())
    assert "TpuMeshWindowExec" not in s_host.last_executed_plan.tree_string()
    assert got == want
    assert cross_check_mesh(s_mesh.last_executed_plan) == []


def test_mesh_window_string_partition_falls_back():
    """String partition keys keep the single-partition gather path (the
    mesh window is gated to fixed-width direct references)."""
    from spark_rapids_tpu.expr import windows as W

    data = {"s": ["a", "b", "a", "c"] * 8, "v": list(range(32))}
    schema = T.StructType([
        T.StructField("s", T.STRING), T.StructField("v", T.LONG)])
    s = TpuSession(ICI)
    spec = W.WindowSpec(partition_by=(col("s"),), order_by=(col("v"),),
                        orders=((True, True),))
    df = s.create_dataframe(data, schema, num_partitions=4).with_windows(
        W.WindowExpression(A.Sum(col("v")), spec, "rs"))
    rows = df.collect()
    assert "TpuMeshWindowExec" not in s.last_executed_plan.tree_string()
    assert len(rows) == 32


# ---------------------------------------------------------------------------
# get_mesh conf (mesh.devices)
# ---------------------------------------------------------------------------
def test_get_mesh_conf_cap_and_memoization():
    from spark_rapids_tpu.parallel.mesh import get_mesh

    m2 = get_mesh(conf=RapidsConf({"spark.rapids.tpu.mesh.devices": 2}))
    assert int(m2.devices.size) == 2
    assert get_mesh(2) is m2  # memoized per (count, device identity)
    m_all = get_mesh(conf=RapidsConf({}))
    assert int(m_all.devices.size) == len(__import__("jax").devices())
    # legacy shuffle.meshSize still honored when mesh.devices unset
    m3 = get_mesh(conf=RapidsConf(
        {"spark.rapids.tpu.shuffle.meshSize": 3}))
    assert int(m3.devices.size) == 3
    # mesh.devices wins over meshSize
    m4 = get_mesh(conf=RapidsConf(
        {"spark.rapids.tpu.mesh.devices": 4,
         "spark.rapids.tpu.shuffle.meshSize": 2}))
    assert int(m4.devices.size) == 4


def test_get_mesh_too_many_devices_is_an_error():
    from spark_rapids_tpu.parallel.mesh import get_mesh

    with pytest.raises(ValueError, match="mesh.devices"):
        get_mesh(conf=RapidsConf(
            {"spark.rapids.tpu.mesh.devices": 4096}))


# ---------------------------------------------------------------------------
# per-shard observability: events + Perfetto tracks
# ---------------------------------------------------------------------------
def test_per_shard_spans_and_transfers_in_event_log():
    from spark_rapids_tpu import events as EV

    logger = EV.EventLogger(RapidsConf(
        {"spark.rapids.tpu.eventLog.enabled": True}))
    EV.install(logger)
    try:
        parts, schema, k, a = _agg_data(n=800, seed=21)
        plan = _agg_plan(_conf(), parts, schema)
        _rows(plan)
    finally:
        EV.uninstall()
    recs = logger.records()
    # every emitted field is declared: required by EVENT_TYPES, optional
    # by EVENT_OPTIONAL_FIELDS (the registry stays the source of truth)
    for r in recs:
        et = r.get("event")
        declared = set(EV.EVENT_TYPES[et]) | set(
            EV.EVENT_OPTIONAL_FIELDS.get(et, ())) | {"ts", "event", "tid"}
        assert set(r) <= declared, (et, sorted(set(r) - declared))
    spans = [r for r in recs if r.get("event") == "op_span"
             and r.get("shard") is not None]
    shards = sorted({r["shard"] for r in spans})
    assert shards == list(range(N_DEV))
    xfers = [r for r in recs if r.get("event") == "transfer"
             and r.get("shard") is not None]
    assert sorted({r["shard"] for r in xfers}) == list(range(N_DEV))
    trace = EV.chrome_trace(recs)
    names = {e["args"]["name"] for e in trace["traceEvents"]
             if e.get("ph") == "M"}
    for sh in range(N_DEV):
        assert any(f"[chip {sh}]" in n for n in names), names
