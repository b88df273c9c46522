"""Shuffle layer tests: partitioners, serializer, exchange execs,
multi-partition plans through the planner.

Reference analog: GpuPartitioningSuite / GpuSinglePartitioningSuite,
GpuColumnarBatchSerializer round-trips, and the join/aggregate integration
tests that exercise GpuShuffleExchangeExec.
"""
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from spark_rapids_tpu import types as T
from spark_rapids_tpu.columnar.batch import ColumnarBatch
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.expr.eval import ColV
from spark_rapids_tpu.ops import hashing
from spark_rapids_tpu.shuffle.partition import (
    HashPartitioning,
    RangePartitioning,
    RoundRobinPartitioning,
    SinglePartitioning,
    partition_cols,
)
from spark_rapids_tpu.shuffle.serializer import (
    deserialize_batch,
    serialize_batch,
)

from harness import assert_tpu_and_cpu_equal, compare_rows


# ---------------------------------------------------------------------------
# partition kernel
# ---------------------------------------------------------------------------
def test_partition_cols_offsets_and_stability():
    cap, n, P = 64, 50, 4
    rng = np.random.default_rng(0)
    pids = rng.integers(0, P, cap).astype(np.int32)
    data = np.arange(cap, dtype=np.int64)
    cols, offsets = partition_cols(
        [ColV(jnp.asarray(data), jnp.ones(cap, bool))],
        jnp.asarray(pids), n, P)
    offsets = np.asarray(offsets)
    out = np.asarray(cols[0].data)
    assert offsets[P] == n
    for j in range(P):
        rows = out[offsets[j]: offsets[j + 1]]
        want = [i for i in range(n) if pids[i] == j]
        assert list(rows) == want  # stable within partition


def test_hash_partitioning_matches_spark_pmod():
    # partition ids must be pmod(murmur3(key), n) — bit-exact vs the
    # hashing kernel (itself differentially tested against Spark vectors)
    cap = 32
    keys = np.array([0, 1, -5, 7, 42, 2**31 - 1, -(2**31), 13] * 4, np.int32)
    col = ColV(jnp.asarray(keys), jnp.ones(cap, bool))
    schema = T.StructType([T.StructField("k", T.INT)])
    part = HashPartitioning([0], 5)
    pids = np.asarray(part.partition_ids(
        [col], schema, jnp.ones(cap, bool), 0))
    h = np.asarray(hashing.murmur3([col], [T.INT]))
    want = ((h % 5) + 5) % 5
    assert (pids == want).all()


def test_round_robin_covers_all_partitions():
    schema = T.StructType([T.StructField("k", T.INT)])
    part = RoundRobinPartitioning(3)
    pids = np.asarray(part.partition_ids(
        [ColV(jnp.zeros(9, jnp.int32), jnp.ones(9, bool))],
        schema, jnp.ones(9, bool), map_index=1))
    assert sorted(set(pids.tolist())) == [0, 1, 2]
    assert (np.bincount(pids, minlength=3) == 3).all()


def test_range_partitioning_orders_partitions():
    from spark_rapids_tpu.ops.sort import SortOrder

    cap = 64
    keys = np.linspace(-100, 100, cap).astype(np.int64)
    rng = np.random.default_rng(1)
    rng.shuffle(keys)
    col = ColV(jnp.asarray(keys), jnp.ones(cap, bool))
    schema = T.StructType([T.StructField("k", T.LONG)])
    part = RangePartitioning([0], [SortOrder(True, None)], 4,
                             bounds=[[-50, 0, 50]])
    pids = np.asarray(part.partition_ids(
        [col], schema, jnp.ones(cap, bool), 0))
    for k, p in zip(keys, pids):
        want = 0 if k < -50 else 1 if k < 0 else 2 if k < 50 else 3
        assert p == want, (k, p, want)


def test_range_partitioning_null_bounds():
    from spark_rapids_tpu.ops.sort import SortOrder

    # nulls sort first (ASC): null bound separates nulls from values
    keys = np.array([5, -3, 0, 7], np.int64)
    valid = np.array([True, False, True, False])
    col = ColV(jnp.asarray(keys), jnp.asarray(valid))
    schema = T.StructType([T.StructField("k", T.LONG)])
    part = RangePartitioning([0], [SortOrder(True, None)], 2, bounds=[[None]])
    pids = np.asarray(part.partition_ids(
        [col], schema, jnp.ones(4, bool), 0))
    # nulls <= null bound -> partition 1? Spark: bound is inclusive-left;
    # null rows compare equal to the null bound -> partition 1; non-null
    # rows are greater than a null bound -> partition 1 too... except the
    # semantics we implement: pid = #bounds <= row; null == null -> 1,
    # values > null -> 1. Everything lands right of a null bound.
    assert (pids == 1).all()


# ---------------------------------------------------------------------------
# serializer
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("codec", ["none", "zstd"])
def test_serializer_round_trip(codec):
    schema = T.StructType([
        T.StructField("i", T.INT),
        T.StructField("l", T.LONG),
        T.StructField("d", T.DOUBLE),
        T.StructField("b", T.BOOLEAN),
        T.StructField("s", T.STRING),
    ])
    data = {
        "i": [1, None, -7, 2**31 - 1],
        "l": [None, 2**40, -1, 0],
        "d": [1.5, float("nan"), None, -0.0],
        "b": [True, False, None, True],
        "s": ["héllo", "", None, "x" * 300],
    }
    b = ColumnarBatch.from_pydict(data, schema)
    wire = serialize_batch(b, codec)
    back = deserialize_batch(wire)
    assert back.schema.names == schema.names
    got = back.to_rows()
    want = b.to_rows()
    compare_rows(want, got, ignore_order=False)


def test_serializer_empty_batch():
    schema = T.StructType([T.StructField("i", T.INT)])
    b = ColumnarBatch.from_pydict({"i": []}, schema)
    back = deserialize_batch(serialize_batch(b))
    assert back.num_rows == 0
    assert back.to_rows() == []


# ---------------------------------------------------------------------------
# exchange through the planner (differential, multi-partition inputs)
# ---------------------------------------------------------------------------
def _rand_kv(n, nkeys, seed, null_frac=0.1):
    rnd = random.Random(seed)
    return {
        "k": [
            rnd.randint(0, nkeys) if rnd.random() > null_frac else None
            for _ in range(n)
        ],
        "v": [
            rnd.randint(-1000, 1000) if rnd.random() > null_frac else None
            for _ in range(n)
        ],
    }


_KV_SCHEMA = T.StructType(
    [T.StructField("k", T.INT), T.StructField("v", T.LONG)])


@pytest.mark.parametrize("parts", [2, 4])
def test_partitioned_aggregate_through_exchange(parts):
    data = _rand_kv(800, 30, seed=parts)

    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(data, _KV_SCHEMA, num_partitions=parts)
        .group_by("k")
        .agg(A.agg(A.Sum(E.col("v")), "s"), A.agg(A.Count(E.col("v")), "c"),
             A.agg(A.Min(E.col("v")), "mn"), A.agg(A.Max(E.col("v")), "mx")),
    )


def test_partitioned_grand_aggregate_single_exchange():
    data = _rand_kv(500, 10, seed=7)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(data, _KV_SCHEMA, num_partitions=3)
        .agg(A.agg(A.Sum(E.col("v")), "s"), A.agg(A.Count(E.col("v")), "c")),
    )


def test_partitioned_sort_through_range_exchange():
    data = _rand_kv(600, 200, seed=11)
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(data, _KV_SCHEMA, num_partitions=4)
        .order_by("k"),
        ignore_order=False,
    )


@pytest.mark.parametrize("how", ["inner", "left", "right", "full", "semi", "anti"])
def test_partitioned_join_through_exchange(how):
    left = _rand_kv(400, 40, seed=13)
    right_schema = T.StructType(
        [T.StructField("k", T.INT), T.StructField("w", T.LONG)])
    rnd = random.Random(17)
    right = {
        "k": [rnd.randint(0, 40) for _ in range(120)],
        "w": [rnd.randint(0, 9) for _ in range(120)],
    }

    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(left, _KV_SCHEMA, num_partitions=3)
        .join(s.create_dataframe(right, right_schema, num_partitions=2),
              on="k", how=how),
    )


def test_partitioned_string_groupby_through_exchange():
    words = ["alpha", "beta", "gamma", "", None, "δελτα", "w" * 80]
    rnd = random.Random(23)
    schema = T.StructType(
        [T.StructField("s", T.STRING), T.StructField("v", T.LONG)])
    data = {
        "s": [rnd.choice(words) for _ in range(500)],
        "v": [rnd.randint(0, 100) for _ in range(500)],
    }
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(data, schema, num_partitions=4)
        .group_by("s")
        .agg(A.agg(A.Count(E.col("v")), "c"), A.agg(A.Sum(E.col("v")), "sv")),
    )


def test_exchange_host_transport_and_codec():
    data = _rand_kv(400, 20, seed=29)
    conf = {
        "spark.rapids.tpu.shuffle.transport.class": "host",
        "spark.rapids.tpu.shuffle.compression.codec": "zstd",
    }
    assert_tpu_and_cpu_equal(
        lambda s: s.create_dataframe(data, _KV_SCHEMA, num_partitions=3)
        .group_by("k")
        .agg(A.agg(A.Sum(E.col("v")), "s"), A.agg(A.Count(E.col("v")), "c")),
        conf=conf,
    )


def test_shuffle_partitions_conf_sets_reducer_count():
    from spark_rapids_tpu.sql.session import TpuSession

    data = _rand_kv(300, 15, seed=31)
    # reducer-count conf applies to the single-host exchange; the mesh path
    # derives its shard count from the device mesh instead
    s = TpuSession({"spark.rapids.tpu.sql.shuffle.partitions": 7,
                    "spark.rapids.tpu.shuffle.mode": "host"})
    df = s.create_dataframe(data, _KV_SCHEMA, num_partitions=2)
    out = df.group_by("k").agg(A.agg(A.Count(), "c")).collect()
    # find the exchange in the executed plan
    plan = s.last_executed_plan.tree_string()
    assert "n=7" in plan, plan
    s1 = TpuSession()
    out1 = (
        s1.create_dataframe(data, _KV_SCHEMA, num_partitions=1)
        .group_by("k").agg(A.agg(A.Count(), "c")).collect()
    )
    compare_rows(out1, out)


# ---------------------------------------------------------------------------
# the map side partitions the rows a batch holds, not the slots it was given
# ---------------------------------------------------------------------------
_LIVE_SCHEMA = T.StructType([
    T.StructField("i", T.INT), T.StructField("d", T.DOUBLE),
    T.StructField("n", T.LONG), T.StructField("s", T.STRING)])
_LIVE_ROWS = 150   # bucket 256
_GIVEN_SLOTS = 4096


def _live_batch(capacity=None, lazy=False, rows=_LIVE_ROWS):
    """``rows`` rows of an int, a float64, a nullable and a string column
    at ``capacity`` slots (their own bucket by default), the count a
    device scalar where ``lazy``: an aggregate's or a filter's output."""
    from spark_rapids_tpu.columnar.column import HostColumn

    rnd = random.Random(5)
    data = {
        "i": [rnd.randint(-40, 40) for _ in range(rows)],
        "d": [rnd.random() * 1e6 for _ in range(rows)],
        "n": [rnd.randint(0, 9) if rnd.random() > 0.3 else None
              for _ in range(rows)],
        "s": [rnd.choice(["", "alpha", "βήτα", "w" * 70, None])
              for _ in range(rows)],
    }
    cols = [HostColumn.from_pylist(data[f.name], f.dataType)
            .to_device(capacity, name=f.name) for f in _LIVE_SCHEMA.fields]
    return ColumnarBatch(cols, _LIVE_SCHEMA,
                         jnp.int32(rows) if lazy else rows)


def _live_partitioning(kind):
    from spark_rapids_tpu.ops.sort import SortOrder

    return {
        "hash": lambda: HashPartitioning([0, 3], 5),
        "range": lambda: RangePartitioning(
            [0, 3], [SortOrder(True, None), SortOrder(False, None)], 4),
        "round_robin": lambda: RoundRobinPartitioning(3),
        "single": lambda: SinglePartitioning(),
    }[kind]()


class _Counts:
    """Stands in for a section's span: keeps the counts set on it."""
    on = True

    def __init__(self):
        self.counts = {}

    def set(self, **counts):
        self.counts.update(counts)


def _exchanged(kind, batch, monkeypatch):
    """(rows of every reduce partition in order, the map span's counts,
    the keys the map side asked ``jit_exchange`` by, the slots of the
    planes ``partition_cols`` was handed) of one batch through an
    exchange of its own."""
    import contextlib

    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec import InMemoryScanExec
    from spark_rapids_tpu.exec import exchange as X
    from spark_rapids_tpu.expr.values import val_capacity

    conf = RapidsConf({})
    ex = X.TpuShuffleExchangeExec(
        conf, InMemoryScanExec(conf, [[batch]], _LIVE_SCHEMA),
        _live_partitioning(kind))
    spans, keys, slots = {}, [], []
    real_map_fn, real_partition = X.TpuShuffleExchangeExec._map_fn, \
        X.partition_cols

    def map_fn(self, sig, cap, schema, sml):
        keys.append((sig, cap, self.num_partitions, sml, schema,
                     self._part_cache_key()))
        return real_map_fn(self, sig, cap, schema, sml)

    def partition(cols, pids, num_rows, P):
        slots.append({val_capacity(c) for c in cols} | {pids.shape[0]})
        return real_partition(cols, pids, num_rows, P)

    with monkeypatch.context() as m:
        m.setattr(ex, "op_timed", lambda section="", *a, **k:
                  contextlib.nullcontext(spans.setdefault(section, _Counts())))
        m.setattr(X.TpuShuffleExchangeExec, "_map_fn", map_fn)
        m.setattr(X, "partition_cols", partition)
        X._MAP_CACHE.clear()  # the map program is traced under the spy
        try:
            parts = [[r for b in ex.execute_partition(p) for r in b.to_rows()]
                     for p in range(ex.num_partitions)]
        finally:
            X._MAP_CACHE.clear()
    return parts, spans.get("map", _Counts()).counts, keys, slots


_KINDS = ["hash", "range", "round_robin", "single"]


@pytest.mark.parametrize("kind", _KINDS)
def test_exchange_of_a_sparse_batch_is_the_exchange_of_its_rows(
        kind, monkeypatch):
    # few live rows at a large capacity, the count a device scalar: the
    # same rows reach the same partitions in the same order as from a
    # batch of their own bucket
    own, own_counts, _, _ = _exchanged(kind, _live_batch(), monkeypatch)
    sparse, counts, keys, _ = _exchanged(
        kind, _live_batch(_GIVEN_SLOTS, lazy=True), monkeypatch)
    assert sum(len(p) for p in sparse) == _LIVE_ROWS
    for got, want in zip(sparse, own):
        compare_rows(want, got, ignore_order=False)
    assert counts["cut"] == 1 and own_counts["cut"] == 0
    assert counts["slots"] == own_counts["slots"] == 256
    assert counts["rows"] == _LIVE_ROWS and counts["inputs"] == 1
    assert [k[1] for k in keys] == [256]


@pytest.mark.parametrize("kind", _KINDS)
def test_exchange_of_a_dense_batch_resolves_the_key_it_did(
        kind, monkeypatch):
    from spark_rapids_tpu.exec import exchange as X
    from spark_rapids_tpu.exec.base import batch_signature

    monkeypatch.setattr(X, "_live_prefix", None)  # not to be called
    for rows, lazy in ((256, False), (_LIVE_ROWS, True)):
        batch = _live_batch(lazy=lazy, rows=rows)
        # the key as the map side built it before it looked at the count
        before = (batch_signature(batch), batch.capacity)
        parts, counts, keys, _ = _exchanged(kind, batch, monkeypatch)
        assert sum(len(p) for p in parts) == rows
        assert [k[:2] for k in keys] == [before]
        assert counts["cut"] == 0 and counts["slots"] == batch.capacity


@pytest.mark.parametrize("kind", _KINDS)
def test_exchange_of_a_batch_of_no_rows_writes_no_piece(kind, monkeypatch):
    from spark_rapids_tpu.shuffle.transport import DeviceShuffleTransport

    wrote = []
    monkeypatch.setattr(DeviceShuffleTransport, "write",
                        lambda self, *a: wrote.append(a))
    batch = _live_batch(_GIVEN_SLOTS, rows=0)
    batch = ColumnarBatch(batch.columns, _LIVE_SCHEMA, jnp.int32(0))
    parts, counts, keys, slots = _exchanged(kind, batch, monkeypatch)
    assert not any(parts) and not wrote
    assert not keys and not slots  # and runs no program for it
    assert counts == {"bytes": 0, "rows": 0, "inputs": 1, "slots": 0,
                      "cut": 0}


@pytest.mark.parametrize("kind", _KINDS)
def test_partition_cols_is_handed_the_bucket_of_the_rows_held(
        kind, monkeypatch):
    # the case that goes red if the cut is lost: every plane the sort and
    # the gather see has choose_capacity(rows) slots, not the batch's
    from spark_rapids_tpu.columnar.column import choose_capacity

    _, _, _, slots = _exchanged(
        kind, _live_batch(_GIVEN_SLOTS, lazy=True), monkeypatch)
    assert slots == [{choose_capacity(_LIVE_ROWS)}]


# ---------------------------------------------------------------------------
# TPC-H Q1's plan over several scan partitions: string keys through both
# exchanges (the benchmark's cell lineitem_full.q1)
# ---------------------------------------------------------------------------
_Q1_SCHEMA = T.StructType([
    T.StructField("l_quantity", T.DOUBLE),
    T.StructField("l_extendedprice", T.DOUBLE),
    T.StructField("l_discount", T.DOUBLE),
    T.StructField("l_tax", T.DOUBLE),
    T.StructField("l_returnflag", T.STRING),
    T.StructField("l_linestatus", T.STRING),
    T.StructField("l_shipdate", T.DATE)])
_Q1_HOST = {"spark.rapids.tpu.shuffle.mode": "host",
            "spark.rapids.tpu.sql.variableFloatAgg.enabled": True}


def _q1_rows(n, seed):
    rnd = random.Random(seed)
    flags = [("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    picked = [rnd.choice(flags) for _ in range(n)]
    return {
        "l_quantity": [float(rnd.randint(1, 50)) for _ in range(n)],
        "l_extendedprice": [rnd.randint(90_000, 10_000_000) / 100.0
                            for _ in range(n)],
        "l_discount": [rnd.randint(0, 10) / 100.0 for _ in range(n)],
        "l_tax": [rnd.randint(0, 8) / 100.0 for _ in range(n)],
        "l_returnflag": [p[0] for p in picked],
        "l_linestatus": [p[1] for p in picked],
        # days since 1970: some rows ship after Q1's cut of 1998-09-02
        "l_shipdate": [rnd.randint(10_300, 10_500) for _ in range(n)]}


def _q1(df):
    one = E.lit(1.0)
    disc_price = E.Multiply(
        E.col("l_extendedprice"), E.Subtract(one, E.col("l_discount")))
    charge = E.Multiply(disc_price, E.Add(one, E.col("l_tax")))
    return (
        df.where(E.LessThanOrEqual(E.col("l_shipdate"),
                                   E.Literal(10_471, T.DATE)))
        .group_by("l_returnflag", "l_linestatus")
        .agg(A.agg(A.Sum(E.col("l_quantity")), "sum_qty"),
             A.agg(A.Sum(E.col("l_extendedprice")), "sum_base_price"),
             A.agg(A.Sum(disc_price), "sum_disc_price"),
             A.agg(A.Sum(charge), "sum_charge"),
             A.agg(A.Average(E.col("l_quantity")), "avg_qty"),
             A.agg(A.Average(E.col("l_extendedprice")), "avg_price"),
             A.agg(A.Average(E.col("l_discount")), "avg_disc"),
             A.agg(A.Count(E.col("l_quantity")), "count_order"))
        .order_by("l_returnflag", "l_linestatus"))


def test_q1_over_several_partitions_matches_the_oracle_in_order():
    # partial -> hash exchange on two string keys -> final -> range
    # exchange on the same keys -> a local sort a partition, collected in
    # the partitions' order
    data = _q1_rows(900, seed=37)
    assert_tpu_and_cpu_equal(
        lambda s: _q1(s.create_dataframe(data, _Q1_SCHEMA,
                                         num_partitions=3)),
        conf=_Q1_HOST, ignore_order=False, approx_float=True)


def test_q1s_second_run_compiles_nothing_and_keeps_no_plan_alive():
    """The exchanges and the sort are built anew for every query's plan,
    and the range exchange's sampled bounds are constants in its
    program's key: the same data samples the same bounds, so the second
    run of the query finds every program in a process-wide cache (the
    sort's too since PR 37) and no exec of the first run's plan is kept
    alive by a cache of its own."""
    from spark_rapids_tpu.exec.base import compile_snapshot
    from spark_rapids_tpu.sql.session import TpuSession

    data = _q1_rows(700, seed=41)
    sess = TpuSession(_Q1_HOST)

    def run():
        df = _q1(sess.create_dataframe(data, _Q1_SCHEMA, num_partitions=3))
        rows = df.collect()
        return rows, sess.last_executed_plan.tree_string()

    first, plan = run()
    assert "HashPartitioning(keys=[0, 1], n=3)" in plan, plan
    assert "RangePartitioning(keys=[0, 1], n=3)" in plan, plan
    assert "mode=partial" in plan and "mode=final" in plan
    assert not sess.plan_fallbacks()
    assert [r[:2] for r in first] == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    _, before = compile_snapshot()
    second, _ = run()
    _, after = compile_snapshot()
    assert second == first
    new = {site: n - before.get(site, 0) for site, n in after.items()
           if n != before.get(site, 0)}
    assert new == {}, new
    # a per-instance program cache registered with the pipeline caches'
    # sweep kept every query's sort exec, and with it the query's plan
    import gc

    from spark_rapids_tpu.exec.sort import TpuSortExec

    for _ in range(3):
        run()
    gc.collect()
    live = [o for o in gc.get_objects() if isinstance(o, TpuSortExec)]
    assert len(live) <= 2, len(live)  # the last plan, and one being built
