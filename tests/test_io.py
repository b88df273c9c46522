"""I/O layer tests: parquet/CSV/ORC scans, pruning, partition values,
reader strategies, writer round trips — differential vs the CPU oracle.

Reference analog: parquet_test.py / orc_test.py / csv_test.py in
integration_tests, ParquetWriterSuite.
"""
import datetime
import decimal
import os
import random

import pyarrow as pa
import pyarrow.orc as paorc
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu import types as T
from spark_rapids_tpu.expr.expressions import col, lit
from spark_rapids_tpu.expr import aggregates as A
from spark_rapids_tpu.expr import expressions as E
from spark_rapids_tpu.sql.session import TpuSession, _SCANNER_CACHE

from harness import assert_tpu_and_cpu_equal, compare_rows


@pytest.fixture
def tmpd(tmp_path):
    return str(tmp_path)


def _mixed_table(n=2000, seed=0):
    rnd = random.Random(seed)
    return pa.table({
        "k": pa.array(
            [rnd.randint(0, 50) if rnd.random() > 0.05 else None
             for _ in range(n)], pa.int32()),
        "v": pa.array(
            [rnd.random() * 100 if rnd.random() > 0.05 else None
             for _ in range(n)], pa.float64()),
        "s": pa.array(
            [rnd.choice(["a", "bb", None, "ccc", "ddd€", ""])
             for _ in range(n)], pa.string()),
        "l": pa.array(
            [rnd.randint(-2**40, 2**40) for _ in range(n)], pa.int64()),
    })


def test_parquet_scan_differential(tmpd):
    t = _mixed_table()
    pq.write_table(t, f"{tmpd}/a.parquet", row_group_size=500)
    pq.write_table(t.slice(0, 700), f"{tmpd}/b.parquet", row_group_size=250)
    assert_tpu_and_cpu_equal(lambda s: s.read.parquet(tmpd))


def test_parquet_all_types_round_trip(tmpd):
    t = pa.table({
        "i8": pa.array([1, None, -128], pa.int8()),
        "i16": pa.array([300, None, -2], pa.int16()),
        "b": pa.array([True, None, False], pa.bool_()),
        "f": pa.array([1.5, None, float("nan")], pa.float32()),
        "dt": pa.array(
            [datetime.date(2020, 2, 29), None, datetime.date(1969, 12, 31)],
            pa.date32()),
        "ts": pa.array(
            [datetime.datetime(2021, 5, 1, 12, 30), None,
             datetime.datetime(1970, 1, 1)], pa.timestamp("us")),
        "dec": pa.array(
            [decimal.Decimal("12.34"), None, decimal.Decimal("-0.01")],
            pa.decimal128(9, 2)),
        "bin": pa.array([b"\x00\xff", None, b""], pa.binary()),
    })
    pq.write_table(t, f"{tmpd}/typed.parquet")
    assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(f"{tmpd}/typed.parquet"),
        conf={"spark.rapids.tpu.sql.decimalType.enabled": True},
    )


def test_parquet_column_pruning(tmpd):
    pq.write_table(_mixed_table(), f"{tmpd}/a.parquet")
    got = assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(f"{tmpd}/a.parquet", columns=["s", "k"]))
    assert len(got[0]) == 2


def test_parquet_row_group_pruning_correct_and_effective(tmpd):
    t = pa.table({"k": pa.array(range(10000), pa.int64())})
    pq.write_table(t, f"{tmpd}/a.parquet", row_group_size=1000)
    _SCANNER_CACHE.clear()
    got = assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(f"{tmpd}/a.parquet")
        .where(E.GreaterThanOrEqual(col("k"), lit(9500))))
    assert len(got) == 500
    pruned = [
        sc for key, sc in _SCANNER_CACHE.items() if key[3]
    ]
    assert pruned, "no pruned scanner was created"
    assert all(
        sum(len(sp.row_groups) for sp in sc.splits()) == 1 for sc in pruned
    ), "pushdown did not prune to a single row group"


def test_parquet_hive_partition_values(tmpd):
    os.makedirs(f"{tmpd}/t/k=a")
    os.makedirs(f"{tmpd}/t/k=b/j=1")
    pq.write_table(pa.table({"v": [1, 2]}), f"{tmpd}/t/k=a/f.parquet")
    pq.write_table(pa.table({"v": [3]}), f"{tmpd}/t/k=b/j=1/f.parquet")
    # note: ragged partition depth keeps only the common first-level key
    got = assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(f"{tmpd}/t"))
    assert sorted(got)[0][0] == 1


@pytest.mark.parametrize("rt", ["PERFILE", "COALESCING", "MULTITHREADED"])
def test_parquet_reader_strategies_agree(tmpd, rt):
    # three files of five row groups: every strategy has files to coalesce
    # and row groups to split; the first case pays the compiles all three
    # share (two row groups a file measured no cheaper)
    t = _mixed_table(1500, seed=3)
    for i in range(3):
        pq.write_table(t.slice(i * 500, 500), f"{tmpd}/p{i}.parquet",
                       row_group_size=100)
    assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(tmpd)
        .group_by("k").agg(A.agg(A.Count(E.col("l")), "c")),
        conf={"spark.rapids.tpu.sql.format.parquet.reader.type": rt},
    )


def test_parquet_write_query_read_round_trip(tmpd):
    pq.write_table(_mixed_table(seed=5), f"{tmpd}/in.parquet")
    s = TpuSession()
    stats = (
        s.read.parquet(f"{tmpd}/in.parquet")
        .where(E.IsNotNull(col("k")))
        .write.parquet(f"{tmpd}/out.parquet")
    )
    assert stats["numRows"] > 0
    assert os.path.exists(f"{tmpd}/out.parquet")
    assert not os.path.exists(f"{tmpd}/out.parquet._temporary")
    assert_tpu_and_cpu_equal(
        lambda s2: s2.read.parquet(f"{tmpd}/out.parquet"))


def test_parquet_write_empty_result(tmpd):
    pq.write_table(pa.table({"k": pa.array([1, 2], pa.int64())}),
                   f"{tmpd}/in.parquet")
    s = TpuSession()
    stats = (
        s.read.parquet(f"{tmpd}/in.parquet")
        .where(E.GreaterThan(col("k"), lit(100)))
        .write.parquet(f"{tmpd}/empty.parquet")
    )
    assert stats["numRows"] == 0
    back = TpuSession().read.parquet(f"{tmpd}/empty.parquet").collect()
    assert back == []


def test_parquet_disabled_falls_back(tmpd):
    from harness import assert_fallback

    pq.write_table(pa.table({"k": pa.array([1, 2, 3], pa.int64())}),
                   f"{tmpd}/a.parquet")
    assert_fallback(
        lambda s: s.read.parquet(f"{tmpd}/a.parquet"),
        "FileSourceScanExec",
        conf={"spark.rapids.tpu.sql.format.parquet.enabled": False},
    )


def test_csv_scan_with_inferred_and_explicit_schema(tmpd):
    with open(f"{tmpd}/x.csv", "w") as f:
        f.write("a,b,c\n1,foo,1.5\n2,bar,\n,baz,2.5\n")
    assert_tpu_and_cpu_equal(lambda s: s.read.csv(f"{tmpd}/x.csv"))
    schema = T.StructType([
        T.StructField("a", T.LONG),
        T.StructField("b", T.STRING),
        T.StructField("c", T.DOUBLE),
    ])
    got = assert_tpu_and_cpu_equal(
        lambda s: s.read.csv(f"{tmpd}/x.csv", schema=schema))
    assert got[0][2] in (1.5, 2.5, None)


def test_orc_scan_differential(tmpd):
    t = _mixed_table(800, seed=9)
    paorc.write_table(t, f"{tmpd}/x.orc")
    assert_tpu_and_cpu_equal(
        lambda s: s.read.orc(f"{tmpd}/x.orc")
        .group_by("k").agg(A.agg(A.Count(E.col("v")), "c")))


def test_scan_feeds_partitioned_aggregate_through_exchange(tmpd):
    # multi-file scan -> multiple partitions -> exchange plan end to end
    t = _mixed_table(1200, seed=12)
    for i in range(4):
        pq.write_table(t.slice(i * 300, 300), f"{tmpd}/p{i}.parquet")
    # shuffle.mode=host pins the single-host exchange path under test
    # (string-bearing schemas are otherwise mesh-eligible now)
    s = TpuSession({
        "spark.rapids.tpu.sql.format.parquet.reader.type": "PERFILE",
        "spark.rapids.tpu.shuffle.mode": "host"})
    df = s.read.parquet(tmpd).group_by("k").agg(
        A.agg(A.Sum(E.col("l")), "sl"))
    out = df.collect()
    assert "ShuffleExchange" in s.last_executed_plan.tree_string()
    cpu = TpuSession({"spark.rapids.tpu.sql.enabled": False})
    want = cpu.read.parquet(tmpd).group_by("k").agg(
        A.agg(A.Sum(E.col("l")), "sl")).collect()
    compare_rows(want, out)


# ---------------------------------------------------------------------------
# round 3: ORC/CSV writers, ORC pushdown, MULTITHREADED prefetch, decimals
# ---------------------------------------------------------------------------
def test_orc_write_query_read_round_trip(tmpd):
    paorc.write_table(_mixed_table(seed=21), f"{tmpd}/in.orc")
    s = TpuSession()
    stats = (
        s.read.orc(f"{tmpd}/in.orc")
        .where(E.GreaterThan(col("k"), lit(10)))
        .write.orc(f"{tmpd}/out.orc")
    )
    assert stats["rows"] > 0
    assert_tpu_and_cpu_equal(lambda se: se.read.orc(f"{tmpd}/out.orc"))


def test_csv_writer_round_trip(tmpd):
    t = _mixed_table(300, seed=22)
    pq.write_table(t, f"{tmpd}/in.parquet")
    s = TpuSession()
    stats = s.read.parquet(f"{tmpd}/in.parquet").write.csv(f"{tmpd}/out.csv")
    assert stats["rows"] == 300
    import pyarrow.csv as pacsv

    back = pacsv.read_csv(f"{tmpd}/out.csv")
    assert back.num_rows == 300


def test_orc_filter_pushdown_differential(tmpd):
    paorc.write_table(_mixed_table(2000, seed=23), f"{tmpd}/a.orc")
    assert_tpu_and_cpu_equal(
        lambda s: s.read.orc(tmpd).where(
            E.And(E.GreaterThanOrEqual(col("k"), lit(20)),
                  E.IsNotNull(col("s")))))


def test_multithreaded_reader_prefetches(tmpd):
    t = _mixed_table(1200, seed=24)
    for i in range(4):
        pq.write_table(t.slice(i * 300, 300), f"{tmpd}/m{i}.parquet")
    assert_tpu_and_cpu_equal(
        lambda s: s.read.parquet(tmpd).group_by("k").agg(
            A.agg(A.Sum(col("l")), "sl")),
        conf={"spark.rapids.tpu.sql.format.parquet.reader.type":
              "MULTITHREADED"},
    )


def test_decimal_write_round_trip(tmpd):
    import decimal as D

    t = pa.table({
        "d": pa.array([D.Decimal("12.34"), None, D.Decimal("-0.05"),
                       D.Decimal("99999.99")], pa.decimal128(10, 2)),
        "v": pa.array([1, 2, 3, 4], pa.int64()),
    })
    pq.write_table(t, f"{tmpd}/dec.parquet")
    s = TpuSession()
    s.read.parquet(f"{tmpd}/dec.parquet").write.parquet(f"{tmpd}/dec_out.parquet")
    back = pq.read_table(f"{tmpd}/dec_out.parquet")
    assert back.column("d").to_pylist() == [
        D.Decimal("12.34"), None, D.Decimal("-0.05"), D.Decimal("99999.99")]
