"""TPC-H Q1, the pricing summary report (TPC-H v3 section 2.4.1), with its
validation parameter DELTA = 90: ``l_shipdate <= date '1998-12-01' -
interval '90' day`` = 1998-09-02, on the ``tpch_lineitem`` deployment."""
from __future__ import annotations

import datetime

TABLE = "lineitem.parquet"
SHIP_CUT = datetime.date(1998, 9, 2)
COLUMNS = ("l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
           "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
           "avg_disc", "count_order")
#: how compare.py holds each output column to the reference
KEYS = (0, 1)
EXACT = (9,)
FLOAT = (2, 3, 4, 5, 6, 7, 8)
ORDERED = True
#: limit on the worst relative error of a float column against the float64
#: reference; set from readings on the chip (PERF.md section 2)
FLOAT_LIMIT = 2e-5
READS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
         "l_returnflag", "l_linestatus", "l_shipdate")


def frame(sess, data_dir: str):
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit

    cut = E.Literal((SHIP_CUT - datetime.date(1970, 1, 1)).days, T.DATE)
    disc_price = E.Multiply(col("l_extendedprice"),
                            E.Subtract(lit(1.0), col("l_discount")))
    charge = E.Multiply(disc_price, E.Add(lit(1.0), col("l_tax")))
    return (
        sess.read.parquet(data_dir)
        .where(E.LessThanOrEqual(col("l_shipdate"), cut))
        .group_by("l_returnflag", "l_linestatus")
        .agg(A.agg(A.Sum(col("l_quantity")), "sum_qty"),
             A.agg(A.Sum(col("l_extendedprice")), "sum_base_price"),
             A.agg(A.Sum(disc_price), "sum_disc_price"),
             A.agg(A.Sum(charge), "sum_charge"),
             A.agg(A.Average(col("l_quantity")), "avg_qty"),
             A.agg(A.Average(col("l_extendedprice")), "avg_price"),
             A.agg(A.Average(col("l_discount")), "avg_disc"),
             A.agg(A.Count(col("l_quantity")), "count_order"))
        .order_by("l_returnflag", "l_linestatus"))


def reference(path: str, float_dtype="float64"):
    """The plain answer: Q1 in pyarrow and numpy on the same file.
    ``float_dtype`` other than float64 is the lower-precision control: the
    float columns cast to float32, the products taken in float32 and every
    sum accumulated as ``stats.grouped_float_sum`` says."""
    import numpy as np
    import pyarrow as pa
    import pyarrow.compute as pc
    import pyarrow.parquet as pq

    from stats import grouped_float_sum

    t = pq.read_table(path, columns=list(READS))
    t = t.filter(pc.less_equal(t["l_shipdate"], pa.scalar(SHIP_CUT)))
    fdt = np.dtype(float_dtype.split("_")[0])

    def num(name):
        return t[name].to_numpy().astype(fdt, copy=False)

    flags = t["l_returnflag"].combine_chunks().dictionary_encode()
    status = t["l_linestatus"].combine_chunks().dictionary_encode()
    n_status = len(status.dictionary)
    gid = (flags.indices.to_numpy().astype(np.int64) * n_status
           + status.indices.to_numpy())
    qty, ext, disc, tax = (num("l_quantity"), num("l_extendedprice"),
                           num("l_discount"), num("l_tax"))
    one = fdt.type(1)
    disc_price = ext * (one - disc)
    charge = disc_price * (one + tax)
    n_groups = len(flags.dictionary) * n_status
    count = np.bincount(gid, minlength=n_groups)

    def gsum(values):
        return grouped_float_sum(gid, values, n_groups, float_dtype)

    s_qty, s_ext, s_dp, s_ch, s_disc = (
        gsum(qty), gsum(ext), gsum(disc_price), gsum(charge), gsum(disc))
    rows = []
    for g in np.flatnonzero(count):
        c = fdt.type(count[g])
        rows.append((
            flags.dictionary[int(g) // n_status].as_py(),
            status.dictionary[int(g) % n_status].as_py(),
            float(s_qty[g]), float(s_ext[g]), float(s_dp[g]), float(s_ch[g]),
            float(s_qty[g] / c), float(s_ext[g] / c), float(s_disc[g] / c),
            int(count[g])))
    return sorted(rows)


def needed_bytes(config: dict) -> int:
    """Bytes the algorithm must read: every row's width in the seven
    columns Q1 reads, whatever program implements it."""
    width = sum(c["width_bytes"] for c in config["columns"]
                if c["name"] in READS)
    return int(config["rows"]) * width


def rows_scanned(config: dict) -> int:
    return int(config["rows"])
