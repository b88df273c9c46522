"""``where ss_sold_date_sk >= 2452015 group by ss_quantity:
sum(ss_wholesale_cost), sum(ss_quantity), count(ss_item_sk)`` — the first
query that ran on the chip (PR 22's smoke run), on the
``tpcds_sf10_store_sales`` deployment."""
from __future__ import annotations

TABLE = "store_sales.parquet"
DATE_CUT = 2_452_015
COLUMNS = ("ss_quantity", "s", "q", "c")
#: how compare.py holds each output column to the reference
KEYS = (0,)
EXACT = (2, 3)
FLOAT = (1,)
ORDERED = False
#: limit on the worst relative error of a float column against the float64
#: reference; set from readings on the chip (PERF.md section 2)
FLOAT_LIMIT = 4e-6
READS = ("ss_item_sk", "ss_quantity", "ss_wholesale_cost", "ss_sold_date_sk")


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


def reference(path: str, float_dtype="float64"):
    """The plain answer: the same query in pandas on the same file.
    ``float_dtype`` other than float64 is the lower-precision control
    (``stats.grouped_float_sum``)."""
    import pandas as pd

    from stats import grouped_float_sum

    pdf = pd.read_parquet(path, columns=list(READS))
    f = pdf[pdf["ss_sold_date_sk"] >= DATE_CUT]
    g = f.groupby("ss_quantity").agg(
        q=("ss_quantity", "sum"), c=("ss_item_sk", "count"))
    keys = f["ss_quantity"].to_numpy()
    s = grouped_float_sum(keys, f["ss_wholesale_cost"].to_numpy(),
                          int(keys.max()) + 1 if len(keys) else 0,
                          float_dtype)
    return [(int(k), float(s[k]), int(r.q), int(r.c))
            for k, r in g.iterrows()]


def needed_bytes(config: dict) -> int:
    """Bytes the algorithm must read: every row's width in the columns the
    query reads, whatever program implements it (the 100 groups it writes
    are nothing beside them)."""
    width = sum(c["width_bytes"] for c in config["columns"]
                if c["name"] in READS)
    return int(config["rows"]) * width


def rows_scanned(config: dict) -> int:
    return int(config["rows"])
