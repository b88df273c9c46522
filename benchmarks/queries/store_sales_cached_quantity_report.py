"""``read.parquet(dir).cache() where ss_sold_date_sk >= 2452015 group by
ss_quantity: sum(ss_wholesale_cost), sum(ss_quantity), count(ss_item_sk)``:
the query of ``store_sales_quantity_report.py`` over a table the session
keeps on its devices (``DataFrame.cache()``), on the
``tpcds_sf100_store_sales_mesh4`` deployment. The harness rebuilds the
frame in every query; the session finds the cached relation by its plan.
The reference reads the file in blocks of row groups: 288 M rows do not go
through one ``read_parquet``."""
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
#: reference; set from readings at SF100 (PERF.md section 2): the program on
#: the four chips reads 5.3e-14 to 6.8e-14 over 4 seeds, the reference summed
#: in float32 within blocks of 65,536 rows 2.5e-7, row by row 6.6e-5
FLOAT_LIMIT = 1e-10
READS = ("ss_item_sk", "ss_quantity", "ss_wholesale_cost", "ss_sold_date_sk")
#: row groups to a block of the reference (16.8 M rows of 2^21)
BLOCK_ROW_GROUPS = 8


def frame(sess, data_dir: str):
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit

    return (
        sess.read.parquet(data_dir).cache()
        .where(E.GreaterThanOrEqual(col("ss_sold_date_sk"), lit(DATE_CUT)))
        .group_by("ss_quantity")
        .agg(A.agg(A.Sum(col("ss_wholesale_cost")), "s"),
             A.agg(A.Sum(col("ss_quantity")), "q"),
             A.agg(A.Count(col("ss_item_sk")), "c")))


def reference(path: str, float_dtype="float64"):
    """The plain answer: the same query in pyarrow and numpy on the same
    file, block by block. ``float_dtype`` other than float64 is the
    lower-precision control (``stats.grouped_float_sum``): ``"float32"``
    accumulates every addend in float32 in row order over the whole file,
    ``"float32_blocked"`` within blocks of 65,536 rows whose partial sums
    are added in float32 (a row group holds a whole number of them)."""
    import numpy as np
    import pyarrow.parquet as pq

    from stats import grouped_float_sum

    pf = pq.ParquetFile(path)
    groups = 101  # ss_quantity is 1..100
    acc = np.float64 if float_dtype == "float64" else np.float32
    s = np.zeros(groups, acc)
    q = np.zeros(groups, np.int64)
    c = np.zeros(groups, np.int64)
    n = np.zeros(groups, np.int64)
    for start in range(0, pf.metadata.num_row_groups, BLOCK_ROW_GROUPS):
        block = pf.read_row_groups(
            list(range(start, min(start + BLOCK_ROW_GROUPS,
                                  pf.metadata.num_row_groups))),
            columns=list(READS))
        keep = block["ss_sold_date_sk"].to_numpy() >= DATE_CUT
        keys = block["ss_quantity"].to_numpy()[keep]
        cost = block["ss_wholesale_cost"].to_numpy()[keep]
        passed = np.bincount(keys, minlength=groups)
        n += passed
        # count(ss_item_sk) counts its non-null values
        item = block["ss_item_sk"]
        c += (passed if item.null_count == 0
              else np.bincount(keys[item.is_valid().to_numpy(
                  zero_copy_only=False)[keep]], minlength=groups))
        q += np.bincount(keys, weights=keys.astype(np.float64),
                         minlength=groups).astype(np.int64)
        if float_dtype == "float32":
            np.add.at(s, keys, cost.astype(np.float32))
        else:
            s += grouped_float_sum(keys, cost, groups, float_dtype)
    return [(int(k), float(s[k]), int(q[k]), int(c[k]))
            for k in range(groups) if n[k]]


def needed_bytes(config: dict) -> int:
    """Bytes the algorithm must read: every row's width in the columns the
    query reads, whatever program implements it and wherever the table
    lives (the 100 groups it writes are nothing beside them)."""
    width = sum(c["width_bytes"] for c in config["columns"]
                if c["name"] in READS)
    return int(config["rows"]) * width


def rows_scanned(config: dict) -> int:
    return int(config["rows"])
