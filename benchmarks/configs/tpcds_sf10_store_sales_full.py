"""Seeded generator of the ``tpcds_sf10_store_sales_full`` deployment:
``store_sales`` at the source's full record width, all 23 columns at the
widths ``tpcds_sf10_store_sales_full.json`` states. The four columns the
queries read have ``tpcds_sf10_store_sales``'s domains and distributions,
every value planted in every row group; the other 19 are seeded filler of
the source's types (``datagen.filler_columns``). Data takes the place of
weights: the same seed gives the same file.

The table is 4.1 GB as arrays and is never held whole: the file is drawn
and written row group by row group, the next one drawn on a worker thread
while this one is written (the generators are drawn from in order, so the
threads change nothing in the file)."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ITEMS = 102_000
DAY0, DAYS = 2_450_815, 2400


def generate(config: dict, seed: int, out_dir: str, rows: int,
             row_group: int) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    from datagen import filler_columns, plant_domain

    rng = np.random.default_rng(seed)
    # 9750 DISTINCT two-decimal prices of the 9901 in 1.00..100.00
    prices = (100 + np.sort(rng.choice(9901, 9750, replace=False))) / 100.0
    domains = {
        "ss_item_sk": np.arange(1, ITEMS + 1, dtype=np.int32),
        "ss_quantity": np.arange(1, 101, dtype=np.int32),
        "ss_wholesale_cost": prices,
        "ss_sold_date_sk": np.arange(DAY0, DAY0 + DAYS, dtype=np.int32),
    }
    order = [c["name"] for c in config["columns"]] + [
        c["name"] for c in config["other_columns"]]

    def draw(i: int, m: int):
        cols = {
            "ss_item_sk": rng.integers(1, ITEMS + 1, m, dtype=np.int32),
            "ss_quantity": rng.integers(1, 101, m, dtype=np.int32),
            "ss_wholesale_cost": prices[rng.integers(0, 9750, m)],
            "ss_sold_date_sk": (DAY0 + rng.integers(0, DAYS, m)).astype(
                np.int32),
        }
        for name, col in cols.items():
            plant_domain(col, domains[name], rng, m)
        table = {n: pa.array(c) for n, c in cols.items()}
        # a generator of its own a row group: the read columns are the
        # same with or without the filler beside them
        table.update(filler_columns(
            config["other_columns"], seed * 4096 + i, m))
        return pa.table({n: table[n] for n in order})

    sizes = [min(row_group, rows - start)
             for start in range(0, rows, row_group)]
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "store_sales.parquet")
    with ThreadPoolExecutor(max_workers=1) as pool:
        nxt = pool.submit(draw, 0, sizes[0])
        writer = None
        try:
            for i in range(len(sizes)):
                table = nxt.result()
                if i + 1 < len(sizes):
                    nxt = pool.submit(draw, i + 1, sizes[i + 1])
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                writer.write_table(table, row_group_size=row_group)
        finally:
            if writer is not None:
                writer.close()
    return path
