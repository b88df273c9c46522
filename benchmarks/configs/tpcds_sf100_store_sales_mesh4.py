"""Seeded generator of the ``tpcds_sf100_store_sales_mesh4`` deployment: the
four ``store_sales`` columns the queries read, at the widths and
distributions ``tpcds_sf100_store_sales_mesh4.json`` states (those of
``tpcds_sf10_store_sales`` with the SF100 ``item`` table's 204,000 keys).
Data takes the place of weights: the same seed gives the same file.

288 M rows are not held in memory: the file is drawn and written row group
by row group, the next one drawn on a worker thread while this one is
written (one generator, drawn from in order, so the threads change
nothing in the file)."""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ITEMS = 204_000
DAY0, DAYS = 2_450_815, 2400


def generate(config: dict, seed: int, out_dir: str, rows: int,
             row_group: int) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    from datagen import plant_domain

    rng = np.random.default_rng(seed)
    # 9750 DISTINCT two-decimal prices of the 9901 in 1.00..100.00
    prices = (100 + np.sort(rng.choice(9901, 9750, replace=False))) / 100.0
    domains = {
        "ss_item_sk": np.arange(1, ITEMS + 1, dtype=np.int32),
        "ss_quantity": np.arange(1, 101, dtype=np.int32),
        "ss_wholesale_cost": prices,
        "ss_sold_date_sk": np.arange(DAY0, DAY0 + DAYS, dtype=np.int32),
    }

    def draw(m: int):
        cols = {
            "ss_item_sk": rng.integers(1, ITEMS + 1, m, dtype=np.int32),
            "ss_quantity": rng.integers(1, 101, m, dtype=np.int32),
            "ss_wholesale_cost": prices[rng.integers(0, 9750, m)],
            "ss_sold_date_sk": (DAY0 + rng.integers(0, DAYS, m)).astype(
                np.int32),
        }
        for name, col in cols.items():
            plant_domain(col, domains[name], rng, m)
        return pa.table({n: pa.array(c) for n, c in cols.items()})

    sizes = [min(row_group, rows - start)
             for start in range(0, rows, row_group)]
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, "store_sales.parquet")
    with ThreadPoolExecutor(max_workers=1) as pool:
        nxt = pool.submit(draw, sizes[0])
        writer = None
        try:
            for i in range(len(sizes)):
                table = nxt.result()
                if i + 1 < len(sizes):
                    nxt = pool.submit(draw, sizes[i + 1])
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                writer.write_table(table, row_group_size=row_group)
        finally:
            if writer is not None:
                writer.close()
    return path
