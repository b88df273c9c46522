"""Seeded generator of the ``tpcds_sf10_store_sales`` deployment: the four
``store_sales`` columns the queries read, at the widths and distributions
``tpcds_sf10_store_sales.json`` states. Data takes the place of weights:
the same seed gives the same file."""
from __future__ import annotations

import numpy as np


def generate(config: dict, seed: int, out_dir: str, rows: int,
             row_group: int) -> str:
    import pyarrow as pa

    from datagen import filler_columns, plant_domain, write_parquet

    rng = np.random.default_rng(seed)
    # 9750 DISTINCT two-decimal prices of the 9901 in 1.00..100.00
    prices = (100 + np.sort(rng.choice(9901, 9750, replace=False))) / 100.0
    item = rng.integers(1, 102_001, rows, dtype=np.int32)
    quantity = rng.integers(1, 101, rows, dtype=np.int32)
    cost = prices[rng.integers(0, 9750, rows)]
    date = (2_450_815 + rng.integers(0, 2400, rows)).astype(np.int32)
    plant_domain(item, np.arange(1, 102_001, dtype=np.int32), rng, row_group)
    plant_domain(quantity, np.arange(1, 101, dtype=np.int32), rng, row_group)
    plant_domain(cost, prices, rng, row_group)
    plant_domain(date, np.arange(2_450_815, 2_453_215, dtype=np.int32), rng,
                 row_group)
    columns = {
        "ss_item_sk": pa.array(item),
        "ss_quantity": pa.array(quantity),
        "ss_wholesale_cost": pa.array(cost),
        "ss_sold_date_sk": pa.array(date),
    }
    if config.get("write_other_columns"):
        columns.update(filler_columns(config["other_columns"], seed, rows))
    table = pa.table(columns)
    return write_parquet(table, out_dir, "store_sales.parquet", row_group)
