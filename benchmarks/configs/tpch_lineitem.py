"""Seeded generator of the ``tpch_lineitem`` deployment: the seven
``lineitem`` columns TPC-H Q1 reads, populated as TPC-H v3 section 4.2.3
says dbgen does (the clauses are quoted beside each column). Data takes
the place of weights: the same seed gives the same file."""
from __future__ import annotations

import datetime

import numpy as np

EPOCH = datetime.date(1970, 1, 1)
#: section 4.2.3: STARTDATE, CURRENTDATE, ENDDATE
STARTDATE = (datetime.date(1992, 1, 1) - EPOCH).days
CURRENTDATE = (datetime.date(1995, 6, 17) - EPOCH).days
ENDDATE = (datetime.date(1998, 12, 31) - EPOCH).days


def _strings(codes: np.ndarray, values: list):
    """A plain string column from small integer codes (pyarrow expands the
    dictionary in C++; a numpy object array of 30M strings takes minutes)."""
    import pyarrow as pa

    return pa.DictionaryArray.from_arrays(
        pa.array(codes), pa.array(values, pa.string())).cast(pa.string())


def generate(config: dict, seed: int, out_dir: str, rows: int,
             row_group: int) -> str:
    import pyarrow as pa

    from datagen import filler_columns, plant_domain, write_parquet

    rng = np.random.default_rng(seed)
    # O_ORDERDATE uniform in [STARTDATE, ENDDATE - 151 days]; an order has
    # 1..7 lines that share it. Orders are drawn until `rows` lines exist.
    n_orders = rows // 4 + 8  # mean 4 lines an order: enough, then cut
    lines = rng.integers(1, 8, n_orders, dtype=np.int8)
    while int(lines.sum(dtype=np.int64)) < rows:
        lines = np.concatenate(
            [lines, rng.integers(1, 8, n_orders // 8 + 8, dtype=np.int8)])
    orderdate = rng.integers(STARTDATE, ENDDATE - 151 + 1, lines.shape[0],
                             dtype=np.int32)
    orderdate = np.repeat(orderdate, lines)[:rows]
    # L_SHIPDATE = O_ORDERDATE + random [1..121]
    shipdate = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
    plant_domain(shipdate,
                 np.arange(STARTDATE + 1, ENDDATE - 151 + 121 + 1,
                           dtype=np.int32), rng, row_group)
    # L_RECEIPTDATE = L_SHIPDATE + random [1..30]
    receiptdate = shipdate + rng.integers(1, 31, rows, dtype=np.int32)
    # L_RETURNFLAG: "R" or "A" at random if L_RECEIPTDATE <= CURRENTDATE,
    # else "N". L_LINESTATUS: "O" if L_SHIPDATE > CURRENTDATE, else "F".
    returnflag = np.where(receiptdate <= CURRENTDATE,
                          rng.integers(0, 2, rows, dtype=np.int8),
                          np.int8(2))
    linestatus = (shipdate > CURRENTDATE).astype(np.int8)
    # L_QUANTITY random [1..50]; L_DISCOUNT [0.00..0.10]; L_TAX [0.00..0.08]
    qty_i = rng.integers(1, 51, rows, dtype=np.int32)
    plant_domain(qty_i, np.arange(1, 51, dtype=np.int32), rng, row_group)
    disc_i = rng.integers(0, 11, rows, dtype=np.int32)
    plant_domain(disc_i, np.arange(0, 11, dtype=np.int32), rng, row_group)
    tax_i = rng.integers(0, 9, rows, dtype=np.int32)
    plant_domain(tax_i, np.arange(0, 9, dtype=np.int32), rng, row_group)
    # L_EXTENDEDPRICE = L_QUANTITY * P_RETAILPRICE, P_RETAILPRICE =
    # (90000 + ((P_PARTKEY/10) mod 20001) + 100 * (P_PARTKEY mod 1000))/100,
    # L_PARTKEY random [1 .. SF * 200,000]
    partkey = rng.integers(1, int(config["scale_factor"] * 200_000) + 1, rows,
                           dtype=np.int32)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    columns = {
        "l_quantity": pa.array(qty_i.astype(np.float64)),
        "l_extendedprice": pa.array(qty_i * retail_cents / 100.0),
        "l_discount": pa.array(disc_i / 100.0),
        "l_tax": pa.array(tax_i / 100.0),
        "l_returnflag": _strings(returnflag, ["A", "R", "N"]),
        "l_linestatus": _strings(linestatus, ["F", "O"]),
        "l_shipdate": pa.array(shipdate, pa.date32()),
    }
    if config.get("write_other_columns"):
        columns.update(filler_columns(config["other_columns"], seed, rows))
    table = pa.table(columns)
    return write_parquet(table, out_dir, "lineitem.parquet", row_group)
