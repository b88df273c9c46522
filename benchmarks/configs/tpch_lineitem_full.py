"""Seeded generator of the ``tpch_lineitem_full`` deployment: ``lineitem``
at the source's full record width, all 16 columns at the widths
``tpch_lineitem_full.json`` states, in the order TPC-H v3 section 1.4 lists
them. The seven columns Q1 reads are drawn as ``tpch_lineitem.py`` draws
them, clause for clause of section 4.2.3 and in the same order from the
same generator, so a seed gives the values ``tpch_lineitem`` has for it;
the other nine are seeded filler of the source's types
(``datagen.filler_columns``). Data takes the place of weights: the same
seed gives the same file.

The read columns are drawn whole as small integers (an order's lines share
its date, so they are not drawn a row group at a time) and become floats,
strings and a table one row group at a time; the filler is drawn a row
group at a time from a generator of its own. The 16 columns are never held
whole: the file is written row group by row group, the next few made on
worker threads while this one is written."""
from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

FILE = "lineitem.parquet"
#: row groups made ahead of the writer, a thread each (a row group has a
#: generator of its own, so the threads change nothing in the file); each
#: holds some 0.3 GB
AHEAD = 3


@functools.lru_cache(maxsize=None)
def _lineitem():
    """``tpch_lineitem.py`` beside this file: section 4.2.3's dates and
    the string column from codes."""
    import loader

    return loader.load_module(
        "config", "configs", "tpch_lineitem",
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def read_columns(config: dict, seed: int, rows: int, row_group: int) -> dict:
    """What Q1 reads, as integers: dates in days, quantity, hundredths of
    discount and tax, cents of the part's retail price, codes of the two
    flags. ``tpch_lineitem.generate``'s draws in its order."""
    from datagen import plant_domain

    li = _lineitem()
    rng = np.random.default_rng(seed)
    n_orders = rows // 4 + 8  # mean 4 lines an order: enough, then cut
    lines = rng.integers(1, 8, n_orders, dtype=np.int8)
    while int(lines.sum(dtype=np.int64)) < rows:
        lines = np.concatenate(
            [lines, rng.integers(1, 8, n_orders // 8 + 8, dtype=np.int8)])
    orderdate = rng.integers(li.STARTDATE, li.ENDDATE - 151 + 1,
                             lines.shape[0], dtype=np.int32)
    orderdate = np.repeat(orderdate, lines)[:rows]
    shipdate = orderdate + rng.integers(1, 122, rows, dtype=np.int32)
    del orderdate, lines
    plant_domain(shipdate,
                 np.arange(li.STARTDATE + 1, li.ENDDATE - 151 + 121 + 1,
                           dtype=np.int32), rng, row_group)
    receiptdate = shipdate + rng.integers(1, 31, rows, dtype=np.int32)
    returnflag = np.where(receiptdate <= li.CURRENTDATE,
                          rng.integers(0, 2, rows, dtype=np.int8),
                          np.int8(2))
    del receiptdate
    linestatus = (shipdate > li.CURRENTDATE).astype(np.int8)
    qty = rng.integers(1, 51, rows, dtype=np.int32)
    plant_domain(qty, np.arange(1, 51, dtype=np.int32), rng, row_group)
    disc = rng.integers(0, 11, rows, dtype=np.int32)
    plant_domain(disc, np.arange(0, 11, dtype=np.int32), rng, row_group)
    tax = rng.integers(0, 9, rows, dtype=np.int32)
    plant_domain(tax, np.arange(0, 9, dtype=np.int32), rng, row_group)
    partkey = rng.integers(1, int(config["scale_factor"] * 200_000) + 1,
                           rows, dtype=np.int32)
    retail_cents = 90_000 + (partkey // 10) % 20_001 + 100 * (partkey % 1000)
    return {"shipdate": shipdate, "returnflag": returnflag,
            "linestatus": linestatus, "qty": qty, "disc": disc, "tax": tax,
            "retail_cents": retail_cents}


def generate(config: dict, seed: int, out_dir: str, rows: int,
             row_group: int) -> str:
    import pyarrow as pa
    import pyarrow.parquet as pq

    from datagen import filler_columns

    li = _lineitem()
    read = read_columns(config, seed, rows, row_group)
    other = (config["other_columns"]
             if config.get("write_other_columns") else [])
    names = {c["name"] for c in config["columns"]} | {
        c["name"] for c in other}
    order = [n for n in config["column_order"] if n in names]

    def make(i: int, start: int, m: int):
        r = {k: v[start:start + m] for k, v in read.items()}
        table = {
            "l_quantity": pa.array(r["qty"].astype(np.float64)),
            "l_extendedprice": pa.array(
                r["qty"] * r["retail_cents"] / 100.0),
            "l_discount": pa.array(r["disc"] / 100.0),
            "l_tax": pa.array(r["tax"] / 100.0),
            "l_returnflag": li._strings(r["returnflag"], ["A", "R", "N"]),
            "l_linestatus": li._strings(r["linestatus"], ["F", "O"]),
            "l_shipdate": pa.array(r["shipdate"], pa.date32()),
        }
        # a generator of its own a row group: the read columns are the
        # same with or without the filler beside them
        table.update(filler_columns(other, seed * 4096 + i, m))
        return pa.table({n: table[n] for n in order})

    starts = list(range(0, rows, row_group))
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, FILE)
    with ThreadPoolExecutor(max_workers=AHEAD) as pool:
        def submit(i):
            return pool.submit(make, i, starts[i],
                               min(row_group, rows - starts[i]))

        ahead = [submit(i) for i in range(min(AHEAD, len(starts)))]
        writer = None
        try:
            for i in range(len(starts)):
                table = ahead.pop(0).result()
                if i + AHEAD < len(starts):
                    ahead.append(submit(i + AHEAD))
                if writer is None:
                    writer = pq.ParquetWriter(path, table.schema)
                writer.write_table(table, row_group_size=row_group)
        finally:
            if writer is not None:
                writer.close()
    return path
