"""Each query file's plain reference and ``needed_bytes`` against values
computed by hand on a tiny table, the comparison that decides ``correct``,
and the lower-precision control, which has to come out not correct."""
import datetime
import json
import os

import numpy as np
import pytest

import benchmark_testlib as lib
import compare
import loader


def _query(name):
    return loader.load_module("query", "queries", name)


def _config(name):
    return loader.load_json("config", "configs", name)


def test_quantity_report_reference_by_hand(tmp_path):
    import pyarrow as pa
    import pyarrow.parquet as pq

    q = _query("store_sales_quantity_report")
    path = str(tmp_path / "store_sales.parquet")
    pq.write_table(pa.table({
        "ss_item_sk": pa.array([1, 2, 3, 4, 5, 6], pa.int32()),
        "ss_quantity": pa.array([7, 7, 9, 9, 9, 7], pa.int32()),
        "ss_wholesale_cost": pa.array([1.5, 2.25, 10.0, 0.5, 4.0, 100.0]),
        # the last row is before the cut and is filtered out
        "ss_sold_date_sk": pa.array(
            [q.DATE_CUT, q.DATE_CUT + 1, q.DATE_CUT, q.DATE_CUT + 9,
             q.DATE_CUT, q.DATE_CUT - 1], pa.int32()),
    }), path)
    assert q.reference(path) == [(7, 3.75, 14, 2), (9, 14.5, 27, 3)]
    assert q.reference(path, "float32") == [(7, 3.75, 14, 2), (9, 14.5, 27, 3)]


def test_quantity_report_needed_bytes():
    q = _query("store_sales_quantity_report")
    cfg = _config("tpcds_sf10_store_sales")
    # 4 + 4 + 8 + 4 bytes a row, 28,800,991 rows
    assert q.needed_bytes(cfg) == 28_800_991 * 20 == 576_019_820
    assert q.rows_scanned(cfg) == 28_800_991
    assert q.needed_bytes(dict(cfg, rows=10)) == 200


def _lineitem_20(path):
    """Twenty rows, values chosen so that every Q1 column can be summed by
    hand: group (A,F) has rows 0..7, (N,O) rows 8..15, (R,F) rows 16..18;
    row 19 ships after the cut and is filtered out."""
    import pyarrow as pa
    import pyarrow.parquet as pq

    flag = ["A"] * 8 + ["N"] * 8 + ["R"] * 3 + ["N"]
    status = ["F"] * 8 + ["O"] * 8 + ["F"] * 3 + ["O"]
    qty = [float(i + 1) for i in range(20)]
    price = [100.0 * (i + 1) for i in range(20)]
    disc = [0.0, 0.5] * 10
    tax = [0.0, 0.0, 0.5, 0.5] * 5
    ship = [datetime.date(1995, 1, 1)] * 19 + [datetime.date(1998, 9, 3)]
    pq.write_table(pa.table({
        "l_quantity": pa.array(qty), "l_extendedprice": pa.array(price),
        "l_discount": pa.array(disc), "l_tax": pa.array(tax),
        "l_returnflag": pa.array(flag), "l_linestatus": pa.array(status),
        "l_shipdate": pa.array(ship, pa.date32()),
    }), path)


def test_q1_reference_by_hand(tmp_path):
    q = _query("lineitem_q1")
    path = str(tmp_path / "lineitem.parquet")
    _lineitem_20(path)
    rows = q.reference(path)
    assert [r[:2] for r in rows] == [("A", "F"), ("N", "O"), ("R", "F")]
    a = rows[0]
    # rows 0..7: qty 1..8, price 100..800, disc 0,.5,..., tax 0,0,.5,.5,...
    assert a[2] == 36.0                      # sum_qty
    assert a[3] == 3600.0                    # sum_base_price
    # price*(1-disc): 100, 100, 300, 200, 500, 300, 700, 400
    assert a[4] == 2600.0                    # sum_disc_price
    # *(1+tax): 100, 100, 450, 300, 500, 300, 1050, 600
    assert a[5] == 3400.0                    # sum_charge
    assert a[6] == 4.5                       # avg_qty
    assert a[7] == 450.0                     # avg_price
    assert a[8] == 0.25                      # avg_disc
    assert a[9] == 8                         # count_order
    r = rows[2]
    # rows 16..18: qty 17,18,19; price 1700,1800,1900; disc 0,.5,0; tax 0,0,.5
    assert r[2:6] == (54.0, 5400.0, 1700.0 + 900.0 + 1900.0,
                      1700.0 + 900.0 + 2850.0)
    assert r[6:] == (18.0, 1800.0, 0.5 / 3, 3)
    assert rows[1][9] == 8  # row 19 (N,O) is past the ship-date cut
    f32 = q.reference(path, "float32")
    assert [x[:2] + x[9:] for x in f32] == [x[:2] + x[9:] for x in rows]
    assert f32[0][2:8] == a[2:8]  # small exact values survive float32
    assert q.reference(path, "float32_blocked") == f32


def test_q1_needed_bytes():
    q = _query("lineitem_q1")
    cfg = _config("tpch_lineitem")
    # four doubles, two one-byte flags and a date: 38 bytes a row
    assert q.needed_bytes(dict(cfg, rows=20)) == 20 * 38
    assert q.needed_bytes(cfg) == cfg["rows"] * 38


class _Q:
    KEYS = (0,)
    EXACT = (2,)
    FLOAT = (1,)
    ORDERED = True
    FLOAT_LIMIT = 1e-6


def test_comparison_numbers():
    want = [(1, 10.0, 5), (2, 20.0, 6), (3, 30.0, 7)]
    same = compare.compare_answer(list(want), want, _Q)
    assert same == {"rows_wrong": 0.0, "exact_wrong": 0.0,
                    "order_wrong": 0.0, "float_rel_err": 0.0}
    got = [(1, 10.0, 5), (3, 30.00003, 7), (2, 20.0, 9)]
    n = compare.compare_answer(got, want, _Q)
    assert n["rows_wrong"] == 0 and n["exact_wrong"] == 1
    assert n["order_wrong"] == 2
    assert n["float_rel_err"] == pytest.approx(1e-6, rel=1e-3)
    n = compare.compare_answer(want[:2] + [(4, 1.0, 1)], want, _Q)
    assert n["rows_wrong"] == 2  # one missing, one that should not be there
    n = compare.compare_answer([(1, float("nan"), 5)] + want[1:], want, _Q)
    assert n["float_rel_err"] == compare.NOT_A_NUMBER
    assert "Infinity" not in json.dumps(n)  # the result line stays JSON
    c = compare.compare_window([(0, got), (0, list(want))], [want], [_Q], ["q"])
    assert c["exact_wrong"] == (1.0, 0.0)
    assert c["float_rel_err"][1] == 1e-6
    assert not compare.all_within(c)
    assert compare.all_within(
        compare.compare_window([(0, list(want))], [want], [_Q], ["q"]))


@pytest.mark.parametrize("cell,rows", [
    ("store_sales.quantity_report", 8_000_000),
    ("lineitem.q1", 2_000_000),
])
def test_float32_control_is_not_correct(cell, rows, tmp_path):
    """The control: the reference put in the program's place and computed
    in float32 where the configuration states float64. At the cell's own
    size it was read on the chip's host (PERF.md section 2); here, at a
    size a test run can hold, it still has to fail the float limit while
    the float64 reference passes against itself."""
    bench = loader.load_cell(cell)
    q = bench["queries"][0]
    path = bench["generator"].generate(
        bench["config"], 2**31 + 5, str(tmp_path), rows, 1 << 20)
    want = q.reference(path)
    control = q.reference(path, "float32")
    names = bench["query_names"]
    c = compare.compare_window([(0, control)], [want], [q], names)
    assert c["rows_wrong"][0] == 0 and c["exact_wrong"][0] == 0
    assert c["float_rel_err"][0] > c["float_rel_err"][1]
    assert not compare.all_within(c)
    # the second reading, summed in blocks: better than row by row, as a
    # float32 path on the device would be (PERF.md says what it reads at
    # the cells' own sizes, and what follows from it)
    blocked = compare.compare_window(
        [(0, q.reference(path, "float32_blocked"))], [want], [q], names)
    assert 0 < blocked["float_rel_err"][0] < c["float_rel_err"][0]
    assert compare.all_within(
        compare.compare_window([(0, want)], [want], [q], names))


@pytest.mark.parametrize("cell,columns", [
    ("store_sales.quantity_report", 23),
    ("lineitem.q1", 16),
])
def test_full_width_file_holds_the_same_answer(cell, columns, tmp_path):
    """``write_other_columns`` writes the source's whole record; the
    columns the queries read, and so the plain answer, are the same with
    and without the filler."""
    import pyarrow.parquet as pq

    bench = loader.load_cell(cell)
    q, config = bench["queries"][0], bench["config"]
    assert len(config["columns"]) + len(config["other_columns"]) == columns
    narrow = bench["generator"].generate(
        config, 2**31 + 9, str(tmp_path / "narrow"), 40_000, 1 << 14)
    full = bench["generator"].generate(
        dict(config, write_other_columns=True), 2**31 + 9,
        str(tmp_path / "full"), 40_000, 1 << 14)
    assert pq.ParquetFile(narrow).metadata.num_columns == len(config["columns"])
    assert pq.ParquetFile(full).metadata.num_columns == columns
    assert q.reference(full) == q.reference(narrow)
    assert os.path.getsize(full) > 2 * os.path.getsize(narrow)
