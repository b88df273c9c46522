"""The end-to-end metrics' arithmetic and the trace reduction, held to
hand-computed values."""
import pytest

import benchmark_testlib  # noqa: F401  (puts benchmarks/ on the path)
import stats
import trace_reduce as TR


def test_p95_is_nearest_rank_and_sees_a_stall():
    # 19 queries of 1 s and one stall of 9 s: rank ceil(0.95 * 20) = 19
    assert stats.percentile_nearest_rank([1.0] * 19 + [9.0], 95) == 1.0
    # two stalls in twenty: the 19th value is a stall
    assert stats.percentile_nearest_rank([1.0] * 18 + [9.0, 9.0], 95) == 9.0
    # fewer than twenty: p95 is the maximum
    assert stats.percentile_nearest_rank([2.0, 5.0, 3.0], 95) == 5.0
    assert stats.percentile_nearest_rank([4.0], 95) == 4.0
    with pytest.raises(ValueError):
        stats.percentile_nearest_rank([], 95)


def test_rows_per_s_is_over_the_whole_window_stall_included():
    # 4 queries of 1000 rows; the window lasted 10 s because one stalled
    assert stats.rows_per_second([1000] * 4, 10.0) == 400.0
    with pytest.raises(ValueError):
        stats.rows_per_second([1000], 0.0)


def test_grouped_float_sum_reference_and_controls(monkeypatch):
    import numpy as np

    keys = np.array([0, 1, 0, 1, 0, 0])
    # float32 holds 2^24 and cannot add 1 to it: row by row the ones vanish
    values = np.array([2.0**24, 0.5, 1.0, 0.25, 1.0, 1.0])
    assert list(stats.grouped_float_sum(keys, values, 3)) == [
        2.0**24 + 3, 0.75, 0.0]
    f32 = stats.grouped_float_sum(keys, values, 3, "float32")
    assert f32.dtype == np.float32 and list(f32) == [2.0**24, 0.75, 0.0]
    # in blocks of 3 rows the second block's ones meet first: 1 + 1 + 1... the
    # block [1, 1] of key 0 sums to 2, which 2^24 + 1 (rounded to 2^24) holds
    monkeypatch.setattr(stats, "CONTROL_BLOCK_ROWS", 3)
    blocked = stats.grouped_float_sum(keys, values, 3, "float32_blocked")
    assert list(blocked) == [2.0**24 + 2, 0.75, 0.0]
    with pytest.raises(ValueError):
        stats.grouped_float_sum(keys, values, 3, "bfloat16")


def test_filler_columns_are_seeded_and_of_the_stated_types():
    import datagen

    specs = [{"name": "a", "type": "int64", "width_bytes": 8, "distinct": 5},
             {"name": "b", "type": "float64", "width_bytes": 8,
              "distinct": 300},
             {"name": "c", "type": "date32", "width_bytes": 4, "distinct": 9},
             {"name": "d", "type": "string", "width_bytes": 10,
              "distinct": 4}]
    one = datagen.filler_columns(specs, 2**31 + 3, 1000)
    two = datagen.filler_columns(specs, 2**31 + 3, 1000)
    assert [str(one[k].type) for k in "abcd"] == [
        "int64", "double", "date32[day]", "string"]
    assert all(one[k].equals(two[k]) for k in "abcd")
    assert not one["a"].equals(
        datagen.filler_columns(specs, 2**31 + 4, 1000)["a"])
    assert set(one["a"].to_pylist()) == {1, 2, 3, 4, 5}
    assert {len(v) for v in one["d"].to_pylist()} == {10}
    assert len(set(one["d"].to_pylist())) == 4


def _planes():
    ms = 1e6  # ns
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_run", 10 * ms, 80 * ms)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 10 * ms, 10 * ms),
                ("fusion.2", 15 * ms, 10 * ms),   # overlaps fusion.1
                ("copy.3", 40 * ms, 10 * ms),
                ("fusion.1", 80 * ms, 10 * ms),
                ("fusion.9", 200 * ms, 10 * ms),  # outside the slice
            ]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [
                (TR.SLICE_MARK, 0.0, 100 * ms),
                (TR.QUERY_MARK, 0.0, 60 * ms),
                (TR.QUERY_MARK, 70 * ms, 30 * ms),
                ("TpuHashAggregateExec.stage", 5 * ms, 50 * ms),
                ("PjitFunction(run)", 6 * ms, 2 * ms),  # not an exec span
            ]},
            {"name": "decode-pool", "events": [
                ("TpuFileSourceScanExec.decode", 26 * ms, 10 * ms)]}]},
    ]


def test_op_key_and_self_time_of_nested_operations():
    text = ("%while.387 = (u32[]{:T(128)}, f32[2097152]{0:T(1024)S(1)}) "
            "while(%tuple.1), condition=%c, body=%b")
    assert TR.op_key(text) == "while u32[]"
    assert TR.op_key("%pad_add_fusion.6 = f32[128,4]{0,1} fusion(%p)") == (
        "pad_add_fusion f32[128,4]")
    assert TR.op_key("jit_run") == "jit_run"
    ms = 1e6
    ops = [(text, 0.0, 100 * ms),
           ("%fusion.1 = f32[8]{0} fusion(%x)", 10 * ms, 20 * ms),
           ("%fusion.2 = f32[8]{0} fusion(%x)", 50 * ms, 30 * ms),
           ("%copy.3 = f32[8]{0} copy(%x)", 100 * ms, 10 * ms)]
    got = TR.self_seconds(ops, 0.0, 1e12)
    assert got["while u32[]"] == pytest.approx(0.050)
    assert got["fusion f32[8]"] == pytest.approx(0.050)
    assert got["copy f32[8]"] == pytest.approx(0.010)


def test_union_clip_gaps():
    assert TR.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert TR.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert TR.gaps([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_reduce_busy_idle_ops_and_gap_attribution():
    r = TR.reduce_planes(_planes())
    # busy: [10,25] + [40,50] + [80,90] = 35 ms of the 100 ms slice
    assert r["window_s"] == pytest.approx(0.100)
    assert r["busy_s"] == pytest.approx(0.035)
    assert r["chips_traced"] == 1
    ops = dict(map(tuple, r["device_ops"]))
    # by kind, self time: fusion.1 [10,20) holds 5 ms of fusion.2 [15,25)
    assert ops["fusion"] == pytest.approx(0.025)  # fusion.9 is outside
    assert ops["copy"] == pytest.approx(0.010)
    assert r["device_ops"][0][0] == "fusion"
    assert sum(ops.values()) == pytest.approx(r["busy_s"])
    gaps = dict(map(tuple, r["idle_gaps"]))
    # idle: [0,10] [25,40] [50,80] [90,100]
    #  [0,5) in a query, no exec span; [5,10) under the stage span
    #  [25,26) stage; [26,36) the decode span (innermost); [36,40) stage
    #  [50,55) stage; [55,60) query only; [60,70) between queries;
    #  [70,80) and [90,100) query only
    assert gaps["TpuHashAggregateExec.stage"] == pytest.approx(0.015)
    assert gaps["TpuFileSourceScanExec.decode"] == pytest.approx(0.010)
    assert gaps[TR.IN_QUERY] == pytest.approx(0.030)
    assert gaps[TR.BETWEEN] == pytest.approx(0.010)
    assert sum(gaps.values()) == pytest.approx(0.065)
    assert 100 * (1 - r["busy_s"] / r["window_s"]) == pytest.approx(65.0)


def test_idle_under_a_dotted_section_goes_to_its_innermost_part():
    """``TpuHashAggregateExec.merge`` names its parts ``merge.<part>``: an
    idle gap under one goes to the part, what no part covers stays under
    ``merge``."""
    ms = 1e6
    merge = "TpuHashAggregateExec.merge"
    for name in (merge, merge + ".concat", "CpuFooExec.a.b.c", "TpuXExec"):
        assert TR.EXEC_SPAN.match(name), name
    for name in ("TpuSession.query", "PjitFunction(run)", merge + ".",
                 merge + "..concat", merge + ".concat x", "Exec.merge"):
        assert not TR.EXEC_SPAN.match(name), name
    planes = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        (TR.SLICE_MARK, 0.0, 100 * ms), (TR.QUERY_MARK, 0.0, 100 * ms),
        (merge, 10 * ms, 80 * ms),
        (merge + ".concat", 10 * ms, 30 * ms),   # starts with its parent
        (merge + ".lengths", 50 * ms, 20 * ms),
        ("TpuSession.plan", 2 * ms, 3 * ms)]}]}]  # no exec span
    spans = TR.host_spans(planes)
    assert len(spans) == 5
    got = TR.attribute([(0.0, 100 * ms)], spans)
    assert got == pytest.approx({
        merge + ".concat": 0.030, merge + ".lengths": 0.020,
        merge: 0.030,  # [40,50) and [70,90)
        TR.IN_QUERY: 0.020})
    # a gap that crosses a part's edge is cut there
    assert TR.attribute([(35 * ms, 55 * ms)], spans) == pytest.approx({
        merge + ".concat": 0.005, merge: 0.010, merge + ".lengths": 0.005})


def test_reduce_without_marks_or_device():
    planes = _planes()
    planes[1]["lines"][0]["events"] = []
    r = TR.reduce_planes(planes, wall_s=0.2)
    assert r["window_s"] == pytest.approx(0.2)  # from the first op on
    assert dict(map(tuple, r["idle_gaps"])).keys() <= {
        TR.UNATTRIBUTED, "TpuFileSourceScanExec.decode"}
    r = TR.reduce_planes([planes[1]], wall_s=0.5)
    assert r["busy_s"] == 0.0 and r["chips_traced"] == 0


def test_metric_readers_return_nothing_without_device_time():
    import loader

    readers = loader.load_metrics()
    assert {"first_query_s", "window_compiles", "device_idle_share",
            "device_ms_per_query", "scan_roofline",
            "spilled_bytes"} <= set(readers)
    r = TR.reduce_planes(_planes())
    r.update(query_indices=[0, 0], queries=2)

    class Q:
        @staticmethod
        def needed_bytes(config):
            return 819_000  # 1 us at 819 GB/s

    ctx = {"trace": r, "peaks": {"hbm_GB/s": 819}, "config": {},
           "queries": [Q], "counters": {"first_query_s": 1.5,
                                        "window_compiles": 0,
                                        "spilled_bytes": 0}}
    assert readers["device_idle_share"].read(ctx) == pytest.approx(65.0)
    assert readers["device_ms_per_query"].read(ctx) == pytest.approx(17.5)
    # two queries need 2 us; the device was busy 35 ms
    assert readers["scan_roofline"].read(ctx) == pytest.approx(
        100 * 2e-6 / 0.035)
    assert readers["window_compiles"].read(ctx) == 0
    empty = dict(r, busy_s=0.0)
    for name in ("device_idle_share", "device_ms_per_query", "scan_roofline"):
        assert readers[name].read(dict(ctx, trace=empty)) is None
        assert readers[name].read(dict(ctx, trace=None)) is None
