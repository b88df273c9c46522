"""The per-layer metric ``merge_slots_per_query``: its entry in
``BENCHMARK.json``, and its reader on planes built by hand. It reads the sum
of the ``slots`` counts on a query's ``TpuHashAggregateExec.merge`` spans,
and nothing where no such span carries the count (a program without the
cut, or a slice whose merges ran inside the fused stage)."""
import pytest

import benchmark_contract as contract
import benchmark_testlib as lib
import loader
import trace_programs as TP

NAME = "merge_slots_per_query"
MS = 1e6  # ns


def test_the_metric_is_in_the_contract_for_both_q1_cells():
    entry = contract.entry_of(lib.load_spec()["per_layer"], NAME, "metric")
    assert entry == {
        "name": NAME, "unit": "count", "better": "lower",
        "source": "program_counter", "layer": "fused stage and groupby ops",
        "moves": "rows_per_s",
        "workloads": ["lineitem.q1", "lineitem_full.q1"]}
    assert loader.load_metrics()[NAME].UNIT == "count"


def _planes(merges):
    """A slice of 100 ms and two queries; a query's merges are
    ``[(partials, counts)]``, 10 ms each with 4 ms of ``merge.concat``."""
    agg = "TpuHashAggregateExec"
    events = [("bench.slice", 0, 100 * MS, {})]
    for qid, q0 in ((7, 0), (8, 50)):
        events += [("bench.query", q0 * MS, 50 * MS, {}),
                   ("TpuSession.query", q0 * MS, 49 * MS, {"query": qid})]
        for k, (partials, counts) in enumerate(merges):
            s0 = q0 + 1 + 12 * k
            events += [
                (agg + ".merge", s0 * MS, 10 * MS,
                 dict(query=qid, mode="partial", partials=partials,
                      **counts)),
                (agg + ".merge.concat", (s0 + 1) * MS, 4 * MS,
                 {"query": qid})]
    return [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": events}]}]


def _ctx(planes):
    return {"trace": {"busy_s": 0.06, "window_s": 0.1, "queries": 2,
                      "query_indices": [0, 0], "chips_traced": 1},
            "trace_programs": TP.reduce_programs(planes),
            "peaks": loader.load_peaks("TPU v5 lite"),
            "config": {"rows": 1000}, "queries": [], "counters": {}}


@pytest.mark.parametrize("merges,want", [
    # lineitem.q1: one merge of 29 partials, each cut to 128 slots
    ([(29, {"slots": 29 * 128, "cut": 29})], 3712.0),
    # lineitem_full.q1: a merge a split, and the final's one partial,
    # which passes through and runs no program
    ([(16, {"slots": 16 * 128, "cut": 16}),
      (13, {"slots": 13 * 128, "cut": 13}), (1, {})], 3712.0),
    # a partial whose rows fill its slots is taken whole
    ([(2, {"slots": 128 + 2048, "cut": 1})], 2176.0),
    # the parent's program: no count on the span
    ([(29, {})], None),
], ids=["one_merge", "a_merge_a_split", "one_taken_whole", "parent"])
def test_the_reader_sums_the_slots_a_query(merges, want):
    got = loader.load_metrics()[NAME].read(_ctx(_planes(merges)))
    assert got == (pytest.approx(want) if want is not None else None)


def test_the_reader_is_silent_where_there_is_nothing_to_read():
    reader = loader.load_metrics()[NAME]
    assert reader.read({"trace": None, "counters": {}}) is None
    no_merge = [{"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.slice", 0, 100 * MS, {})]}]}]
    assert reader.read(_ctx(no_merge)) is None
