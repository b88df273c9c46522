"""Device scan cache: hot-file reuse + rewrite invalidation.

Reference analog: the cached-batch serializer keeps columnar data resident
(ParquetCachedBatchSerializer.scala); here the pool is keyed by file
identity (path, mtime, size) so a rewritten file can never serve stale
columns.
"""
import os
import time

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from spark_rapids_tpu.conf import RapidsConf
from spark_rapids_tpu.io.scan_cache import DeviceScanCache
from spark_rapids_tpu.sql import TpuSession


@pytest.fixture(autouse=True)
def fresh_cache():
    DeviceScanCache.reset()
    yield
    DeviceScanCache.reset()


def _write(path, vals):
    pq.write_table(
        pa.table({"k": pa.array(np.array(vals) % 8, type=pa.int32()),
                  "v": pa.array(np.array(vals, dtype=np.int64))}),
        path)


def _query(sess, d):
    from spark_rapids_tpu.expr import aggregates as A
    from spark_rapids_tpu.expr.expressions import col

    df = sess.read.parquet(d)
    rows = df.group_by("k").agg(A.agg(A.Sum(col("v")), "s")).collect()
    return sorted(rows)


def test_cache_hit_and_rewrite_invalidation(tmp_path):
    d = str(tmp_path)
    p = os.path.join(d, "t.parquet")
    _write(p, list(range(64)))
    sess = TpuSession({})
    first = _query(sess, d)
    cache = DeviceScanCache.get_instance(RapidsConf({}))
    assert cache is not None
    misses0 = cache.misses
    again = _query(sess, d)
    assert again == first
    assert cache.misses == misses0  # second read served from the pool
    assert cache.hits > 0
    # the stats() API mirrors the raw counters (cache effectiveness was
    # previously unobservable outside the attributes)
    st = cache.stats()
    assert st["hits"] == cache.hits and st["misses"] == cache.misses
    assert st["evictions"] == 0 and st["entries"] >= 1 and st["bytes"] > 0

    # rewrite the file: mtime/size key must miss and recompute
    time.sleep(0.01)  # ensure mtime_ns moves even on coarse filesystems
    _write(p, [10] * 64)
    changed = _query(sess, d)
    assert changed != first
    total = sum(s for _, s in changed)
    assert total == 10 * 64


def test_cache_disabled_by_conf(tmp_path):
    d = str(tmp_path)
    _write(os.path.join(d, "t.parquet"), list(range(32)))
    sess = TpuSession({"spark.rapids.tpu.scan.deviceCache.enabled": False})
    _query(sess, d)
    assert DeviceScanCache._instance is None


def test_cache_lru_eviction():
    c = DeviceScanCache(100)
    c.put(("a", 0, 0, 0, (), None), "A", 60)
    c.put(("b", 0, 0, 0, (), None), "B", 60)  # evicts A
    assert c.evictions == 1
    assert c.get(("a", 0, 0, 0, (), None)) is None
    assert c.get(("b", 0, 0, 0, (), None)) == "B"
    # oversized entries never enter the pool (and are not "evictions")
    c.put(("c", 0, 0, 0, (), None), "C", 1000)
    assert c.get(("c", 0, 0, 0, (), None)) is None
    assert c.stats() == {"hits": 1, "misses": 2, "evictions": 1,
                         "entries": 1, "bytes": 60, "max_bytes": 100}


def test_cache_events_emitted():
    # hit/miss/evict activity lands in the structured event log
    from spark_rapids_tpu import events as EV

    logger = EV.EventLogger(RapidsConf(
        {"spark.rapids.tpu.eventLog.enabled": True}))
    EV.install(logger)
    try:
        c = DeviceScanCache(100)
        c.get(("a", 0, 0, 0, (), None))          # miss
        c.put(("a", 0, 0, 0, (), None), "A", 60)
        c.get(("a", 0, 0, 0, (), None))          # hit
        c.put(("b", 0, 0, 0, (), None), "B", 60)  # evicts a
        ops = [r["op"] for r in logger.records()
               if r["event"] == "scan_cache"]
        assert ops == ["miss", "put", "hit", "put", "evict"]
    finally:
        EV.uninstall()


def test_budget_resize_on_get_instance():
    # a later session's maxBytes governs: the singleton resizes (evicting
    # LRU if shrunk) instead of silently pinning the first session's value
    key = "spark.rapids.tpu.scan.deviceCache.maxBytes"
    inst = DeviceScanCache.get_instance(RapidsConf({key: 200}))
    inst.put(("a", 0, 0, 0, (), None), "A", 80)
    inst.put(("b", 0, 0, 0, (), None), "B", 80)
    grown = DeviceScanCache.get_instance(RapidsConf({key: 500}))
    assert grown is inst and inst.max_bytes == 500
    assert inst.get(("a", 0, 0, 0, (), None)) == "A"
    shrunk = DeviceScanCache.get_instance(RapidsConf({key: 100}))
    assert shrunk is inst and inst.max_bytes == 100
    # LRU eviction down to the new budget: only the most recent survives
    assert inst.get(("b", 0, 0, 0, (), None)) is None
    assert inst.get(("a", 0, 0, 0, (), None)) == "A"


def test_file_key_and_invalidate_normalize_symlinks(tmp_path):
    from spark_rapids_tpu.io.scan_cache import file_key

    real = tmp_path / "real.parquet"
    _write(str(real), list(range(8)))
    link = tmp_path / "link.parquet"
    os.symlink(str(real), str(link))
    k_real = file_key(str(real), 0, ("k",), "batch")
    k_link = file_key(str(link), 0, ("k",), "batch")
    assert k_real == k_link  # one entry per physical file
    c = DeviceScanCache(1000)
    c.put(k_real, "V", 10)
    c.invalidate_path(str(link))  # commit through the symlink still hits
    assert c.get(k_real) is None


@pytest.mark.parametrize("fusion", ["ON", "OFF"])
def test_stage_fusion_modes_agree(tmp_path, fusion):
    # AUTO skips scan->agg fusion on the CPU backend; force both lowerings
    # through the same session query and diff them
    d = str(tmp_path)
    _write(os.path.join(d, "t.parquet"), list(range(256)))
    sess = TpuSession({
        "spark.rapids.tpu.sql.stageFusion": fusion,
        "spark.rapids.tpu.sql.variableFloatAgg.enabled": True,
    })
    rows = _query(sess, d)
    assert rows == sorted(
        (k, sum(v for v in range(256) if v % 8 == k)) for k in range(8))


def _rg(i):
    return ("f", 0, 0, i, (), None)


@pytest.mark.parametrize("splits", [
    [range(29)], [range(16), range(16, 29)],
    [range(8), range(8, 16), range(16, 24), range(24, 29)]],
    ids=["one_split", "two_splits", "four_splits"])
def test_a_scan_that_comes_round_again_keeps_what_the_budget_holds(splits):
    """A table of 29 row groups under a budget of 20, scanned a split at a
    time in every query (probe the split's keys, then put its misses): a
    split's puts do not evict what another split of the same query has
    asked for, so every query after the first finds 20 row groups however
    the scan is split. Plain LRU found 20 under one split and 11 under
    two; one split still IS plain LRU (nothing is ever protected)."""
    c, plain = DeviceScanCache(20 * 10), DeviceScanCache(20 * 10)
    for query in range(1, 6):
        hits = 0
        for part, split in enumerate(splits):
            by = (query, part)
            found = [c.get(_rg(i), by=by) for i in split]
            hits += sum(v is not None for v in found)
            for i, v in zip(split, found):
                if v is None:
                    c.put(_rg(i), i, 10, by=by)
            for i, v in zip(split, [plain.get(_rg(i)) for i in split]):
                if v is None:
                    plain.put(_rg(i), i, 10)
        assert hits == (0 if query == 1 else 20), (query, hits)
        assert c.stats()["bytes"] == 200
        if len(splits) == 1:  # entry for entry what plain LRU keeps
            assert list(c._entries) == list(plain._entries)


def test_what_only_this_part_or_an_earlier_query_asked_for_is_evicted():
    c = DeviceScanCache(30)
    for i in range(3):
        c.put(_rg(i), i, 10, by=(1, "a"))
    assert c.get(_rg(0), by=(2, "a")) == 0   # query 2's first part asks
    c.put(_rg(3), 3, 10, by=(2, "b"))        # its second evicts query 1's
    c.put(_rg(4), 4, 10, by=(2, "b"))
    assert sorted(k[3] for k in c._entries) == [0, 3, 4]
    c.put(_rg(5), 5, 10, by=(2, "b"))        # then its own, in LRU order
    assert sorted(k[3] for k in c._entries) == [0, 4, 5]
    c.get(_rg(4), by=(2, "a")), c.get(_rg(5), by=(2, "a"))
    c.put(_rg(6), 6, 10, by=(2, "b"))        # all another part's: not admitted
    assert sorted(k[3] for k in c._entries) == [0, 4, 5]
    c.put(_rg(7), 7, 10)                     # no query named: plain LRU
    assert sorted(k[3] for k in c._entries) == [4, 5, 7]
