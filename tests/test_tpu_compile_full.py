"""Ask the TPU's compiler, without a chip, for what the cell
``store_sales_full.quantity_report`` adds to the programs the other asks
hold: the one-host shuffle's three programs and the ``FINAL`` merge of the
exchanged partials, under the configuration's own conf (no
``sql.agg.strategy``: AUTO). The rules are ``tpu_compile_asks``'s docstring.

The cell plans two scan splits and 100 groups, so two map inputs send
pieces of about 50 rows to two reduce partitions, and the final merge runs
at capacity 256 whatever the file's rows: two row groups of the
configuration's data give the real capacity. The programs are captured on
the TPU branch (``jax.default_backend`` patched) where every one of them
passes, ``cached_pipeline`` -> ``xla_cost.wrap``, and run on the CPU for
the capture: the merge needs the pieces the exchange cut. The fused
``PARTIAL`` stages are ``test_tpu_compile.py``'s family (minutes each) and
are not compiled again here."""
from unittest import mock

import pytest

import jax

from tpu_compile_asks import (  # noqa: F401  (fixtures)
    HBM_BYTES, REFUSED_ON_V5E, compile_all, load_cell, no_persistent_cache,
    on, one_chip, topo)

CELL = "store_sales_full.quantity_report"
#: capacity of the final merge: two splits x 100 groups, rounded up
FINAL_CAP = 256
#: slots the exchange's map program partitions: the bucket of a partial's
#: 100 groups, whatever capacity the partial was left at
MAP_CAP = 128


@pytest.fixture(scope="module")
def full_programs(tmp_path_factory):
    """``{program word: [(jitted fn, args, kwargs)]}`` of the cell's query
    over two splits of one row group each."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.exec.base import clear_pipeline_caches
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell(CELL)
    conf, (query,) = bench["config"]["conf"], bench["queries"]
    assert "spark.rapids.tpu.sql.agg.strategy" not in conf
    captured = {}

    def capture(fn, site, key):
        def run_and_keep(*args, **kw):
            captured.setdefault(site or fn.__name__, []).append(
                (fn, args, kw))
            return fn(*args, **kw)

        return run_and_keep

    data_dir = str(tmp_path_factory.mktemp("full_rg"))
    path = bench["generator"].generate(
        bench["config"], 35, data_dir, rows=2 * 16384, row_group=16384)
    rg_bytes = pq.ParquetFile(path).metadata.row_group(0).total_byte_size
    clear_pipeline_caches()
    DeviceScanCache.reset()
    try:
        with mock.patch.object(xla_cost, "wrap", capture), \
                mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                mock.patch.object(mesh, "device_count", lambda: 1):
            sess = TpuSession(dict(conf, **{
                "spark.rapids.tpu.sql.reader.batchSizeBytes": rg_bytes + 1}))
            rows = query.frame(sess, data_dir).collect()
            plan = sess.last_executed_plan.tree_string()
    finally:
        clear_pipeline_caches()
        DeviceScanCache.reset()
    assert len(rows) == 100
    assert "mode=final" in plan and "strategy=MATMUL" in plan, plan
    assert "TpuShuffleExchangeExec HashPartitioning(keys=[0], n=2)" in plan
    return captured


def test_final_merge_of_the_exchanged_partials_compiles_for_v5e(
        full_programs, one_chip, no_persistent_cache):
    programs = full_programs.get("agg_plan")
    assert programs, sorted(full_programs)
    (fn, args, kw), = programs
    caps = {x.shape[0] for x in jax.tree.leaves(args) if x.ndim == 1}
    assert caps == {FINAL_CAP}, caps
    ((secs, mem),) = compile_all(programs, one_chip)
    assert mem.temp_size_in_bytes < HBM_BYTES // 64
    assert secs < 120, secs


def test_map_programs_partition_the_bucket_of_a_partials_groups(
        full_programs):
    """What cell 4's exchange costs rests on this: a ``PARTIAL`` stage
    leaves its 100 groups at the capacity of its stacked row groups (here
    one of 16,384 rows, on the chip 2^25 and 2^24 slots), and the map
    program sorts and gathers every slot it is handed."""
    programs = full_programs.get("exchange")
    assert programs and len(programs) == 2, sorted(full_programs)  # a split
    for fn, args, kw in programs:
        caps = {x.shape[0] for x in jax.tree.leaves(args) if x.ndim == 1}
        assert caps == {MAP_CAP}, caps
    # and the cut that hands it over is a ``jit_exchange_slice`` of its own
    cuts = [args for fn, args, kw in full_programs["exchange_slice"]
            if len(args) == 1]
    assert len(cuts) == 2
    for args in cuts:
        assert {x.shape[0] for x in jax.tree.leaves(args)} == {16384}


@pytest.mark.parametrize("word", [
    "exchange", "exchange_slice", "exchange_concat"])
def test_shuffle_program_compiles_for_v5e(
        word, full_programs, one_chip, no_persistent_cache):
    programs = full_programs.get(word)
    assert programs, sorted(full_programs)
    for secs, mem in compile_all(programs, one_chip):
        assert mem.temp_size_in_bytes < HBM_BYTES // 64
        assert secs < 120, (word, secs)


@pytest.mark.xfail(strict=True, reason=REFUSED_ON_V5E["RADIX"])
def test_radix_merge_at_the_final_capacity_compiles_for_v5e(
        one_chip, no_persistent_cache):
    """What AUTO resolved for this merge before it stopped offering RADIX
    on the chip; the ask stays so the day the compiler takes it the suite
    says so."""
    import numpy as np

    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.expr.eval import ColV
    from spark_rapids_tpu.ops import groupby as G

    def merge(k, kv, s, sv, q, qv, n):
        keys, aggs, nseg = G.groupby_agg(
            [ColV(k, kv)], [T.INT], [ColV(s, sv), ColV(q, qv)],
            ["sum", "sum"], n, (), approx_float_sum=True, strategy="RADIX")
        return ([(c.data, c.validity) for c in keys],
                [(c.data, c.validity) for c in aggs], nseg)

    def s(dt):
        return jax.ShapeDtypeStruct((FINAL_CAP,), dt)

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jax.jit(merge).lower(*on(one_chip, [
            s(np.int32), s(np.bool_), s(np.float64), s(np.bool_),
            s(np.int64), s(np.bool_),
            jax.ShapeDtypeStruct((), np.int32)])).compile()
