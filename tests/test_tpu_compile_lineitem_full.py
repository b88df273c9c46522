"""Ask the TPU's compiler, without a chip, for what the cell
``lineitem_full.q1`` adds to the programs the other asks hold: the one-host
shuffle's programs with STRING keys and byte planes (the hash exchange under
the ``FINAL`` aggregate, the range exchange under the sort), the merge of
the exchanged partials on plain string keys, and the local sort, under the
configuration's own conf. The rules are ``tpu_compile_asks``'s docstring.

The cell plans two scan splits and Q1 has four groups, so every program
after a split's ``PARTIAL`` merge runs at the bucket of a handful of rows
(128 slots, 128 bytes a string plane) whatever the file's rows: four row
groups of the configuration's data in two splits give the timed
capacities. The programs are captured on the TPU branch
(``jax.default_backend`` patched) where every one of them passes,
``cached_pipeline`` -> ``xla_cost.wrap``, and run on the CPU for the
capture: the merge needs the pieces the exchange cut. The per-row-group
``agg_update`` at 2^21 rows and the scan's programs are cell 2's, which
has run on the chip since PR 25, and are not compiled again here."""
from unittest import mock

import numpy as np
import pytest

import jax

from tpu_compile_asks import (  # noqa: F401  (fixtures)
    HBM_BYTES, compile_all, load_cell, one_chip, topo)

CELL = "lineitem_full.q1"
#: slots of every program past a split's ``PARTIAL`` merge: the bucket of
#: its four groups
CAP = 128
ROW_GROUP = 16384


@pytest.fixture(scope="module")
def q1_programs(tmp_path_factory):
    """``{program word: [(jitted fn, args, kwargs, cache key)]}`` of the
    cell's query over two splits of two row groups each."""
    import pyarrow.parquet as pq

    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.exec.base import clear_pipeline_caches
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell(CELL)
    conf, (query,) = bench["config"]["conf"], bench["queries"]
    assert list(conf) == ["spark.rapids.tpu.sql.variableFloatAgg.enabled"]
    captured = {}

    def capture(fn, site, key):
        def run_and_keep(*args, **kw):
            captured.setdefault(site or fn.__name__, []).append(
                (fn, args, kw, key))
            return fn(*args, **kw)

        return run_and_keep

    data_dir = str(tmp_path_factory.mktemp("li_full_rg"))
    path = bench["generator"].generate(
        bench["config"], 37, data_dir, rows=4 * ROW_GROUP,
        row_group=ROW_GROUP)
    rg_bytes = pq.ParquetFile(path).metadata.row_group(0).total_byte_size
    clear_pipeline_caches()
    DeviceScanCache.reset()
    try:
        with mock.patch.object(xla_cost, "wrap", capture), \
                mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                mock.patch.object(mesh, "device_count", lambda: 1):
            sess = TpuSession(dict(conf, **{
                "spark.rapids.tpu.sql.reader.batchSizeBytes":
                    2 * rg_bytes + 1000}))
            rows = query.frame(sess, data_dir).collect()
            plan = sess.last_executed_plan.tree_string()
    finally:
        clear_pipeline_caches()
        DeviceScanCache.reset()
    assert [r[:2] for r in rows] == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    assert "mode=final" in plan and "mode=partial" in plan, plan
    assert "strategy=MATMUL" in plan and "(local)" in plan, plan
    for part in ("HashPartitioning(keys=[0, 1], n=2)",
                 "RangePartitioning(keys=[0, 1], n=2)"):
        assert "TpuShuffleExchangeExec " + part in plan, plan
    return captured


def _shapes(args):
    return {(x.shape, np.dtype(x.dtype).name)
            for x in jax.tree.leaves(args) if hasattr(x, "shape")}


def _ask(programs, one_chip):
    for secs, mem in compile_all([p[:3] for p in programs], one_chip):
        assert mem.temp_size_in_bytes < HBM_BYTES // 64
        assert secs < 120, secs


@pytest.mark.parametrize("kind", ["Hash", "Range"])
def test_string_key_exchange_compiles_for_v5e(
        kind, q1_programs, one_chip):
    """``jit_exchange`` with ``murmur3`` over string bytes (hash) and with
    the chunked string comparison against the sampled bounds, which are
    constants of the program (range); both sort and gather byte planes."""
    programs = [p for p in q1_programs.get("exchange", ())
                if f"{kind}Partitioning(keys=[0, 1], n=2)" in str(p[3])]
    assert programs, sorted(q1_programs)
    for fn, args, kw, key in programs:
        shapes = _shapes(args)
        # two string keys: offsets and a byte plane each, at the bucket
        assert ((CAP + 1,), "int32") in shapes and (
            (CAP,), "uint8") in shapes, shapes
        assert {s[0] for s, _ in shapes if s} <= {CAP, CAP + 1}, shapes
    _ask(programs, one_chip)


@pytest.mark.parametrize("word", ["exchange_slice", "exchange_concat"])
def test_shuffle_piece_program_with_byte_planes_compiles_for_v5e(
        word, q1_programs, one_chip):
    programs = q1_programs.get(word)
    assert programs, sorted(q1_programs)
    for fn, args, kw, key in programs:
        assert any(dt == "uint8" for _, dt in _shapes(args))
    _ask(programs, one_chip)


def test_merge_on_plain_string_keys_compiles_for_v5e(
        q1_programs, one_chip):
    """The ``agg_update`` program that merges partials whose keys are
    plain strings: a split's ``PARTIAL`` merge of its row groups'
    partials, and the ``FINAL`` merge of the exchanged ones."""
    programs = [p for p in q1_programs.get("agg_update", ())
                if ((CAP + 1,), "int32") in _shapes(p[1])]
    assert programs, sorted(q1_programs)
    for fn, args, kw, key in programs:
        assert all(s[0] != ROW_GROUP for s, _ in _shapes(args) if s)
    _ask(programs, one_chip)


def test_local_sort_on_string_keys_compiles_for_v5e(
        q1_programs, one_chip):
    programs = q1_programs.get("sort")
    assert programs and len(programs) == 2, sorted(q1_programs)  # a partition
    _ask(programs, one_chip)
