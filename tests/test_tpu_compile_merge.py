"""Ask the TPU's compiler, without a chip, for the program that builds a
synced merge's input (``exec/aggregate._merge_concat``) at the shapes both
TPC-H Q1 cells give it: partials of a row group's capacity (2^21 slots)
that hold Q1's four groups, cut to the bucket of 128 slots, their two
dictionary-string keys expanded there and spliced with Q1's buffer columns.
``lineitem.q1`` merges 29 such partials in one program; ``lineitem_full.q1``
merges 16 and 13, a program a scan split. The rules are
``tpu_compile_asks``'s docstring.

The partials' layout is captured from the cell's own query on the TPU
branch (``jax.default_backend`` patched) over four small row groups, where
the merge's program passes ``cached_pipeline`` -> ``xla_cost.wrap``; the
compile is asked at the timed capacity with the count of partials each
cell merges."""
from unittest import mock

import numpy as np
import pytest

import jax

from tpu_compile_asks import (  # noqa: F401  (fixtures)
    CAP, compile_all, load_cell, no_persistent_cache, one_chip, topo)

ROW_GROUP = 16384
#: the bucket of Q1's four groups
LIVE = 128


@pytest.fixture(scope="module")
def captured_merge(tmp_path_factory):
    """(args, key) of the merge program's dispatch in ``lineitem.q1``'s
    query over four row groups of the configuration's data."""
    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.exec.base import clear_pipeline_caches
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.parallel import mesh
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell("lineitem.q1")
    conf, (query,) = bench["config"]["conf"], bench["queries"]
    captured = []

    def capture(fn, site, key):
        def run_and_keep(*args, **kw):
            if key[0] == "merge_concat":
                captured.append((args, key))
            return fn(*args, **kw)

        return run_and_keep

    data_dir = str(tmp_path_factory.mktemp("q1_merge"))
    bench["generator"].generate(
        bench["config"], 41, data_dir, rows=4 * ROW_GROUP,
        row_group=ROW_GROUP)
    clear_pipeline_caches()
    DeviceScanCache.reset()
    try:
        with mock.patch.object(xla_cost, "wrap", capture), \
                mock.patch.object(jax, "default_backend", lambda: "tpu"), \
                mock.patch.object(mesh, "device_count", lambda: 1):
            rows = query.frame(TpuSession(conf), data_dir).collect()
    finally:
        clear_pipeline_caches()
        DeviceScanCache.reset()
    assert [r[:2] for r in rows] == [
        ("A", "F"), ("N", "F"), ("N", "O"), ("R", "F")]
    assert len(captured) == 1, [k[:1] for _, k in captured]
    return captured[0]


def _at_timed_capacity(partial):
    """One partial's planes as shapes, its row planes at ``CAP`` slots
    (the offsets at ``CAP + 1``); a dictionary keeps its entries."""
    def grow(x):
        n = x.shape[0] if x.shape else None
        shape = ((CAP,) if n == ROW_GROUP else (CAP + 1,)
                 if n == ROW_GROUP + 1 else x.shape)
        return jax.ShapeDtypeStruct(shape, x.dtype)

    return jax.tree.map(grow, partial)


@pytest.mark.parametrize("parts", [(29,), (16, 13)],
                         ids=["lineitem.q1", "lineitem_full.q1"])
def test_merge_at_the_rows_held_compiles_for_v5e(
        parts, captured_merge, one_chip, no_persistent_cache):
    from spark_rapids_tpu.columnar.column import choose_capacity
    from spark_rapids_tpu.exec import aggregate as XA
    from spark_rapids_tpu.expr.values import DictV

    (partials, counts), key = captured_merge
    partial = partials[0]
    # Q1's partial: its two keys still dictionary-encoded, then the
    # buffers, every row plane at the row group's capacity
    assert [type(v).__name__ for v in partial[:2]] == ["DictV", "DictV"]
    assert key[2] == (LIVE,) * len(partials)
    timed = _at_timed_capacity(partial)
    assert isinstance(timed[0], DictV) and timed[0].codes.shape == (CAP,)
    programs = []
    for n in parts:
        fn = XA._merge_concat(
            ("compile ask", n), (LIVE,) * n, choose_capacity(4 * n))
        programs.append((fn, ([timed] * n, np.zeros(n, np.int32)), {}))
    for secs, mem in compile_all(programs, one_chip):
        # nothing is expanded at the partials' capacity: the two keys of
        # 29 partials at 2^21 slots would take some 0.7 GB (offsets, bytes
        # and validity); the program at 128 slots asks 13 MB
        assert mem.temp_size_in_bytes < 32 << 20, mem.temp_size_in_bytes
        assert secs < 120, secs
