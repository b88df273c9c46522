"""Ask the TPU's compiler, without a chip, for the one-chip programs of the
benchmark's cell ``store_sales.quantity_report`` at row-group capacity
``1 << 21``: the per-batch pieces, the fused scan->aggregate stage, and the
Pallas kernels it refuses. The rules are ``tpu_compile_asks``'s docstring;
the four-chip programs have a file each beside this one.

Programs are captured where every one of them passes,
``exec/base.cached_pipeline`` -> ``xla_cost.wrap``, and are NOT executed:
each dispatch is answered with zeros of the right shapes.
"""
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from tpu_compile_asks import (  # noqa: F401  (fixtures)
    CAP, HBM_BYTES, compile_all, load_cell, on,
    one_chip, row_sized_scatters, topo)


@pytest.fixture(scope="module")
def report_programs(tmp_path_factory):
    """The programs the cell's query dispatches on the TPU branch, at one
    row group of ``CAP`` rows of the cell's own data (every column's whole
    domain in it, so ``ss_item_sk``'s dictionary is the cell's 102,000):
    ``{site: [(jitted fn, args, kwargs)]}``. Captured once per module; the
    pipeline caches are cleared around the capture so zero-answering
    wrappers never leak into other tests."""
    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.exec.base import clear_pipeline_caches
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell("store_sales.quantity_report")
    conf, (query,) = bench["config"]["conf"], bench["queries"]
    captured = {}

    def capture(fn, site, key):
        def answer_with_zeros(*args, **kw):
            captured.setdefault(site, []).append((fn, args, kw))
            out = jax.eval_shape(fn, *args, **kw)
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), out)

        return answer_with_zeros

    data_dir = str(tmp_path_factory.mktemp("report_rg"))
    bench["generator"].generate(
        bench["config"], 19, data_dir, rows=CAP, row_group=CAP)
    # per-batch path (what the CPU backend takes): separate decode,
    # unpack, update programs — the pieces
    off = {"spark.rapids.tpu.sql.stageFusion": "OFF",
           "spark.rapids.tpu.sql.agg.fusedPlan": "OFF"}
    clear_pipeline_caches()
    DeviceScanCache.reset()
    try:
        with mock.patch.object(xla_cost, "wrap", capture), \
                mock.patch.object(jax, "default_backend", lambda: "tpu"):
            # the default conf on a TPU: ONE fused scan->agg stage program
            query.frame(TpuSession(conf), data_dir).collect()
            sess = TpuSession({**conf, **off})
            query.frame(sess, data_dir).collect()
            # the fused filter chain on its own (no aggregate above it)
            sess.read.parquet(data_dir).where(E.GreaterThanOrEqual(
                col("ss_sold_date_sk"), lit(query.DATE_CUT))).collect()
    finally:
        clear_pipeline_caches()
        DeviceScanCache.reset()
    return captured


@pytest.mark.parametrize("site", [
    "upload_unpack", "pq_decode", "fused_chain", "project"])
def test_smoke_query_piece_compiles_for_v5e(
        site, report_programs, one_chip):
    programs = report_programs.get(site)
    assert programs, (
        f"the cell's query dispatched no {site!r} program; captured "
        f"{sorted(report_programs)}")
    for secs, mem in compile_all(programs, one_chip):
        # a program whose temporaries pass 256 MiB for ONE 2^21-row group
        # is sized by a layout accident, not by data (the byte-buffer
        # unpack asked 904 MiB for 14 MiB)
        assert mem.temp_size_in_bytes < HBM_BYTES // 64, (
            site, mem.temp_size_in_bytes)
        # the narrow-minor-dim bitcasts took the compiler ~20 minutes
        assert secs < 60, (site, secs)


def test_smoke_query_aggregate_compiles_for_v5e(
        report_programs, one_chip):
    """The program the chip really runs under the cell's conf: the fused
    scan->filter->aggregate stage (decode + chain + MATMUL update + merge
    + result projection), here over one row group of 2^21 rows. Minutes,
    not seconds — the v5e compiler spends ~170 s on the groupby's stable
    3-key sort at ANY capacity (measured by PR 23's compile asks)."""
    programs = report_programs.get("agg_stage")
    assert programs, sorted(report_programs)
    ((secs, mem),) = compile_all(programs[:1], one_chip)
    assert mem.temp_size_in_bytes < HBM_BYTES // 4, mem.temp_size_in_bytes


def test_row_sized_scatters_reads_a_programs_text():
    text = """
%fused.1 (param_0.1: f32[128], param_1.1: s32[4096,1], p2: f32[4096]) -> f32[128] {
  %param_0.1 = f32[128]{0:T(128)} parameter(0)
  %param_1.1 = s32[4096,1]{1,0} parameter(1)
  %p2 = f32[4096]{0} parameter(2)
  ROOT %scatter.1 = f32[128]{0} scatter(%param_0.1, %param_1.1, %p2), to_apply=%add, metadata={op_name="jit(f)/agg_update/cond/branch_1_fun/scatter-add"}
}
%fused.2 {
  %a = f32[8]{0} parameter(0)
  %b = f32[8]{0} parameter(1)
  %i = s32[64]{0} parameter(2)
  %u = f32[64]{0} parameter(3)
  %v = f32[64]{0} parameter(4)
  ROOT %scatter.2 = (f32[8]{0}, f32[8]{0}) scatter(%a, %b, %i, %u, %v), to_apply=%add2, metadata={op_name="jit(f)/agg_merge/scatter-add"}
}
"""
    assert row_sized_scatters(text, 1) == [
        (4096, "jit(f)/agg_update/cond/branch_1_fun/scatter-add"),
        (64, "jit(f)/agg_merge/scatter-add")]
    assert row_sized_scatters(text, 100) == [
        (4096, "jit(f)/agg_update/cond/branch_1_fun/scatter-add")]


# ---------------------------------------------------------------------------
# The Pallas kernels have only ever run with interpret=True. They are NOT
# on the cells' path (AUTO never picks PALLAS). Mosaic refuses all
# three families today; the engine raises envinfo.MosaicRefused by name on
# the chip, and these strict xfails keep the ask so the day a kernel
# compiles the suite says so (ROADMAP A6).
# ---------------------------------------------------------------------------
def _mosaic_compile(module, fn, shapes, sharding):
    with mock.patch.object(module, "_interpret", lambda: False):
        return jax.jit(fn).lower(*on(sharding, shapes)).compile()


def _s(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: failed to legalize operation 'func.return' (i32, i64) — the "
    "BlockSpec index maps trace to i64 under jax_enable_x64"))
def test_pallas_groupby_reduce_compiles_for_v5e(
        one_chip):
    from spark_rapids_tpu.ops import pallas_groupby as PG

    def reduce_(seg, iv, valid, fv):
        return PG.pallas_bucket_reduce(
            seg, 128, [(iv, valid)], [valid], [(fv, valid)])

    _mosaic_compile(PG, reduce_, [
        _s((CAP,), np.int32), _s((CAP,), np.int64), _s((CAP,), np.bool_),
        _s((CAP,), np.float64)], one_chip)


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: Reductions over unsigned integers not implemented (the "
    "winner kernel's u32 min/max)"))
def test_pallas_groupby_winner_compiles_for_v5e(
        one_chip):
    from spark_rapids_tpu.ops import pallas_groupby as PG

    _mosaic_compile(
        PG, lambda seg, hi, lo: PG.pallas_bucket_winner(
            seg, 128, "min", hi, lo),
        [_s((CAP,), np.int32), _s((CAP,), np.uint32),
         _s((CAP,), np.uint32)], one_chip)


@pytest.mark.xfail(strict=True, reason=(
    "RecursionError: maximum recursion depth exceeded while lowering the "
    "probe kernel for Mosaic"))
def test_pallas_join_probe_compiles_for_v5e(one_chip):
    from spark_rapids_tpu.ops import pallas_join as PJ

    def probe(bhi, blo, cnt, phi, plo, live):
        return PJ.pallas_probe_ranges([bhi, blo], cnt, [phi, plo], live)

    _mosaic_compile(PJ, probe, [
        _s((CAP,), np.uint32), _s((CAP,), np.uint32), _s((), np.int32),
        _s((CAP,), np.uint32), _s((CAP,), np.uint32),
        _s((CAP,), np.bool_)], one_chip)


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: Not implemented: changeBitwidth when minor tiling is not 128 "
    "(1-D u8 blocks widened to i32)"))
def test_pallas_udf_word_starts_compiles_for_v5e(
        one_chip):
    from spark_rapids_tpu.udf import native as UN

    _mosaic_compile(UN, UN._word_starts_pallas,
                    [_s((1 << 24,), np.uint8)], one_chip)


@pytest.mark.parametrize("module,call", [
    ("spark_rapids_tpu.ops.pallas_groupby", "_interpret"),
    ("spark_rapids_tpu.ops.pallas_join", "_interpret"),
    ("spark_rapids_tpu.udf.native", "_interpret"),
])
def test_refused_pallas_kernel_fails_by_name_on_tpu(module, call):
    import importlib

    from spark_rapids_tpu.envinfo import MosaicRefused

    mod = importlib.import_module(module)
    assert getattr(mod, call)() is True  # the CPU backend interprets
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        with pytest.raises(MosaicRefused, match="Mosaic"):
            getattr(mod, call)()
    with mock.patch.object(jax, "default_backend", lambda: "rocm"):
        with pytest.raises(RuntimeError, match="neither"):
            getattr(mod, call)()


def test_agg_chooser_refuses_an_unknown_backend():
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec.aggregate import choose_agg_strategy

    with pytest.raises(ValueError, match="knows no backend"):
        choose_agg_strategy(
            RapidsConf({}), CAP, ("count",), (None,), backend="rocm")
    pick, _ = choose_agg_strategy(
        RapidsConf({}), CAP, ("count",), (None,), backend="tpu")
    assert pick == "MATMUL"
