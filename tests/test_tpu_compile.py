"""Ask the TPU's compiler, without a chip, for the programs of
``chip_smoke.py``'s query at row-group capacity ``1 << 21``.

The v5e compiler is installed with jax and compiles for a chip that is
DESCRIBED, not attached (on-chip-measurement guide, section 2): what it
refuses here costs no chip time. Nothing runs, so these tests say nothing
about results or times — a compile that passes is not a chip run.

Rules this file keeps (the suite runs under six xdist workers, each of
which imports every test file): the topology is described inside a
module-scoped fixture that skips when it cannot be — never at import,
never in conftest, never autouse; no child process; the persistent
compile cache is off around the compiles (a TPU executable written there
cannot be read back without a chip); everything lives in THIS one file.

Engine code that asks ``jax.default_backend()`` while planning or tracing
would take its CPU branch here, so the tests patch that answer to
``"tpu"`` for the capture — in the test, never through a program option.
Programs are captured where every one of them passes,
``exec/base.cached_pipeline`` -> ``xla_cost.wrap``, and are NOT executed:
each dispatch is answered with zeros of the right shapes.
"""
import os
import time
from unittest import mock

import numpy as np
import pytest

import jax
import jax.numpy as jnp

os.environ.setdefault("TPU_LOG_DIR", "disabled")

CAP = 1 << 21


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        if hasattr(x, "shape") else x, tree)


@pytest.fixture(scope="module")
def smoke_programs(tmp_path_factory):
    """The programs the smoke query dispatches on the TPU branch, at one
    row group of ``CAP`` rows: ``{site: [(jitted fn, args, kwargs)]}``.
    Captured once per module; the pipeline caches are cleared around the
    capture so zero-answering wrappers never leak into other tests."""
    import chip_smoke
    from spark_rapids_tpu import xla_cost
    from spark_rapids_tpu.exec.base import clear_pipeline_caches
    from spark_rapids_tpu.expr import expressions as E
    from spark_rapids_tpu.expr.expressions import col, lit
    from spark_rapids_tpu.io.scan_cache import DeviceScanCache
    from spark_rapids_tpu.sql import TpuSession

    captured = {}

    def capture(fn, site, key):
        def answer_with_zeros(*args, **kw):
            captured.setdefault(site, []).append((fn, args, kw))
            out = jax.eval_shape(fn, *args, **kw)
            return jax.tree.map(
                lambda s: jnp.zeros(s.shape, s.dtype), out)

        return answer_with_zeros

    data_dir = str(tmp_path_factory.mktemp("smoke_rg"))
    chip_smoke.make_data(data_dir, CAP, seed=19, row_group=CAP)
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
            chip_smoke.frame(
                TpuSession(chip_smoke.CONF), data_dir).collect()
            sess = TpuSession({**chip_smoke.CONF, **off})
            chip_smoke.frame(sess, data_dir).collect()
            # the fused filter chain on its own (no aggregate above it)
            sess.read.parquet(data_dir).where(E.GreaterThanOrEqual(
                col("ss_sold_date_sk"), lit(chip_smoke.DATE_CUT))).collect()
    finally:
        clear_pipeline_caches()
        DeviceScanCache.reset()
    return captured


def _compile_all(programs, sharding):
    """Compile every captured dispatch once per distinct signature;
    returns [(seconds, memory_analysis)]."""
    done = {}
    for fn, args, kw in programs:
        sargs, skw = _on(sharding, (args, kw))
        sig = (id(fn), str(sargs), str(skw))
        if sig in done:
            continue
        t0 = time.perf_counter()
        with mock.patch.object(jax, "default_backend", lambda: "tpu"):
            compiled = fn.lower(*sargs, **skw).compile()
        done[sig] = (time.perf_counter() - t0, compiled.memory_analysis())
    return list(done.values())


#: one v5e chip's HBM; a program whose temporaries alone pass a quarter of
#: it for ONE 2^21-row group is sized by a layout accident, not by data
#: (the byte-buffer unpack this PR replaced asked 904 MiB for 14 MiB)
HBM_BYTES = 16 << 30


@pytest.mark.parametrize("site", [
    "upload_unpack", "pq_decode", "fused_chain", "project"])
def test_smoke_query_piece_compiles_for_v5e(
        site, smoke_programs, one_chip, no_persistent_cache):
    programs = smoke_programs.get(site)
    assert programs, (
        f"the smoke query dispatched no {site!r} program; captured "
        f"{sorted(smoke_programs)}")
    for secs, mem in _compile_all(programs, one_chip):
        assert mem.temp_size_in_bytes < HBM_BYTES // 64, (
            site, mem.temp_size_in_bytes)
        # the narrow-minor-dim bitcasts took the compiler ~20 minutes
        assert secs < 60, (site, secs)


def test_smoke_query_aggregate_compiles_for_v5e(
        smoke_programs, one_chip, no_persistent_cache):
    """The program the chip really runs under the default conf: the fused
    scan->filter->aggregate stage (decode + chain + MATMUL update + merge
    + result projection), here over one row group of 2^21 rows. Minutes,
    not seconds — the v5e compiler spends ~170 s on the groupby's stable
    3-key sort at ANY capacity (measured by this PR's compile asks)."""
    programs = smoke_programs.get("agg_stage")
    assert programs, sorted(smoke_programs)
    ((secs, mem),) = _compile_all(programs[:1], one_chip)
    assert mem.temp_size_in_bytes < HBM_BYTES // 4, mem.temp_size_in_bytes


def _compile_mesh_program_for_four_chips(topo, collect, patches=()):
    """Run ``collect()`` on the virtual CPU devices with the engine's
    ``shard_map`` spied: the SPMD program is captured at its dispatch (it
    never runs), re-targeted at four DESCRIBED chips and compiled there.
    Returns (the dispatch's argument shapes, the compiled program)."""
    import contextlib

    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from spark_rapids_tpu.exec import mesh as XM
    from spark_rapids_tpu.parallel.mesh import (
        AXIS, mesh_jit_kwargs, shard_map)

    class Captured(Exception):
        pass

    cap = {}

    def spy_shard_map(f, mesh, in_specs, out_specs, **kw):
        def stop_at_dispatch(*args):
            cap.update(f=f, in_specs=in_specs, out_specs=out_specs,
                       shapes=[(a.shape, a.dtype) for a in args])
            raise Captured()

        return stop_at_dispatch

    with contextlib.ExitStack() as stack:
        stack.enter_context(mock.patch.object(XM, "shard_map", spy_shard_map))
        stack.enter_context(
            mock.patch.object(jax, "default_backend", lambda: "tpu"))
        for patch in patches:
            stack.enter_context(patch)
        with pytest.raises(Captured):
            collect()
        chips = Mesh(np.array(topo.devices[:4]), (AXIS,))
        rows_on_chips = NamedSharding(chips, P(AXIS))
        fn = jax.jit(
            shard_map(cap["f"], mesh=chips, in_specs=cap["in_specs"],
                      out_specs=cap["out_specs"]), **mesh_jit_kwargs())
        compiled = fn.lower(*[
            jax.ShapeDtypeStruct(s, dt, sharding=rows_on_chips)
            for s, dt in cap["shapes"]]).compile()
    return cap["shapes"], compiled


def test_mesh_aggregate_compiles_for_four_v5e_chips(
        topo, no_persistent_cache, tmp_path):
    """``chip_smoke.py --mesh 4``'s one program across chips:
    ``TpuMeshAggregateExec``'s shard_map groupby with its all_to_all
    exchange, compiled for four DESCRIBED chips. Without
    ``parallel/mesh.mesh_jit_kwargs`` the compiler aborts the whole
    process here (conditional-code-motion, see that docstring). The
    program is captured from a run staged on the virtual CPU devices and
    re-targeted at the described mesh; size does not matter to the
    fault (65,536 rows abort like 28.8M do)."""
    import chip_smoke
    from spark_rapids_tpu.sql import TpuSession

    rows = chip_smoke.REHEARSE_ROWS
    chip_smoke.make_data(str(tmp_path), rows, seed=19, row_group=rows // 4)
    sess = TpuSession({
        **chip_smoke.CONF,
        "spark.rapids.tpu.shuffle.mode": "ici",
        "spark.rapids.tpu.sql.reader.batchSizeBytes": 1,
        "spark.rapids.tpu.mesh.devices": 4})
    _, compiled = _compile_mesh_program_for_four_chips(
        topo, lambda: chip_smoke.frame(sess, str(tmp_path)).collect())
    assert "all-to-all" in compiled.as_text()


#: slots a shard of ``tpcds_sf100_store_sales_mesh4``: up to 73.4 M rows padded
#: to a power of two
SF100_SHARD_CAP = 1 << 27


def test_mesh_aggregate_compiles_at_sf100_shard_capacity(
        topo, no_persistent_cache, tmp_path):
    """The same SPMD aggregate at the capacity the benchmark's four-chip
    cell runs it: 2^27 slots a shard, the planes of a cached relation
    (``store_sales_sf100.cached_report.mesh4``). 12.9 GB of planes are not
    staged here: the stage is handed shapes where the cell hands resident
    planes, the program is captured at its dispatch and re-targeted at four
    described chips. What the compiler says of memory is what one program
    needs beside its 3.2 GB of arguments a chip: it has to fit the chip,
    which the one-piece update (``exec/mesh.AGG_UPDATE_CHUNK_ROWS`` at or
    above the shard's slots) does not."""
    import importlib.util
    import json

    from spark_rapids_tpu.exec import mesh as XM
    from spark_rapids_tpu.sql import TpuSession

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    spec = importlib.util.spec_from_file_location(
        "cached_report_query", os.path.join(
            bench, "queries", "store_sales_cached_quantity_report.py"))
    query = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(query)
    with open(os.path.join(
            bench, "configs", "tpcds_sf100_store_sales_mesh4.json")) as f:
        config = json.load(f)

    def shapes_for_planes(self, child):
        """``_stage_child`` with nothing staged: the absorbed chain and
        the planes' shapes at the cell's capacity."""
        base, steps = self._absorb_chain(child)
        n = self.n_shards
        cols = []
        for f in base.output_schema.fields:
            cols.append(jax.ShapeDtypeStruct(
                (n * SF100_SHARD_CAP,), f.dataType.to_numpy()))
            cols.append(jax.ShapeDtypeStruct((n * SF100_SHARD_CAP,), bool))
        fields = base.output_schema.fields
        return XM.StagedChild(
            cols, np.full(n, 72_000_000, np.int32), SF100_SHARD_CAP,
            tuple(("f",) for _ in fields), tuple(0 for _ in fields), steps,
            source="cached")

    # a file of the deployment's schema, so that the plan is the cell's
    import pyarrow as pa
    import pyarrow.parquet as pq
    pq.write_table(pa.table({
        c["name"]: pa.array(np.ones(8, c["type"])) for c in config["columns"]
    }), str(tmp_path / query.TABLE))
    sess = TpuSession(config["conf"])
    shapes, compiled = _compile_mesh_program_for_four_chips(
        topo, lambda: query.frame(sess, str(tmp_path)).collect(),
        patches=[mock.patch.object(XM._MeshStage, "_stage_child",
                                   shapes_for_planes)])
    sess.close()
    assert shapes[0][0] == (4 * SF100_SHARD_CAP,)
    assert "all-to-all" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the planes are the cached relation's, resident beside the program:
    # 20 bytes of values a slot (and 4 validity bytes the compiler packs)
    assert mem.argument_size_in_bytes >= SF100_SHARD_CAP * 20, mem
    # updated in one piece the program asks 21 GB of temporaries a chip
    # and is refused; in chunks of reshaped planes 6.8 GB; in chunks
    # sliced from the resident planes 2.5 GB (PR 30's asks)
    assert mem.temp_size_in_bytes < HBM_BYTES // 4, mem
    # the float sum rides the limb matmul as fixed-point limbs (PR 31): of
    # the aggregate's two halves no scatter that walks a chunk's slots, or
    # the merge's 262,144 received partial rows, is left on the taken
    # path; those that remain sit in a branch of a conditional (the float
    # detour, the hash and sort tiers). The exchange places its rows by
    # scatter under its own scope word: not the aggregate's
    walks = [w for w in _row_sized_scatters(compiled.as_text(), 1 << 16)
             if "/agg_update/" in w[1] or "/agg_merge/" in w[1]]
    assert {n for n, _ in walks} == {XM.AGG_UPDATE_CHUNK_ROWS, 4 << 16}, walks
    assert [w for w in walks if "/cond/branch_" not in w[1]] == [], walks


def _row_sized_scatters(text, rows):
    """(indices' elements, op_name) of every ``scatter`` instruction of a
    compiled program's text whose indices operand has ``rows`` elements or
    more. A scatter of N arrays has 2N+1 operands: the indices are the
    middle one."""
    import math
    import re

    shape_of = dict(re.findall(
        r"^\s*(?:ROOT )?(%[\w.-]+) = \(?\w+\[([\d,]*)\]", text, re.M))
    found = []
    for line in text.splitlines():
        m = re.search(r" scatter\(([^)]*)\)", line)
        if not m:
            continue
        operands = [o.strip().split(" ")[-1] for o in m.group(1).split(",")]
        dims = shape_of[operands[len(operands) // 2]]
        n = math.prod(int(d) for d in dims.split(",") if d)
        name = re.search(r'op_name="([^"]*)"', line)
        if n >= rows:
            found.append((n, name.group(1) if name else ""))
    return found


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
    assert _row_sized_scatters(text, 1) == [
        (4096, "jit(f)/agg_update/cond/branch_1_fun/scatter-add"),
        (64, "jit(f)/agg_merge/scatter-add")]
    assert _row_sized_scatters(text, 100) == [
        (4096, "jit(f)/agg_update/cond/branch_1_fun/scatter-add")]


# ---------------------------------------------------------------------------
# The Pallas kernels have only ever run with interpret=True. They are NOT
# on the smoke query's path (AUTO never picks PALLAS). Mosaic refuses all
# three families today; the engine raises envinfo.MosaicRefused by name on
# the chip, and these strict xfails keep the ask so the day a kernel
# compiles the suite says so (ROADMAP A6).
# ---------------------------------------------------------------------------
def _mosaic_compile(module, fn, shapes, sharding):
    with mock.patch.object(module, "_interpret", lambda: False):
        return jax.jit(fn).lower(*_on(sharding, shapes)).compile()


def _s(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic: failed to legalize operation 'func.return' (i32, i64) — the "
    "BlockSpec index maps trace to i64 under jax_enable_x64"))
def test_pallas_groupby_reduce_compiles_for_v5e(
        one_chip, no_persistent_cache):
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
        one_chip, no_persistent_cache):
    from spark_rapids_tpu.ops import pallas_groupby as PG

    _mosaic_compile(
        PG, lambda seg, hi, lo: PG.pallas_bucket_winner(
            seg, 128, "min", hi, lo),
        [_s((CAP,), np.int32), _s((CAP,), np.uint32),
         _s((CAP,), np.uint32)], one_chip)


@pytest.mark.xfail(strict=True, reason=(
    "RecursionError: maximum recursion depth exceeded while lowering the "
    "probe kernel for Mosaic"))
def test_pallas_join_probe_compiles_for_v5e(one_chip, no_persistent_cache):
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
        one_chip, no_persistent_cache):
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
    from spark_rapids_tpu import types as T
    from spark_rapids_tpu.conf import RapidsConf
    from spark_rapids_tpu.exec.aggregate import choose_agg_strategy

    with pytest.raises(ValueError, match="no roofline peaks"):
        choose_agg_strategy(
            RapidsConf({}), CAP, ("count",), (None,), (T.INT,),
            backend="rocm")
    pick, _ = choose_agg_strategy(
        RapidsConf({}), CAP, ("count",), (None,), (T.INT,), backend="tpu")
    assert pick in ("MATMUL", "RADIX", "SORT")
