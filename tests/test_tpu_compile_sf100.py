"""Ask the TPU's compiler, without a chip, for the SPMD aggregate of the
four-chip cell ``store_sales_sf100.cached_report.mesh4`` at the capacity the
cell runs it. The rules are ``tpu_compile_asks``'s docstring."""
from unittest import mock

import numpy as np
import pytest

import jax

from tpu_compile_asks import (  # noqa: F401  (fixtures)
    HBM_BYTES, IN_CHUNK_BRANCH, chunk_updates,
    compile_mesh_program_for_four_chips, computations_under_a_conditional,
    instructions_named, load_cell, row_sized_scatters,
    topo)

#: slots a shard of ``tpcds_sf100_store_sales_mesh4``: up to 73.4 M rows padded
#: to a power of two
SF100_SHARD_CAP = 1 << 27


# slow: 474 s beside only the other compile asks, 733-822 s of one worker
# in a cold run of the suite under six workers on an 8-core host: more than
# half the suite's 1,470 s budget. The benchmark's four-chip cell runs this
# program on the chip; run the file by hand after a change to exec/mesh.py.
@pytest.mark.slow
def test_mesh_aggregate_compiles_at_sf100_shard_capacity(
        topo, tmp_path):
    """The same SPMD aggregate at the capacity the benchmark's four-chip
    cell runs it: 2^27 slots a shard, the planes of a cached relation
    (``store_sales_sf100.cached_report.mesh4``). 12.9 GB of planes are not
    staged here: the stage is handed shapes where the cell hands resident
    planes, the program is captured at its dispatch and re-targeted at four
    described chips. What the compiler says of memory is what one program
    needs beside its 3.2 GB of arguments a chip: it has to fit the chip,
    which the one-piece update (``exec/mesh.AGG_UPDATE_CHUNK_ROWS`` at or
    above the shard's slots) does not."""
    from spark_rapids_tpu.exec import mesh as XM
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell("store_sales_sf100.cached_report.mesh4")
    config, (query,) = bench["config"], bench["queries"]

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
    shapes, compiled = compile_mesh_program_for_four_chips(
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
    text = compiled.as_text()
    walks = [w for w in row_sized_scatters(text, 1 << 16)
             if "/agg_update/" in w[1] or "/agg_merge/" in w[1]]
    assert {n for n, _ in walks} == {XM.AGG_UPDATE_CHUNK_ROWS, 4 << 16}, walks
    assert [w for w in walks if "/cond/branch_" not in w[1]] == [], walks
    # a chunk of a shard with no live row skips its update (PR 33): 7 of
    # the cell's 16. The whole update of a chunk, the row-sized walks of
    # its hash tiers and the limb matmul over its 2^23 slots with them,
    # sits in a branch of the loop's conditional, by its name stack and in
    # the compiled program's own computations
    assert [w for w in walks if "/agg_update/" in w[1]
            and not IN_CHUNK_BRANCH.search(w[1])] == [], walks
    update, outside = chunk_updates(text)
    assert any(n.endswith("/agg_update/brl,brB->blB/dot_general")
               for _, n in update), update  # the direct tier's limb matmul
    assert outside == [], outside
    # the merge of the exchanged partials runs whatever the chunks held
    under = computations_under_a_conditional(text)
    assert [c for c, _ in instructions_named(
        text, r"/agg_merge/brl,brB->blB/dot_general") if c in under] == []
