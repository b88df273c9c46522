"""Ask the TPU's compiler, without a chip, for the one program across chips:
``TpuMeshAggregateExec``'s shard_map groupby over the one-chip cell's data,
for four DESCRIBED v5e chips. The rules are ``tpu_compile_asks``'s
docstring."""
from tpu_compile_asks import (  # noqa: F401  (fixtures)
    compile_mesh_program_for_four_chips, load_cell, no_persistent_cache,
    topo)


def test_mesh_aggregate_compiles_for_four_v5e_chips(
        topo, no_persistent_cache, tmp_path):
    """The ``shuffle.mode=ici`` program of the quantity report:
    ``TpuMeshAggregateExec``'s shard_map groupby with its all_to_all
    exchange, compiled for four DESCRIBED chips. Without
    ``parallel/mesh.mesh_jit_kwargs`` the compiler aborts the whole
    process here (conditional-code-motion, see that docstring). The
    program is captured from a run staged on the virtual CPU devices and
    re-targeted at the described mesh; size does not matter to the
    fault (65,536 rows abort like 28.8M do), so the data is the cell's
    rehearsal size."""
    from spark_rapids_tpu.sql import TpuSession

    bench = load_cell("store_sales.quantity_report")
    (query,) = bench["queries"]
    rows = bench["config"]["rehearse"]["rows"]
    bench["generator"].generate(
        bench["config"], 19, str(tmp_path), rows=rows, row_group=rows // 4)
    sess = TpuSession({
        **bench["config"]["conf"],
        "spark.rapids.tpu.shuffle.mode": "ici",
        "spark.rapids.tpu.sql.reader.batchSizeBytes": 1,
        "spark.rapids.tpu.mesh.devices": 4})
    _, compiled = compile_mesh_program_for_four_chips(
        topo, lambda: query.frame(sess, str(tmp_path)).collect())
    assert "all-to-all" in compiled.as_text()
