"""Row-sized scatters a query's SPMD aggregate runs on every row: the
``row_scatters`` count on the ``TpuMeshAggregateExec.spmd`` spans of the
traced slice over its queries (the engine counts, when the program is
traced, the row-sized ``segment_*`` operations of the first hash tier that
stand outside any ``lax.cond``: a scatter float sum, a min/max family, the
SCATTER lowering's two families). 0 where every aggregate enters the limb
matmul; a scatter that comes back shows here before it shows in the rate.
Nothing where no such span carries the count (one chip, or a program from
before the count)."""
import trace_mesh

NAME = "agg_row_scatters_per_query"
UNIT = "count"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, trace_mesh.MESH_AGG + ".spmd", "row_scatters")
