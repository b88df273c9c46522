"""Slots a query's synced merges took: the ``slots`` count on the
``TpuHashAggregateExec.merge`` spans (each partial cut to the bucket of the
rows it holds, or whole where its rows fill it, summed over the partials
the merge's one program took), summed over the traced slice's queries.
Nothing where the program's span carries no such count."""
import trace_mesh

NAME = "merge_slots_per_query"
UNIT = "count"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, "TpuHashAggregateExec.merge", "slots")
