"""Partials a query's aggregates merge: the ``partials`` count on the
``TpuHashAggregateExec.merge`` spans (the batches a merge took: a row
group's update each on the ``PARTIAL`` side of a scan partition, the
exchanged partials of a reduce partition on the ``FINAL`` side), summed
over the traced slice's queries. Each of them past the first is
materialized, measured and spliced on the host's path (``merge.*``), so the
merge's host time grows with this count. Nothing where the program's span
carries no such count."""
import trace_mesh

NAME = "merge_partials_per_query"
UNIT = "count"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, "TpuHashAggregateExec.merge", "partials")
