"""Bytes a query's mesh stage sends to the devices to stage its input: the
``h2d_bytes`` count on the ``TpuMeshAggregateExec.stage`` spans of the
traced slice over its queries. 0 where a cached relation hands over resident
planes; the staged planes' bytes where the scan stages in every query.
Nothing on one chip."""
import trace_mesh

NAME = "mesh_h2d_bytes_per_query"
UNIT = "bytes"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, trace_mesh.MESH_AGG + ".stage", "h2d_bytes")
