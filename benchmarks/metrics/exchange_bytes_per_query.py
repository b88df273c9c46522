"""Bytes a query hands to the collective exchange, over all shards: the
``exchange_bytes`` count on the ``TpuMeshAggregateExec.spmd`` spans (the
engine computes it from the exchanged block's shapes: shards x shards x
rows of the block x bytes of a partial row) over the slice's queries.
Nothing on one chip."""
import trace_mesh

NAME = "exchange_bytes_per_query"
UNIT = "bytes"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, trace_mesh.MESH_AGG + ".spmd", "exchange_bytes")
