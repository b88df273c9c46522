"""How unevenly the rows lie over the shards: the largest shard's rows over
the mean shard's, from the ``shard_rows_max``, ``shard_rows_sum`` and
``shards`` counts on the ``TpuMeshAggregateExec.stage`` spans. 1 is even;
every chip waits for the largest shard. Nothing on one chip."""
import trace_mesh

NAME = "mesh_shard_rows_skew"
UNIT = "ratio"


def read(ctx):
    rec = trace_mesh.span(ctx, trace_mesh.MESH_AGG + ".stage")
    if not rec:
        return None
    c = rec["counts"]
    if not c.get("shard_rows_sum") or not c.get("shards"):
        return None
    mean = c["shard_rows_sum"] / (c["shards"] / rec["count"])
    return c["shard_rows_max"] / mean
