"""Bytes a query's map side hands the shuffle transport: the ``bytes``
count on the ``TpuShuffleExchangeExec.map`` spans (the device bytes of
every piece written, over all map inputs and reduce partitions), over the
traced slice's queries. Nothing where the plan has no exchange or its span
carries no such count."""
import trace_mesh
import trace_scan

NAME = "shuffle_bytes_per_query"
UNIT = "bytes"


def read(ctx):
    return trace_mesh.count_per_query(
        ctx, trace_scan.EXCHANGE + ".map", "bytes")
