"""Share of the slice's device busy time under the scope ``mesh_exchange``
(the SPMD aggregate's key hash, partition sort, ``all_to_all`` and
compaction between its partial and its final half). Nothing where no
operation carries the scope: one chip, or a program from before it."""
import trace_mesh

NAME = "exchange_device_share"
UNIT = "%"


def read(ctx):
    seconds = trace_mesh.exchange_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * seconds / busy
