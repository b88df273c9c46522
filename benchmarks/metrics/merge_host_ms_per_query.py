"""Host milliseconds a query's aggregate spends merging its partials apart
from waiting for the device: the self time of the ``merge.*`` spans other
than ``merge.pull``. 0 where the merge runs inside the program (the fused
stage)."""
import trace_programs

NAME = "merge_host_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    if not any(name.startswith("TpuHashAggregateExec")
               for name in reduced["spans"]):
        return None  # the slice aggregated nothing
    parts = [rec for name, rec in reduced["spans"].items()
             if ".merge." in name and not name.endswith(".merge.pull")]
    return trace_programs.per_query(ctx, sum(p["self_s"] for p in parts))
