"""The SPMD aggregate's share of the chips' HBM roofline: the least time
the chips traced could take to read the bytes the slice's queries need
(each query's ``needed_bytes``, over chips x the published HBM bytes/s)
over the device seconds a chip spent in the program ``mesh_agg``. The same
work whatever implements it, so a program that reads padded slots, or reads
them more than once, shows here. Nothing where no ``mesh_agg`` program
ran."""
import trace_mesh
import trace_programs

NAME = "mesh_agg_roofline"
UNIT = "%"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    trace = ctx.get("trace") or {}
    if not trace_programs.has_engine_names(reduced):
        return None
    seconds = reduced["by_program"].get("mesh_agg")
    chips = trace.get("chips_traced")
    peak = trace_mesh.ici_peak(ctx)
    if not seconds or not chips or not peak:
        return None
    needed = sum(ctx["queries"][qi].needed_bytes(ctx["config"])
                 for qi in trace["query_indices"])
    least_s = needed / (chips * peak["hbm_GB/s"] * 1e9)
    return 100.0 * least_s / seconds
