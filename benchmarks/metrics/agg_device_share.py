"""Share of the slice's device busy time spent aggregating: operations
under ``agg_update`` and ``agg_merge`` (scope or program) and what the fused
program ``agg_plan`` runs outside every scope."""
import trace_programs

NAME = "agg_device_share"
UNIT = "%"


def read(ctx):
    return trace_programs.device_share(
        ctx, "agg_update", "agg_merge", "agg_plan")
