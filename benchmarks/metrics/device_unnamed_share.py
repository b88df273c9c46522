"""Share of the slice's device busy time in operations with neither an
engine program name nor an engine scope: what no issue can aim at. Nothing
where the program under test lacks the names: a share of 100 (or 47, as the
parent of PR 26 would read with its one named program) would be a reading
of the yardstick, not of the chip."""
import trace_programs

NAME = "device_unnamed_share"
UNIT = "%"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if not trace_programs.has_engine_names(reduced) or not busy:
        return None
    return 100.0 * reduced["unnamed_s"] / busy
