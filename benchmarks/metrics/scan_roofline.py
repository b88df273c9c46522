"""The traced slice's share of the HBM roofline: the least time the chip
could take to read the bytes the slice's queries need (each query's
``needed_bytes`` from the schema's widths and the rows scanned, over the
published peak HBM bytes/s of the chips traced) over the time a chip was
busy (``busy_s`` is averaged over the chips, so four chips' bytes stand
against four chips' peak: the whole query's share, where
``mesh_agg_roofline`` is the one program's). Memory-bound by construction:
the queries here move bytes, their arithmetic is nothing beside the chip's
FLOP/s. A reader that finds no device time returns nothing."""
NAME = "scan_roofline"
UNIT = "%"


def read(ctx):
    trace = ctx.get("trace")
    peaks = ctx.get("peaks")
    if not trace or not peaks or not trace["busy_s"]:
        return None
    needed = sum(ctx["queries"][qi].needed_bytes(ctx["config"])
                 for qi in trace["query_indices"])
    if not needed:
        return None
    least_s = needed / (trace["chips_traced"] * peaks["hbm_GB/s"] * 1e9)
    return 100.0 * least_s / trace["busy_s"]
