"""The exchange's share of the interconnect's roofline: the least time a
chip's share of the slice's exchanged bytes (``exchange_bytes`` on the
``spmd`` spans, over the chips traced) could take at the published
chip-to-chip bandwidth (``ici_peaks.json``), over the device seconds a chip
spent under ``mesh_exchange``. The scope holds the partition sort and the
compaction beside the collective, and the blocks are mostly padding, so a
small share is what 100 groups a shard give."""
import trace_mesh

NAME = "exchange_roofline"
UNIT = "%"


def read(ctx):
    seconds = trace_mesh.exchange_seconds(ctx)
    rec = trace_mesh.span(ctx, trace_mesh.MESH_AGG + ".spmd")
    chips = (ctx.get("trace") or {}).get("chips_traced")
    if (not seconds or not chips or not rec
            or "exchange_bytes" not in rec["counts"]):
        return None
    peak = trace_mesh.ici_peak(ctx)
    if not peak:
        return None
    least_s = rec["counts"]["exchange_bytes"] / chips / (
        peak["ici_GB/s"] * 1e9)
    return 100.0 * least_s / seconds
