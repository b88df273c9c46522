"""Row groups the ``DeviceScanCache`` served over the row groups looked up
in the traced slice: the ``hits`` and ``lookups`` counts on the scan's
``cache_lookup`` spans."""
import trace_programs

NAME = "scan_cache_hit_share"
UNIT = "%"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not reduced:
        return None
    spans = trace_programs.section_spans(reduced, "cache_lookup")
    lookups = sum(s["counts"].get("lookups", 0) for s in spans)
    if not lookups:
        return None
    return 100.0 * sum(s["counts"].get("hits", 0) for s in spans) / lookups
