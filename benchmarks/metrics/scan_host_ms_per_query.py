"""Host milliseconds a query's scan spends on the file itself: the self
time of the spans ``read_file`` (open, footer, map), ``page_plan`` (header
parse, dictionary load, RLE/bit-pack planning; on the decode pool's threads
too, so these are CPU milliseconds, not wall) and ``host_decode`` (pyarrow,
for the columns the device decoder declines). 0 where the scan cache served
the slice; nothing where the program has no such spans."""
import trace_programs

NAME = "scan_host_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not (trace_programs.has_engine_names(reduced)
            and trace_programs.scanned_a_file(reduced)):
        return None
    parts = trace_programs.section_spans(
        reduced, "read_file", "page_plan", "host_decode")
    return trace_programs.per_query(ctx, sum(p["self_s"] for p in parts))
