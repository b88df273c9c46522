"""Host milliseconds a query waits for its answer to cross to the host: the
``d2h`` spans (``ColumnarBatch._parallel_get``; each ends when the data is
on the host) of the traced slice over its queries."""
import trace_programs

NAME = "collect_d2h_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not reduced:
        return None
    spans = trace_programs.section_spans(reduced, "d2h")
    if not spans:
        return None
    return trace_programs.per_query(ctx, sum(s["total_s"] for s in spans))
