"""Column chunks a query decoded on the host because the device decoder
declined them: the ``columns`` count on the ``host_decode`` spans of the
traced slice over its queries (one a row group and column). 0 where every
column took the device path."""
import trace_programs

NAME = "host_fallback_columns"
UNIT = "count"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not (trace_programs.has_engine_names(reduced)
            and trace_programs.scanned_a_file(reduced)):
        return None
    queries = ctx["trace"]["queries"]
    if not queries:
        return None
    spans = trace_programs.section_spans(reduced, "host_decode")
    return sum(s["counts"].get("columns", 0) for s in spans) / queries
