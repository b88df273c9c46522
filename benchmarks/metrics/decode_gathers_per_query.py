"""Per-element gathers left in the decode programs a query runs: the
``gathers`` count on the spans under which a query's chunk decodes are
spliced into the fused stage (``TpuHashAggregateExec.stage``) or dispatched
one a column (``TpuFileSourceScanExec.decode_dispatch``), over the traced
slice's queries. The engine counts them from each chunk's program key: one
for a code read that is a true expand (a chunk with nulls), one for a
dictionary look-up that is neither a one-hot nor a two-level matmul. A row
group whose DECODED batch the scan cache serves runs no decode program and
adds none: a slice that decoded nothing reads 0."""
import trace_programs

NAME = "decode_gathers_per_query"
UNIT = "count"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not (trace_programs.has_engine_names(reduced)
            and trace_programs.scanned_a_file(reduced)):
        return None
    queries = ctx["trace"]["queries"]
    spans = trace_programs.section_spans(reduced, "stage", "decode_dispatch")
    counted = [s["counts"]["gathers"] for s in spans
               if "gathers" in s["counts"]]
    if not queries or (spans and not counted):
        return None  # a program from before the count: nothing to read
    return sum(counted) / queries
