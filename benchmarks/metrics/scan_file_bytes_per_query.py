"""File bytes a query touches: the ``file_bytes`` count on the scan's
``read_file`` spans (the footer, or a whole file read at once) and
``page_plan`` spans (the compressed bytes of each column chunk planned),
over the traced slice's queries. Beside the query's ``needed_bytes`` it is
the read amplification; beside the file's size it says what column pruning
and the scan cache spare: 0 where the cache served every row group.
Nothing where the program counts no splits (it predates both counts)."""
import trace_programs
import trace_scan

NAME = "scan_file_bytes_per_query"
UNIT = "bytes"


def read(ctx):
    reduced, queries = trace_scan.reduced_with_queries(ctx)
    if not reduced or not trace_scan.split_spans(reduced):
        return None
    spans = trace_programs.section_spans(reduced, "read_file", "page_plan")
    return sum(s["counts"].get("file_bytes", 0) for s in spans) / queries
