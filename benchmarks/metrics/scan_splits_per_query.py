"""Scan partitions a query plans: the ``splits`` count on the scan's
``plan`` spans (one a split the fused stage takes) and ``decode`` spans
(one a split decoded on its own), over the traced slice's queries. 1 where
the file's row groups pack into one split (a file of only the columns
read); several where ``reader.batchSizeBytes`` cuts a file of the source's
record width, and then a partial aggregate a split, an exchange and a final
merge follow. Nothing where the program's spans carry no such count."""
import trace_scan

NAME = "scan_splits_per_query"
UNIT = "count"


def read(ctx):
    reduced, queries = trace_scan.reduced_with_queries(ctx)
    spans = trace_scan.split_spans(reduced) if reduced else ()
    if not spans:
        return None
    return sum(s["counts"]["splits"] for s in spans) / queries
