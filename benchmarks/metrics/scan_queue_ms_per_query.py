"""Milliseconds a query's scan tasks wait in their pool before they start,
summed over the tasks: the ``queued_us`` count on the scan's spans, which a
pool task's outermost span carries (``page_plan``, a column chunk's plan on
the ``srtpu-pqdec`` pool; ``host_decode``, a split read ahead on
``srtpu-scan`` or a shard on ``srtpu-meshdec``; ``prefetch``, a split on
``srtpu-prefetch``). Task-milliseconds, so it can exceed the query: the
consumer's side of the same wait is the span ``plan_wait``. Nothing where
no scan span carries the count (a program from before it, or a slice whose
scan ran no pool task)."""
import trace_programs
import trace_scan

NAME = "scan_queue_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    waits = [rec["counts"]["queued_us"]
             for name, rec in reduced["spans"].items()
             if name.startswith(trace_scan.SCAN + ".")
             and "queued_us" in rec["counts"]]
    if not waits:
        return None
    return trace_programs.per_query(ctx, sum(waits) / 1e6)
