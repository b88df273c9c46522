"""Thread-CPU milliseconds a query's scan spends on the file itself: the
``cpu_us`` count of the spans whose self time ``scan_host_ms_per_query``
sums (``read_file``, ``page_plan``, ``host_decode``), on every thread that
opens them, the decode pool's and the consumer's. A span's ``cpu_us`` is
its thread's CPU inside it less that of the spans nested in it on that
thread, as its self time is its wall less theirs; so this metric is at
most that one, and the gap between the two is the time the scan's threads
waited inside those spans (for the GIL, a page fault, a lock) rather than
computed. Nothing where no such span carries the count (a program from
before it) or the slice read no file."""
import trace_programs

NAME = "scan_host_cpu_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    counted = [rec["counts"]["cpu_us"] for rec in trace_programs.section_spans(
        reduced, "read_file", "page_plan", "host_decode")
        if "cpu_us" in rec["counts"]]
    if not counted:
        return None
    return trace_programs.per_query(ctx, sum(counted) / 1e6)
