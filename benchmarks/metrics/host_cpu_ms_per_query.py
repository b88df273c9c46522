"""CPU milliseconds the whole process spends a query, user and system, on
all its threads: the ``process_cpu_us`` count on the ``TpuSession.query``
spans of the traced slice (the process's rusage over the span), over its
queries. Beside ``device_idle_share`` it says whether the host that keeps
the chip waiting computes (near the query's wall times the threads that
work) or waits itself (well under the wall). Nothing where the query span
carries no such count (a program from before it)."""
import trace_programs

NAME = "host_cpu_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    counts = reduced["spans"][trace_programs.QUERY_SPAN]["counts"]
    if "process_cpu_us" not in counts:
        return None
    return trace_programs.per_query(ctx, counts["process_cpu_us"] / 1e6)
