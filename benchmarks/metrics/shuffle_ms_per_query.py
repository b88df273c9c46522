"""Host milliseconds a query spends in the one-host shuffle itself: the
self time of the ``TpuShuffleExchangeExec`` spans (``.map``: partition
program dispatch, the pull of the offsets, the slicing of the pieces, less
the child plan that runs nested in it; ``.reduce``: the concat of a reduce
partition's pieces), over the traced slice's queries. The pull of the
offsets is where the host waits for every program dispatched so far, so on
a device-bound query this is the query's wall time, not host work alone.
Nothing where the plan has no exchange."""
import trace_programs
import trace_scan

NAME = "shuffle_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced, _ = trace_scan.reduced_with_queries(ctx)
    spans = trace_scan.exchange_spans(reduced) if reduced else ()
    if not spans:
        return None
    return trace_programs.per_query(ctx, sum(s["self_s"] for s in spans))
