"""Host milliseconds a query's range exchange spends choosing its bounds:
the self time of the ``TpuShuffleExchangeExec.sample`` spans (a strided
gather of the key columns and a pull to the host an input batch, then the
host's sort of the sampled rows), over the traced slice's queries. The
span opens after the exchange's child has run, so the wait for the child's
programs is not in it; the pull waits for the gather alone. Nothing where
no plan of the slice sorts across partitions or the program has no such
span."""
import trace_mesh
import trace_programs
import trace_scan

NAME = "range_sample_ms_per_query"
UNIT = "ms"


def read(ctx):
    rec = trace_mesh.span(ctx, trace_scan.EXCHANGE + ".sample")
    return trace_programs.per_query(ctx, rec["self_s"]) if rec else None
