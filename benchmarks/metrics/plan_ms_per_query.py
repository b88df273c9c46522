"""Host milliseconds a query spends planning: the ``TpuSession.plan`` span
(``_lower``, the static analysis and ``overrides.apply``) of the traced
slice over its queries."""
import trace_programs

NAME = "plan_ms_per_query"
UNIT = "ms"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not reduced or "TpuSession.plan" not in reduced["spans"]:
        return None
    return trace_programs.per_query(
        ctx, reduced["spans"]["TpuSession.plan"]["total_s"])
