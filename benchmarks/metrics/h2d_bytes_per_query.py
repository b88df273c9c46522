"""Bytes a query sends to the device: the ``bytes`` count on the ``upload``
spans (staging and ``device_put`` in ``io/arrow_convert.packed_upload``) of
the traced slice over its queries. 0 where the scan cache served the
slice."""
import trace_programs

NAME = "h2d_bytes_per_query"
UNIT = "bytes"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    queries = ctx["trace"]["queries"]
    if not queries:
        return None
    spans = trace_programs.section_spans(reduced, "upload")
    return sum(s["counts"].get("bytes", 0) for s in spans) / queries
