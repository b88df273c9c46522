"""Column chunks whose pages one native call walked, over the chunk plans
of the traced slice: the ``native`` and ``chunks`` counts on the scan's
``page_plan`` spans. A chunk's plan carries ``chunks`` 1 and ``native`` 1
where ``srtpu_pq_plan_chunk`` walked its pages, whether it planned the
chunk or refused it (a refused chunk, such as PLAIN DOUBLE, is then decoded
by pyarrow and counted by ``host_fallback_columns``), and ``native`` 0
where the Python page loop walked them (a codec other than SNAPPY or
UNCOMPRESSED, or a host without the native library); the consumer's own
``page_plan`` spans carry neither.
Nothing where no span carries the counts (a program from before them) or
the slice planned no chunk (the scan cache served every row group)."""
import trace_programs

NAME = "scan_native_plan_share"
UNIT = "%"


def read(ctx):
    reduced = trace_programs.for_ctx(ctx)
    if not trace_programs.has_engine_names(reduced):
        return None
    spans = trace_programs.section_spans(reduced, "page_plan")
    chunks = sum(s["counts"].get("chunks", 0) for s in spans)
    if not chunks:
        return None
    return 100.0 * sum(s["counts"].get("native", 0) for s in spans) / chunks
