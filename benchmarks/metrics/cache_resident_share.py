"""Rows the traced slice's queries were served from a cached relation's
resident planes (``DataFrame.cache()``: the ``rows`` count on the
``TpuInMemoryTableScanExec.serve`` spans, which is 0 on a call that had to
fill) over the rows those queries scan. 100 where every query of the slice
reads the table from the devices; nothing where no query asks a cached
relation (or the program has none)."""
import trace_mesh

NAME = "cache_resident_share"
UNIT = "%"


def read(ctx):
    rec = trace_mesh.span(ctx, trace_mesh.CACHED_SCAN + ".serve")
    if not rec or "rows" not in rec["counts"]:
        return None
    scanned = sum(ctx["queries"][qi].rows_scanned(ctx["config"])
                  for qi in ctx["trace"]["query_indices"])
    return 100.0 * rec["counts"]["rows"] / scanned if scanned else None
