"""Bytes the buffer catalog spilled during the window
(``BufferCatalog.get().metrics.spilled_bytes`` delta)."""
NAME = "spilled_bytes"
UNIT = "bytes"


def read(ctx):
    return ctx["counters"].get("spilled_bytes")
