"""Host clock around the first ``collect()`` of the process: where the
compile cache is read or filled, and the scan first decodes and uploads."""
NAME = "first_query_s"
UNIT = "s"


def read(ctx):
    return ctx["counters"].get("first_query_s")
