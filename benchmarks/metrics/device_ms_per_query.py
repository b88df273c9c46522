"""Device busy time of the traced slice over the queries it ran."""
NAME = "device_ms_per_query"
UNIT = "ms"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"] or not trace["queries"]:
        return None
    return 1000.0 * trace["busy_s"] / trace["queries"]
