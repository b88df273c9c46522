"""The share of the traced slice in which no operation ran on the device:
1 - (union of the device-op intervals) / (the slice's length)."""
NAME = "device_idle_share"
UNIT = "%"


def read(ctx):
    trace = ctx.get("trace")
    if not trace or not trace["busy_s"] or not trace["window_s"]:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
