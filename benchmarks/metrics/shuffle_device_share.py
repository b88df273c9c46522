"""Share of the slice's device busy time under the scope
``shuffle_exchange``: the one-host shuffle's programs (``jit_exchange``,
the partition ids, the sort by them and the gather of every plane by its
permutation; ``jit_exchange_slice``; ``jit_exchange_concat``). Device
seconds, so the layer's own cost, where ``shuffle_ms_per_query`` holds the
host's wait for the whole query. Nothing where the plan has no exchange or
no operation carries the scope."""
import trace_scan

NAME = "shuffle_device_share"
UNIT = "%"


def read(ctx):
    seconds = trace_scan.exchange_device_seconds(ctx)
    busy = (ctx.get("trace") or {}).get("busy_s")
    if seconds is None or not busy:
        return None
    return 100.0 * seconds / busy
