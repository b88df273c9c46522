"""Share of the slice's device busy time spent decoding: operations under
the scope or the program ``pq_decode`` (parquet page expansion) and
``upload_unpack`` (the staged upload's split)."""
import trace_programs

NAME = "decode_device_share"
UNIT = "%"


def read(ctx):
    return trace_programs.device_share(ctx, "pq_decode", "upload_unpack")
