"""Compile misses inside the window (``exec/base.COMPILE_COUNTER`` delta):
a warm window should count none."""
NAME = "window_compiles"
UNIT = "count"


def read(ctx):
    return ctx["counters"].get("window_compiles")
