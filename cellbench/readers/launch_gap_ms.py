"""Median idle interval between the end of one step program and the
start of the next on chip 0, from the device trace."""


def read(ctx):
    return ctx.trace.get("launch_gap_ms")
