"""Streams generated per grid point (the program's ``repro.traffic_gen``
spans in the trace over the window's points): 1.0 while every point of a
grid makes its own stream, 1/points once one stream serves a shared
traffic spec."""

import spans

KEY = "traffic_gen"


def read(ctx):
    if ctx.trace is None or not ctx.work:
        return None
    n = spans.count(ctx.trace, KEY)
    return n / ctx.work if n else None
