"""Host seconds per grid point of generating traffic alone, without the
failover remap, binning and partition that ``stream_gen`` also holds (the
program's ``repro.traffic_gen`` spans in the trace over the window's
points)."""

import spans

KEY = "traffic_gen"


def read(ctx):
    if ctx.trace is None or not ctx.work:
        return None
    s = spans.seconds(ctx.trace, KEY)
    return s / ctx.work if s else None
