"""Host seconds of stream generation per grid point: the sweep's traffic
generation, failover remap, window binning and partition (its ``stream_gen``
span) over the points of the window."""

KEY = "stream_gen"


def read(ctx):
    if not ctx.work or KEY not in ctx.profile:
        return None
    return ctx.profile[KEY] / ctx.work
