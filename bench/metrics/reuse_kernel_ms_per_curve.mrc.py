"""Device milliseconds of the Pallas reuse-distance kernel per miss-rate
curve: the kernel's op time in the trace over the curves of the window."""

KERNEL = "reuse_distance"   # the pallas_call's name


def read(ctx):
    if ctx.trace is None or not ctx.queries:
        return None
    seconds = ctx.trace.op_time_s(lambda op: op.split(".")[0] == KERNEL)
    if not seconds:
        return None
    return 1e3 * seconds / ctx.queries
