"""Seconds per grid point the sweep waits for the batched tier-1 engine and
the transfer of its counters back (its ``engine_dispatch_wait`` span)."""

KEY = "engine_dispatch_wait"


def read(ctx):
    if not ctx.work or KEY not in ctx.profile:
        return None
    return ctx.profile[KEY] / ctx.work
