"""Host seconds per grid point of handing the stacked streams to the device
and submitting the batched engine (the sweep's ``engine_dispatch_submit``
span)."""

KEY = "engine_dispatch_submit"


def read(ctx):
    if not ctx.work or KEY not in ctx.profile:
        return None
    return ctx.profile[KEY] / ctx.work
