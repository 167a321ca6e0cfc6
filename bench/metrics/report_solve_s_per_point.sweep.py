"""Seconds per grid point of the report stage: the batched queuing solve of
every point's windowed rates (the sweep's ``report_solve`` span)."""

KEY = "report_solve"


def read(ctx):
    if not ctx.work or KEY not in ctx.profile:
        return None
    return ctx.profile[KEY] / ctx.work
