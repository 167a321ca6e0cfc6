"""Chunk-engine scan steps per real request: each chunk runs its length
bucket on every shard, pads included (the program's ``stream_scan_steps``
counter over its ``stream_requests`` counter). 1.0 would be a scan with no
padding."""


def read(ctx):
    n = ctx.profile.get("stream_requests", 0)
    if not n or "stream_scan_steps" not in ctx.profile:
        return None
    return ctx.profile["stream_scan_steps"] / n
