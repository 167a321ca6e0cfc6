"""Milliseconds per replay chunk of the chunk pipeline, from the first
chunk's submission to the carry back on the host: the device's chunk
engine with the later chunks' host prep overlapped (the program's
``stream_engine`` span over its ``stream_chunks`` counter)."""


def read(ctx):
    n = ctx.profile.get("stream_chunks", 0)
    if not n or "stream_engine" not in ctx.profile:
        return None
    return 1e3 * ctx.profile["stream_engine"] / n
