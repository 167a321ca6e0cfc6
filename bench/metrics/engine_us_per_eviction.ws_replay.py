"""Microseconds of the chunk pipeline per eviction the learner decided: the
program's ``stream_engine`` span over its ``stream_evictions`` counter (the
evictions the replay's calls made, summed over shards). A program without
the counter reads nothing."""


def read(ctx):
    n = ctx.profile.get("stream_evictions", 0)
    if not n or "stream_engine" not in ctx.profile:
        return None
    return 1e6 * ctx.profile["stream_engine"] / n
