"""Host milliseconds per replay chunk: traffic binning and the partition
into shard rows, before the chunk is handed to the device (the program's
``stream_chunk_host`` span over its ``stream_chunks`` counter)."""


def read(ctx):
    n = ctx.profile.get("stream_chunks", 0)
    if not n:
        return None
    return 1e3 * ctx.profile["stream_chunk_host"] / n
