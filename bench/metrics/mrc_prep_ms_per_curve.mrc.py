"""Host milliseconds per miss-rate curve of preparing the distance pass:
the stream, owner map, window binning, partition into shard rows and
padding (the program's ``repro.mrc_prep`` spans in the trace)."""

import spans


def read(ctx):
    return spans.per_query_ms(ctx, "mrc_prep")
