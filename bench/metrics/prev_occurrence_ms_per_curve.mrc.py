"""Host milliseconds per miss-rate curve of finding each request's previous
access in its shard row (the program's ``repro.mrc_prev_occurrence`` spans
in the trace)."""

import spans


def read(ctx):
    return spans.per_query_ms(ctx, "mrc_prev_occurrence")
