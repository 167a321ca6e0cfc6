"""Milliseconds per miss-rate curve of the reuse-distance call as the host
sees it: operands up, the distance kernel, the distances back on the host
(the program's ``repro.mrc_reuse_distances`` spans in the trace)."""

import spans


def read(ctx):
    return spans.per_query_ms(ctx, "mrc_reuse_distances")
