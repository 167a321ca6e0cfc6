"""Host milliseconds per miss-rate curve of binning the distances by cache
size and assembling every size's counters (the program's
``repro.mrc_histogram`` spans in the trace)."""

import spans


def read(ctx):
    return spans.per_query_ms(ctx, "mrc_histogram")
