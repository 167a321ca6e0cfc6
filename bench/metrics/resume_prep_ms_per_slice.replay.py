"""Host milliseconds per resumed slice of preparing the whole trace before
its first chunk: the stream, the whole-trace window binning and the owner
map (the program's ``stream_resume_prep`` span over the window's
queries)."""

KEY = "stream_resume_prep"


def read(ctx):
    if not ctx.queries or KEY not in ctx.profile:
        return None
    return 1e3 * ctx.profile[KEY] / ctx.queries
