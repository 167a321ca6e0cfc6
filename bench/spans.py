"""The program's own stage spans, read from a traced window.

The simulator brackets each host stage with a profiler annotation
``repro.<key>`` (``repro.sim.spans.span``), which the trace records as a
host event on the same clock as the device's ops. A metric that reads a
stage from the trace takes the events named ``repro.<key>`` on every host
plane, clipped to the traced window ``[summary.t0, summary.t1]``:

- ``seconds``: the length of their union, so a span cut by either edge of
  the window counts only its part inside;
- ``count``: how many of them reach into the window, a span cut by an edge
  counting once.

A program without the span (an older tree) gives 0 for both, and the
metric then reports nothing.
"""
from __future__ import annotations

from trace_reduce import _merge

PREFIX = "repro."


def intervals(summary, key: str) -> list:
    """``(start_ns, end_ns)`` of every ``repro.<key>`` host event, clipped
    to the window; events wholly outside it are left out."""
    name = PREFIX + key
    out = []
    for p in summary.host:
        for evs in p.lines.values():
            for n, s, d in evs:
                if n == name:
                    s0, e0 = max(s, summary.t0), min(s + d, summary.t1)
                    if e0 > s0:
                        out.append((s0, e0))
    return out


def seconds(summary, key: str) -> float:
    """Seconds of the window spent inside ``repro.<key>`` spans."""
    return sum(e - s for s, e in _merge(intervals(summary, key))) / 1e9


def count(summary, key: str) -> int:
    """``repro.<key>`` spans that reach into the window."""
    return len(intervals(summary, key))


def per_query_ms(ctx, key: str):
    """Milliseconds in ``repro.<key>`` spans per query of the window, or
    None where the run was not traced or the span is absent."""
    if ctx.trace is None or not ctx.queries:
        return None
    s = seconds(ctx.trace, key)
    return 1e3 * s / ctx.queries if s else None
