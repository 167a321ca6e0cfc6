"""Stage spans: how the simulator times itself.

``span(name, profile)`` brackets one stage of the host pipeline. It opens a
profiler annotation ``repro.<name>``, so a traced run shows the stage on
the same clock as the device's ops, and, where ``profile`` is a dict, adds
the stage's wall-clock seconds to ``profile[name]``. ``count(name,
profile, n)`` adds ``n`` to an integer counter in the same dict. Neither
touches the device: a span ends when the host leaves it, so a stage that
only submits device work measures the submission, and the wait lands in
whichever span first reads a result back.

Spans belong around stages, never inside a per-request loop or a jitted
function (where the body runs once, at trace time).
"""
from __future__ import annotations

import contextlib
from time import perf_counter
from typing import Optional

import jax

__all__ = ["PREFIX", "span", "count"]

PREFIX = "repro."


@contextlib.contextmanager
def span(name: str, profile: Optional[dict] = None):
    """Time the enclosed stage as ``repro.<name>`` in the profiler trace
    and, with a ``profile`` dict, add its seconds to ``profile[name]``."""
    with jax.profiler.TraceAnnotation(PREFIX + name):
        t0 = perf_counter()
        try:
            yield
        finally:
            if profile is not None:
                profile[name] = profile.get(name, 0.0) + (perf_counter() - t0)


def count(name: str, profile: Optional[dict] = None, n: int = 1) -> None:
    """Add ``n`` to the integer counter ``profile[name]`` (no-op without a
    dict)."""
    if profile is not None:
        profile[name] = profile.get(name, 0) + int(n)
