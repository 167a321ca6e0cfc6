"""JAX's compile events on the host clock.

Compile time is the union of the trace, lowering and backend-compile spans
JAX reports (traces nest, so their sum would count twice); the events that
fall inside a measured window are counted so that a run can show nothing
compiled there.
"""
from __future__ import annotations

import time

import jax

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileLog:
    """Collects ``(event, start, end)`` of every compile event after
    :meth:`listen`."""

    def __init__(self):
        self.spans: list = []

    def listen(self) -> "CompileLog":
        def on_event(event, duration, **_):
            if event in COMPILE_EVENTS:
                end = time.perf_counter()
                self.spans.append((event, end - duration, end))
        jax.monitoring.register_event_duration_secs_listener(on_event)
        return self

    def between(self, t0: float, t1: float) -> dict:
        """Events that ended in ``[t0, t1]``, counted by kind, and the
        union of their spans in seconds."""
        inside = [s for s in self.spans if t0 <= s[2] <= t1]
        counts = {e.rsplit("/", 1)[-1].removesuffix("_duration"): 0
                  for e in COMPILE_EVENTS}
        for e, _, _ in inside:
            counts[e.rsplit("/", 1)[-1].removesuffix("_duration")] += 1
        union, reach = 0.0, t0
        for _, s, e in sorted(inside, key=lambda x: x[1]):
            s = max(s, reach)
            if e > s:
                union += e - s
                reach = e
        return {"events": counts, "seconds": union}
