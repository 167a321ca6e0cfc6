"""Long-trace replay: ``repro.sim.stream.stream_tier1_counters`` resumed
from its checkpoint, one slice of ``slice_chunks`` chunks per query.

The trace is made in set-up from the seed and passed as ``trace``. When it
runs out, the next query starts a fresh replay. The check compares the
counters of every replay the window drove, over the requests the entry asked
for, with the plain reference.
"""
from __future__ import annotations

import adapters
import reference
from traffic_gen import make_trace

# Exact counters: any differing cell fails.
LIMITS = {"counter_mismatches": 0, "weight_mismatches": 0}


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices):
        from repro.sim import stream as sim_stream
        self.run = sim_stream
        self.cfg = cfg
        self.spec = adapters.sim_spec(cfg)
        self.trace = make_trace(cfg["stream"], seed)
        self.total = len(self.trace[0])
        self.chunk = int(mix["chunk"])
        self.slice = int(mix["slice_chunks"]) * self.chunk
        self.profile: dict = {}
        self.passes: list = []      # (expected requests, counters) per replay
        # Warm-up: one chunk from a fresh state compiles the chunk engine
        # and every eager op a slice runs; the window starts afresh.
        self.run.stream_tier1_counters(self.spec, self.trace, chunk=self.chunk,
                                       max_requests=self.chunk)
        self.ck = None
        self.expected = 0

    def query(self, i: int) -> int:
        if self.ck is None or self.expected >= self.total:
            self.ck, self.expected = None, 0
            self.passes.append(None)
        want = min(self.slice, self.total - self.expected)
        ctr, _, self.ck = self.run.stream_tier1_counters(
            self.spec, self.trace, chunk=self.chunk, checkpoint=self.ck,
            max_requests=want, profile=self.profile)
        self.expected += want
        self.passes[-1] = (self.expected, ctr)
        return want

    def check(self) -> dict:
        bad = {"counters": 0, "weights": 0}
        refs: dict = {}     # every whole replay of the trace has one answer
        # A counter set the program returned more than once is one answer.
        answers = {(e, id(c)): (e, c) for e, c in self.passes}
        for expected, ctr in answers.values():
            if expected not in refs:
                refs[expected] = reference.counters(
                    *self.trace, prefix=expected,
                    **adapters.reference_args(self.cfg))
            got = reference.mismatches(ctr, refs[expected])
            bad["counters"] += got["counters"]
            bad["weights"] += got["weights"]
        return {"counter_mismatches": (bad["counters"],
                                       LIMITS["counter_mismatches"]),
                "weight_mismatches": (bad["weights"],
                                      LIMITS["weight_mismatches"])}
