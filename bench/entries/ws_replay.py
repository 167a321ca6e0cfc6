"""Long-trace replay under the weight-sharing learner:
``repro.sim.stream.stream_tier1_counters`` resumed from its checkpoint, one
slice of ``slice_chunks`` chunks per query, with the store's policy and
learner knobs as the configuration states them.

The trace is made in set-up from the seed and passed as ``trace``. Set-up
replays its first chunk from a cold cache, which compiles the chunk engine
and fills tier 1, and every replay of the window resumes from that chunk's
checkpoint, so each query's chunk evicts; when the trace runs out, the next
query resumes from that checkpoint again. The check replays the trace
once through the plain reference of Algorithms 1-2 (``reference_ws``), over
as many requests as the window's longest replay consumed, and compares the
counters of every replay the window drove exactly and their window weights
within the reference's tolerance.
"""
from __future__ import annotations

import dataclasses
import sys

import adapters
import reference_ws
from traffic_gen import make_trace

# Exact counters; weights differing by more than the tolerance, none.
LIMITS = {"counter_mismatches": 0, "weight_mismatches": 0}


def learner(store: dict) -> reference_ws.Learner:
    """The learner knobs a configuration's ``store`` block states."""
    return reference_ws.Learner(
        epoch_width=int(store["epoch_width"]), alpha=float(store["alpha"]),
        beta=float(store["beta"]), threshold=float(store["threshold"]))


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices):
        from repro.sim import stream as sim_stream
        self.run = sim_stream
        self.cfg = cfg
        spec = adapters.sim_spec(cfg)
        self.spec = dataclasses.replace(spec, store=dataclasses.replace(
            spec.store, **learner(cfg["store"])._asdict()))
        self.trace = make_trace(cfg["stream"], seed)
        self.total = len(self.trace[0])
        self.chunk = int(mix["chunk"])
        self.slice = int(mix["slice_chunks"]) * self.chunk
        self.profile: dict = {}
        self.passes: list = []      # (expected requests, counters) per replay
        # Warm-up: the first chunk from a cold cache compiles the chunk
        # engine and every eager op a slice runs; its checkpoint is where
        # each replay of the window starts.
        _, _, self.warm = self.run.stream_tier1_counters(
            self.spec, self.trace, chunk=self.chunk, max_requests=self.chunk)
        self.ck = None
        self.expected = 0

    def query(self, i: int) -> int:
        if self.ck is None or self.expected >= self.total:
            self.ck, self.expected = self.warm, self.warm.offset
            self.passes.append(None)
        want = min(self.slice, self.total - self.expected)
        ctr, _, self.ck = self.run.stream_tier1_counters(
            self.spec, self.trace, chunk=self.chunk, checkpoint=self.ck,
            max_requests=want, profile=self.profile)
        self.expected += want
        self.passes[-1] = (self.expected, ctr)
        return want

    def check(self) -> dict:
        st, store = self.cfg["stream"], self.cfg["store"]
        n_windows = int(self.cfg["windows"]["n_windows"])
        # A counter set the program returned more than once is one answer.
        answers = {(e, id(c)): (e, c) for e, c in self.passes}
        upto = max(e for e, _ in answers.values())
        ref = reference_ws.Replay(
            *self.trace, n_shards=int(store["n_shards"]),
            mapping=store["mapping"], n_lines=int(store["n_lines"]),
            n_windows=n_windows,
            window_dt=int(st["n_requests"]) / float(st["rate"]) / n_windows,
            policy=store["policy"], learner=learner(store), upto=upto)
        bad = {"counters": 0, "weights": 0}
        err = 0.0
        for expected, ctr in answers.values():
            got = reference_ws.mismatches(ctr, ref.counters(expected),
                                          reference_ws.WEIGHT_TOL)
            bad["counters"] += got["counters"]
            bad["weights"] += got["weights"]
            err = max(err, got["weight_err"])
        print(f"reference: {upto} requests, largest weight difference "
              f"{err!r} (tolerance {reference_ws.WEIGHT_TOL!r})",
              file=sys.stderr, flush=True)
        return {"counter_mismatches": (bad["counters"],
                                       LIMITS["counter_mismatches"]),
                "weight_mismatches": (bad["weights"],
                                      LIMITS["weight_mismatches"])}
