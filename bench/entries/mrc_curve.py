"""Whole miss-rate curve: ``repro.sim.mrc_tier1_counters`` at every size of
the mix, one curve per query.

The trace is made in set-up from the mix's fixed ``stream_seed``, the same
in every run; query ``i`` rotates it by an offset drawn from the run's seed
and ``i`` (the same requests and arrival times, the pages in another
order), so no query repeats another and every run does the same work. The
check compares one answer at every size of the curve, a (query, shard)
drawn from the seed for each, with the plain reference.
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

import adapters
import reference

LIMITS = {"counter_mismatches": 0, "weight_mismatches": 0}


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices):
        from repro import sim
        self.run = sim
        self.cfg = cfg
        self.seed = seed
        self.spec = adapters.sim_spec(cfg)
        self.trace = adapters.curve_trace(cfg, mix)
        self.total = len(self.trace[0])
        self.sizes = adapters.curve_sizes(mix)
        self.profile: dict = {}
        self.outputs: list = []     # (offset, {size: counters}) per query
        self.run.mrc_tier1_counters(self.spec, self.sizes, self.trace)

    def rotated(self, offset: int):
        pages, is_write, times = self.trace
        return np.roll(pages, -offset), np.roll(is_write, -offset), times

    def query(self, i: int) -> int:
        offset = adapters.curve_offset(self.seed, i, self.total)
        out = self.run.mrc_tier1_counters(self.spec, self.sizes,
                                          self.rotated(offset))
        self.outputs.append((offset, out))
        return self.total

    def check(self) -> dict:
        args = adapters.reference_args(self.cfg)
        del args["n_lines"]
        by_query = defaultdict(list)
        for q, size, shard in adapters.curve_checks(
                self.seed, len(self.outputs), self.sizes, args["n_shards"]):
            by_query[q].append((shard, size))
        bad = {"counters": 0, "weights": 0}
        for q, pairs in sorted(by_query.items()):
            offset, out = self.outputs[q]
            refs = reference.pair_counters(*self.rotated(offset), pairs,
                                           **args)
            for shard, size in pairs:
                got = reference.mismatches(out[size],
                                           {shard: refs[(shard, size)]})
                bad["counters"] += got["counters"]
                bad["weights"] += got["weights"]
        return {"counter_mismatches": (bad["counters"],
                                       LIMITS["counter_mismatches"]),
                "weight_mismatches": (bad["weights"],
                                      LIMITS["weight_mismatches"])}
