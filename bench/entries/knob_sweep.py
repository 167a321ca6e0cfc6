"""Knob sweep: ``repro.sim.sweep`` over the mix's grid, one whole grid per
query, each query over a fresh stream.

The program makes each query's stream itself from the spec (the point of
this cell: the sweep's host stream generation, its engine dispatch and its
batched reports); query ``i``'s traffic seed is drawn from the run's seed
and ``i``. Set-up runs one whole grid, which compiles the batched engine
and the report solve. The check replays each query's stream (at most
``CHECK_QUERIES`` of them, drawn from the seed), made again from its
traffic seed by the benchmark's copy of the generator, through the plain
reference of each policy on the grid, and compares every point's
per-shard and per-window counters and window weights.
"""
from __future__ import annotations

import numpy as np

import adapters
import reference
from traffic_gen import poisson_decay_trace, rng_for

LIMITS = {"counter_mismatches": 0, "weight_mismatches": 0}
# A window holds two or three grids; where it holds more, the check takes
# this many of them, drawn from the seed.
CHECK_QUERIES = 8
PROFILE_KEYS = ("stream_gen", "engine_dispatch_submit",
                "engine_dispatch_wait", "report_solve", "assembly")


class Entry:
    def __init__(self, cfg: dict, mix: dict, seed: int, devices):
        import repro.sim
        self.sim = repro.sim
        self.cfg = cfg
        self.seed = seed
        self.axes = {k: tuple(v) for k, v in mix["axes"].items()}
        self.devices = tuple(devices)
        self.profile: dict = {}
        self.outputs: list = []     # (traffic seed, SweepResult) per query
        self.grid(-1)

    def grid(self, query: int):
        tseed = adapters.sweep_traffic_seed(self.seed, query)
        res = self.sim.sweep(adapters.sim_spec(self.cfg, tseed), self.axes,
                             profile=True, devices=self.devices)
        return tseed, res

    def query(self, i: int) -> int:
        tseed, res = self.grid(i)
        for k in PROFILE_KEYS:
            self.profile[k] = self.profile.get(k, 0.0) + res.profile[k]
        self.outputs.append((tseed, res))
        return len(res.points)

    def check(self) -> dict:
        bad = {"counters": 0, "weights": 0}
        n_grid = int(np.prod([len(v) for v in self.axes.values()]))
        picked = range(len(self.outputs))
        if len(self.outputs) > CHECK_QUERIES:
            picked = sorted(rng_for(self.seed, 6).choice(
                len(self.outputs), CHECK_QUERIES, replace=False))
        for q in picked:
            tseed, res = self.outputs[q]
            trace = poisson_decay_trace(self.cfg["stream"], tseed)
            refs: dict = {}
            if len(res.points) != n_grid:
                bad["counters"] += n_grid
            for point, rep in zip(res.points, res.reports):
                policy = point.get("store.policy", self.cfg["store"]["policy"])
                if policy not in refs:
                    refs[policy] = reference.fault_counters(
                        *trace, **adapters.fault_reference_args(self.cfg,
                                                                policy))
                got = reference.report_mismatches(rep, refs[policy])
                bad["counters"] += got["counters"]
                bad["weights"] += got["weights"]
        return {"counter_mismatches": (bad["counters"],
                                       LIMITS["counter_mismatches"]),
                "weight_mismatches": (bad["weights"],
                                      LIMITS["weight_mismatches"])}
