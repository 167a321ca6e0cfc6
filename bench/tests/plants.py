"""Faults planted in the program's timed path, for the test that a broken
path reads ``correct: false``. Each patches the entry point a cell's window
drives (``repro.sim.stream.stream_tier1_counters`` for the replay,
``repro.sim.mrc_tier1_counters`` for the curve, ``repro.sim.sweep`` for
the knob sweep):

- ``unchanged`` — after the warm-up call, every call returns the warm-up's
  state and counters unchanged: the replay does not advance, the curve
  repeats its first answer, the sweep its set-up grid's;
- ``half`` — half of the batch is left out: the replay consumes half the
  requests it was asked for, the curve is built from half the trace, the
  sweep answers the first half of its grid;
- ``altered`` — answers altered where they are produced: one hit more in
  each shard's first window, in every counter set or report returned (the
  curve's check compares one answer at each size, so each answer carries
  the fault).

A cell on one chip has no exchange between chips to leave out.
"""
from __future__ import annotations

import dataclasses

import numpy as np

FAULTS = ("unchanged", "half", "altered")


def _alter(ctr):
    hits = np.array(ctr.win_hits, copy=True)
    hits[:, 0] += 1
    return ctr._replace(win_hits=hits)


def _alter_report(rep):
    hits = np.array(rep.windows.hits, copy=True)
    hits[:, 0] += 1
    return dataclasses.replace(rep, windows=rep.windows._replace(hits=hits))


def plant(name: str) -> None:
    import repro.sim
    import repro.sim.stream as stream
    replay, curve = stream.stream_tier1_counters, repro.sim.mrc_tier1_counters
    sweep, expand = repro.sim.sweep, repro.sim.expand_grid
    seen: dict = {}

    def bad_replay(spec, trace=None, **kw):
        if name == "half" and kw.get("max_requests"):
            kw["max_requests"] = max(1, kw["max_requests"] // 2)
        if name == "unchanged" and "out" in seen:
            return seen["out"]
        out = replay(spec, trace, **kw)
        if name == "altered":
            out = (_alter(out[0]),) + out[1:]
        seen.setdefault("out", out)
        return out

    def bad_curve(spec, sizes, trace=None):
        if name == "unchanged" and "out" in seen:
            return seen["out"]
        if name == "half":
            n = len(trace[0]) // 2
            trace = tuple(np.asarray(a)[:n] for a in trace)
        out = curve(spec, sizes, trace)
        if name == "altered":
            out = {k: _alter(v) for k, v in out.items()}
        seen.setdefault("out", out)
        return out

    def bad_sweep(base, axes, **kw):
        if name == "unchanged" and "out" in seen:
            return seen["out"]
        if name == "half":
            points = expand(axes)
            axes = points[:len(points) // 2]
        out = sweep(base, axes, **kw)
        if name == "altered":
            out = dataclasses.replace(out, reports=tuple(
                _alter_report(r) for r in out.reports))
        seen.setdefault("out", out)
        return out

    stream.stream_tier1_counters = bad_replay
    repro.sim.mrc_tier1_counters = bad_curve
    repro.sim.sweep = bad_sweep
