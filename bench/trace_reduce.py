"""From a profiler trace to the benchmark's device numbers.

A trace is read into plain :class:`Plane` records (name, lines of
``(name, start_ns, dur_ns)`` events), so the reduction can be checked on a
recorded trace or on hand-made planes alike. From them:

- **busy and idle.** Busy time is the union of the intervals in which an
  operation ran on a device (its op line), clipped to the traced window and
  averaged over the chips. The window is the host span ``WINDOW_SPAN`` that
  the harness opens around the measured queries where the trace holds it.
  A trace of a sample holds instead the mark ``SAMPLE_START`` the harness
  sets as the profiler starts, and the window runs from it for the
  sample's length on the host clock (the profiler's own stop, which
  collects the trace, lies outside). Else it is the profiler's extent.
- **kernel time.** The summed device time of the ops whose name matches a
  metric's stable name.
- **breakdown.** The device ops that took most time, and the longest idle
  gaps, each named by the innermost host event running at its midpoint.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import gzip
import os
from typing import Callable

WINDOW_SPAN = "bench.window"
SAMPLE_START = "bench.sample_start"
DEVICE_PREFIX = "/device:TPU:"
OP_LINES = ("XLA Ops",)
TOP = 10


@dataclasses.dataclass
class Plane:
    name: str
    lines: dict           # line name -> list of (name, start_ns, dur_ns)

    @property
    def is_device(self) -> bool:
        return (self.name.startswith(DEVICE_PREFIX)
                and self.name[len(DEVICE_PREFIX):].isdigit())


def newest_trace(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def load(path: str):
    """``(planes, extent)``: the trace's planes, and the profiler's
    ``(start, stop)`` on the events' clock (events count from its start).
    A ``.gz`` file is read through gzip."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes, extent = [], None
    for p in data.planes:
        stats = dict(p.stats)
        if "profile_start_time" in stats:
            extent = (0.0, float(stats["profile_stop_time"]
                                 - stats["profile_start_time"]))
        device = Plane(p.name, {}).is_device
        planes.append(Plane(p.name, {
            ln.name: [(e.name, float(e.start_ns), float(e.duration_ns))
                      for e in ln.events] for ln in p.lines
            if not device or ln.name in OP_LINES}))
    return planes, extent


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _op(name: str) -> str:
    """An op event's HLO instruction name: ``%fusion.3 = f32[8] fusion(..)``
    -> ``fusion.3``."""
    return name.split(" = ", 1)[0].lstrip("%")


class Summary:
    """The reduction of one traced window."""

    def __init__(self, planes: list[Plane], extent=None, sample_s=None):
        self.devices = [p for p in planes if p.is_device]
        self.host = [p for p in planes if not p.is_device]
        marks: dict = {}
        for p in self.host:
            for evs in p.lines.values():
                for n, s, d in evs:
                    if n in (WINDOW_SPAN, SAMPLE_START):
                        marks.setdefault(n, []).append((s, s + d))
        if WINDOW_SPAN in marks:
            spans = marks[WINDOW_SPAN]
            extent = (min(s for s, _ in spans), max(e for _, e in spans))
        elif SAMPLE_START in marks and sample_s is not None:
            t0 = min(s for s, _ in marks[SAMPLE_START])
            extent = (t0, t0 + 1e9 * sample_s)
        if extent is None:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span and no "
                             "profiler extent")
        self.t0, self.t1 = extent

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) / 1e9

    @functools.cached_property
    def _ops(self) -> list:
        """``(chip, op, start_ns, end_ns)`` of every device op, clipped to
        the window."""
        out = []
        for p in self.devices:
            for ln in OP_LINES:
                for n, s, d in p.lines.get(ln, ()):
                    s0, e0 = max(s, self.t0), min(s + d, self.t1)
                    if e0 > s0:
                        out.append((p.name, _op(n), s0, e0))
        return out

    @functools.cached_property
    def _busy(self) -> dict:
        per: dict = {p.name: [] for p in self.devices}
        for dev, _, s, e in self._ops:
            per[dev].append((s, e))
        return {d: _merge(iv) for d, iv in per.items()}

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the traced chips."""
        if not self._busy:
            return 0.0
        return sum(e - s for iv in self._busy.values() for s, e in iv) / (
            1e9 * len(self._busy))

    def op_time_s(self, match: Callable[[str], bool]) -> float:
        """Device seconds of the ops whose instruction name ``match``
        accepts, summed over chips. Ops nest (a loop's body ops lie inside
        the loop's event), so ``match`` names ops that do not contain one
        another."""
        return sum(e - s for _, n, s, e in self._ops if match(n)) / 1e9

    def _host_labels(self, times) -> list:
        """The innermost host event running at each of ``times``."""
        best = [None] * len(times)
        for p in self.host:
            for evs in p.lines.values():
                for n, s, d in evs:
                    if s + d < self.t0 or s > self.t1 or n in (
                            WINDOW_SPAN, SAMPLE_START):
                        continue
                    for i, t in enumerate(times):
                        if s <= t <= s + d and (best[i] is None
                                                or d < best[i][1]):
                            best[i] = (n, d)
        return [b[0] if b else "none" for b in best]

    def breakdown(self) -> dict:
        ops: dict = {}
        for _, n, s, e in self._ops:
            ops[n] = ops.get(n, 0.0) + (e - s) / 1e9
        top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = []
        for iv in self._busy.values():
            edges = [self.t0] + [x for s, e in iv for x in (s, e)] + [self.t1]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((b - a, (a + b) / 2))
        gaps = sorted(gaps, reverse=True)[:TOP]
        labels = self._host_labels([mid for _, mid in gaps])
        return {"device_ops": [[n, s] for n, s in top_ops],
                "idle_gaps": [[name, g / 1e9]
                              for name, (g, _) in zip(labels, gaps)]}


def summarize(directory: str, sample_s=None) -> Summary:
    return Summary(*load(newest_trace(directory)), sample_s=sample_s)
