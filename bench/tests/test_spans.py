"""The readers of the program's ``repro.<key>`` spans, on hand-made planes:
clipping to the traced window, spans cut at either edge, the per-query
metric files, and a program without the spans.

  python -m pytest bench/tests -q
"""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import spans  # noqa: E402
import trace_reduce as tr  # noqa: E402
from harness import load_module  # noqa: E402

MS = 1e6  # ns


def planes():
    """Window 10-110 ms (``bench.window``). ``repro.traffic_gen`` spans:
    0-20 (cut at the window's start), 30-40, 50-60 (that one twice, on
    two host lines, as one event), 100-130 (cut at its end) and 120-125
    (outside). ``repro.mrc_prep`` 40-50 and 45-55 overlap."""
    host = tr.Plane("/host:CPU", {
        "python": [("bench.window", 10 * MS, 100 * MS),
                   ("repro.traffic_gen", 0, 20 * MS),
                   ("repro.traffic_gen", 30 * MS, 10 * MS),
                   ("repro.traffic_gen", 50 * MS, 10 * MS),
                   ("repro.traffic_gen", 100 * MS, 30 * MS),
                   ("repro.traffic_gen", 120 * MS, 5 * MS),
                   ("repro.mrc_prep", 40 * MS, 10 * MS),
                   ("traffic_gen", 60 * MS, 10 * MS)],
        "main/1": [("repro.mrc_prep", 45 * MS, 10 * MS)],
    })
    dev = tr.Plane("/device:TPU:0", {
        "XLA Ops": [("repro.traffic_gen", 60 * MS, 10 * MS)]})
    return [host, dev]


def test_seconds_clip_to_the_window():
    s = tr.Summary(planes())
    assert (s.t0, s.t1) == (10 * MS, 110 * MS)
    # [10, 20] + [30, 40] + [50, 60] + [100, 110]; the device plane's op and
    # an event without the prefix are not spans.
    assert spans.seconds(s, "traffic_gen") == pytest.approx(0.04)
    # Overlapping spans on two lines count their union once.
    assert spans.seconds(s, "mrc_prep") == pytest.approx(0.015)
    assert spans.seconds(s, "absent") == 0.0


def test_count_takes_spans_reaching_into_the_window():
    s = tr.Summary(planes())
    # Cut at the start, two whole, cut at the end; 120-125 lies outside.
    assert spans.count(s, "traffic_gen") == 4
    assert spans.count(s, "mrc_prep") == 2
    assert spans.count(s, "absent") == 0


def test_intervals_are_clipped():
    s = tr.Summary(planes())
    assert sorted(spans.intervals(s, "traffic_gen")) == [
        (10 * MS, 20 * MS), (30 * MS, 40 * MS), (50 * MS, 60 * MS),
        (100 * MS, 110 * MS)]


def metric(name):
    return load_module(os.path.join(BENCH, "metrics", name + ".py"),
                       "bench_metric_" + name)


def ctx(trace, queries=2, work=8, profile=None):
    return types.SimpleNamespace(profile=profile or {}, trace=trace,
                                 queries=queries, work=work, window_s=0.1)


def test_trace_metrics_per_query_and_point():
    s = tr.Summary(planes())
    assert metric("mrc_prep_ms_per_curve.mrc").read(ctx(s)) == (
        pytest.approx(7.5))
    assert metric("traffic_gen_s_per_point.sweep").read(ctx(s)) == (
        pytest.approx(0.005))
    assert metric("traffic_gens_per_point.sweep").read(ctx(s)) == 0.5


@pytest.mark.parametrize("name", [
    "mrc_prep_ms_per_curve.mrc", "prev_occurrence_ms_per_curve.mrc",
    "reuse_call_ms_per_curve.mrc", "mrc_histogram_ms_per_curve.mrc",
    "traffic_gen_s_per_point.sweep", "traffic_gens_per_point.sweep"])
def test_trace_metrics_report_nothing_without_spans(name):
    """An untraced run, or a program that emits no span, reports nothing
    and does not raise."""
    bare = tr.Summary([tr.Plane("/host:CPU", {
        "python": [("bench.window", 0, 100 * MS)]})])
    assert metric(name).read(ctx(bare)) is None
    assert metric(name).read(ctx(None)) is None


def test_replay_metrics_read_the_profile():
    prof = {"stream_resume_prep": 0.7, "stream_engine": 22.0,
            "stream_chunks": 8, "stream_scan_steps": 8 * 16 * 262144,
            "stream_requests": 8 * 262144}
    c = ctx(None, queries=2, profile=prof)
    assert metric("resume_prep_ms_per_slice.replay").read(c) == (
        pytest.approx(350.0))
    assert metric("chunk_engine_ms.replay").read(c) == pytest.approx(2750.0)
    assert metric("scan_steps_per_request.replay").read(c) == 16.0
    # The parent program fills only the chunk keys.
    old = ctx(None, profile={"stream_chunk_host": 0.2, "stream_chunks": 8})
    for name in ("resume_prep_ms_per_slice.replay", "chunk_engine_ms.replay",
                 "scan_steps_per_request.replay"):
        assert metric(name).read(old) is None
