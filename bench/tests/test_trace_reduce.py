"""The trace reduction and the compile-span union, on hand-made traces and
on a trace recorded on a TPU v5e (``data/``, where present).

  python -m pytest bench/tests -q
"""
import glob
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402
from clock import CompileLog  # noqa: E402

MS = 1e6  # ns


def planes():
    """One chip; window 0-100 ms; ops busy 10-30, 25-40 (overlap) and
    70-90 ms; a 5 ms op outside the window; host spans name the gaps."""
    dev = tr.Plane("/device:TPU:0", {
        "XLA Ops": [("fusion.1", 10 * MS, 20 * MS),
                    ("reuse_distance.3", 25 * MS, 15 * MS),
                    ("fusion.1", 70 * MS, 20 * MS),
                    ("fusion.1", 120 * MS, 5 * MS)],
    })
    host = tr.Plane("/host:CPU", {
        "python": [("bench.window", 0, 100 * MS),
                   ("bench.query", 0, 100 * MS),
                   ("partition", 40 * MS, 30 * MS),
                   ("np.bincount", 50 * MS, 5 * MS)],
    })
    return [host, dev, tr.Plane("/device:TPU:0 SparseCore", {})]


def test_busy_and_window():
    s = tr.Summary(planes())
    assert s.window_s == pytest.approx(0.1)
    # Union of [10, 40] and [70, 90] ms inside the window.
    assert s.busy_s == pytest.approx(0.05)


def test_kernel_time():
    s = tr.Summary(planes())
    assert s.op_time_s(lambda n: "reuse_distance" in n) == pytest.approx(
        0.015)
    assert s.op_time_s(lambda n: n == "fusion.1") == pytest.approx(0.04)
    assert s.op_time_s(lambda n: n == "none") == 0.0


def test_breakdown_names_gaps_by_innermost_host_event():
    b = tr.Summary(planes()).breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.04)]
    assert b["device_ops"][1] == ["reuse_distance.3", pytest.approx(0.015)]
    gaps = b["idle_gaps"]
    # Gaps 40-70 (midpoint 55: inside np.bincount), 0-10, 90-100 ms.
    assert gaps[0] == ["np.bincount", pytest.approx(0.03)]
    assert sorted(g for _, g in gaps[1:]) == [pytest.approx(0.01)] * 2
    assert all(name == "bench.query" for name, _ in gaps[1:])


def test_sample_marks_then_profiler_extent_set_the_window():
    ps = planes()
    ps[0].lines["python"] = ps[0].lines["python"][1:]    # a sampled trace
    s = tr.Summary(ps, extent=(20 * MS, 80 * MS))
    assert s.window_s == pytest.approx(0.06)
    assert s.busy_s == pytest.approx(0.03)              # [20, 40], [70, 80]
    ps[0].lines["python"].append((tr.SAMPLE_START, 30 * MS, 0))
    s = tr.Summary(ps, extent=(20 * MS, 99 * MS), sample_s=0.045)
    assert s.window_s == pytest.approx(0.045)
    assert s.busy_s == pytest.approx(0.015)             # [30, 40], [70, 75]
    with pytest.raises(ValueError):
        tr.Summary([tr.Plane("/host:CPU", {"python": []})])


def test_compile_union_counts_nested_spans_once():
    log = CompileLog()
    ev = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/backend_compile_duration")
    log.spans = [(ev[0], 1.0, 3.0), (ev[1], 2.0, 2.5), (ev[1], 5.0, 6.0),
                 (ev[1], 9.0, 12.0)]
    got = log.between(0.0, 10.0)
    assert got["seconds"] == pytest.approx(3.0)
    assert got["events"]["jaxpr_trace"] == 1
    assert got["events"]["backend_compile"] == 2


RECORDED = sorted(glob.glob(os.path.join(os.path.dirname(__file__), "data",
                                         "*.xplane.pb.gz")))


@pytest.mark.parametrize("path", RECORDED, ids=os.path.basename)
def test_recorded_chip_trace(path):
    s = tr.Summary(*tr.load(path))
    assert s.devices, "a TPU trace has device planes"
    assert 0 < s.busy_s <= s.window_s
    b = s.breakdown()
    assert b["device_ops"] and len(b["device_ops"]) <= tr.TOP
    assert len(b["idle_gaps"]) <= tr.TOP
    total_ops = sum(t for _, t in b["device_ops"])
    assert total_ops >= s.busy_s * 0.5
