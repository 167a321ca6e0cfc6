"""The control and the plain reference at a size a test run holds: the
reference on a hand-worked trace, and each control failing the comparison
the benchmark makes (the readings the limits sit below)."""
import os
import sys

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import control  # noqa: E402
import reference  # noqa: E402

CFG = {
    "stream": {"kind": "irm", "n_requests": 40000, "n_pages": 2048,
               "zipf_s": 1.1, "scramble": "fnv1a64", "write_fraction": 0.0,
               "rate": 1600.0},
    "store": {"n_shards": 4, "mapping": "block", "n_lines": 128,
              "policy": "lru", "prefetch": False},
    "windows": {"n_windows": 8},
}


def test_lru_by_hand():
    # Two lines: 1 2 1 3 2 -> miss miss hit miss(evicts 2) miss(evicts 1).
    hit, ev, wb = reference.lru_flags([1, 2, 1, 3, 2], [0, 1, 0, 0, 0], 2)
    assert list(hit) == [0, 0, 1, 0, 0]
    assert list(ev) == [0, 0, 0, 1, 1]
    assert list(wb) == [0, 0, 0, 1, 0]          # page 2 was written
    # FIFO keeps 1 as the oldest: the 3 evicts 1, so the last 2 hits.
    hit, ev, _ = reference.lru_flags([1, 2, 1, 3, 2], [0] * 5, 2, "fifo")
    assert list(hit) == [0, 0, 1, 0, 1]
    # A reset empties the cache: the 1 after it misses.
    hit, _, _ = reference.lru_flags([1, 2, 1], [0] * 3, 2, "reset", [2])
    assert list(hit) == [0, 0, 0]


def test_windows_and_shards():
    pages = np.array([0, 9, 5, 3])
    assert reference.owners(pages, 2, "block").tolist() == [0, 1, 1, 0]
    times = np.array([10.0, 10.5, 11.9, 99.0])
    assert reference.window_ids(times, 3, 1.0).tolist() == [0, 0, 1, 2]


def test_replay_controls_fail_the_check():
    mix = {"chunk": 4096, "slice_chunks": 2}
    got = control.replay_readings(CFG, mix, seed=2**40 + 3, slices=3)
    assert got["fifo"]["counter_mismatches"] > 0
    assert got["reset"]["counter_mismatches"] > 0


def test_curve_control_fails_the_check():
    mix = {"sizes": {"first": 32, "step": 32, "count": 8},
           "stream_seed": 2**33 + 5}
    got = control.curve_readings(CFG, mix, seed=11)
    assert got["fifo"]["counter_mismatches"] > 0
