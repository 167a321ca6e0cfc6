"""Stage spans (``repro.sim.spans``): the profile dict they fill, the
profiler events they emit, and the spans and counters of the chunked
replay and the MRC pass."""
import glob
import os
import time

import jax
import numpy as np
import pytest

from repro.core.traffic import TrafficSpec
from repro.sim import SimSpec, mrc_tier1_counters, stream_tier1_counters
from repro.sim.spans import count, span
from repro.storage.tiered_store import StoreConfig


def assert_counters_equal(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"Tier1Counters.{f} differs")


def test_span_adds_seconds_and_nests():
    prof = {}
    with span("outer", prof):
        with span("inner", prof):
            time.sleep(0.01)
        with span("inner", prof):
            pass
    assert set(prof) == {"outer", "inner"}
    assert prof["inner"] >= 0.01
    assert prof["outer"] >= prof["inner"]
    with span("outer", prof):
        pass
    assert prof["outer"] >= 0.01      # accumulated, not overwritten


def test_span_records_a_stage_that_raises():
    prof = {}
    with pytest.raises(ValueError):
        with span("stage", prof):
            raise ValueError("boom")
    assert prof["stage"] >= 0.0


def test_no_profile_writes_nothing():
    with span("stage"):
        with span("inner", None):
            pass
    count("chunks", None, 3)            # no dict: nothing to write, no error
    prof = {}
    count("chunks", prof)
    count("chunks", prof, 4)
    assert prof == {"chunks": 5}
    assert isinstance(prof["chunks"], int)


def _trace_events(path):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                      recursive=True)
    assert files, "the profiler wrote no trace"
    data = ProfileData.from_file(files[0])
    return [(p.name, ev.name) for p in data.planes for ln in p.lines
            for ev in ln.events]


def test_span_emits_a_host_event_in_the_profiler_trace(tmp_path):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("probe_stage", {}):
            jax.numpy.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = _trace_events(str(tmp_path))
    hits = [plane for plane, name in events if name == "repro.probe_stage"]
    assert hits and all(p.startswith("/host:") for p in hits)


def test_mrc_profile_fills_its_four_spans():
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=3000, n_pages=400,
                            zipf_s=1.1, seed=11),
        store=StoreConfig(n_lines=32, policy="lru"),
        n_shards=4, n_windows=5,
    )
    sizes = [8, 32, 128]
    prof = {}
    got = mrc_tier1_counters(spec, sizes, profile=prof)
    assert set(prof) == {"mrc_prep", "mrc_prev_occurrence",
                         "mrc_reuse_distances", "mrc_histogram"}
    assert all(v > 0 for v in prof.values())
    want = mrc_tier1_counters(spec, sizes)
    assert sorted(got) == sorted(want) == sizes
    for c in sizes:
        assert_counters_equal(got[c], want[c])


def test_stream_profile_spans_and_scan_step_counters():
    """Three chunks of 1,024 requests over four block-mapped shards of 256
    pages: the outer chunks spread evenly and fit the 512-step primary
    bucket, the middle one lands on shard 0 alone and takes the 1,024-step
    fallback."""
    rng = np.random.default_rng(7)
    even = rng.permutation(1024)
    hot = rng.integers(0, 256, 1024)
    pages = np.concatenate([even, hot, rng.permutation(1024)]).astype(
        np.int32)
    is_write = rng.random(pages.size) < 0.2
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=pages.size, n_pages=1024,
                            seed=1),
        store=StoreConfig(n_lines=64, policy="lru"),
        n_shards=4, n_windows=6, mapping="block",
    )
    prof = {}
    ctr, _, ck = stream_tier1_counters(spec, (pages, is_write), chunk=1024,
                                       profile=prof)
    assert ck.done
    for key in ("stream_resume_prep", "stream_engine", "stream_chunk_host",
                "stream_chunk_dispatch", "stream_chunk_wait"):
        assert prof[key] > 0, key
    assert prof["stream_chunks"] == 3
    assert prof["stream_requests"] == pages.size
    assert prof["stream_scan_steps"] == (512 + 1024 + 512) * 4
    # The engine span holds every chunk after the first one's host prep.
    assert prof["stream_engine"] >= prof["stream_chunk_wait"]
    plain, _, _ = stream_tier1_counters(spec, (pages, is_write), chunk=1024)
    assert_counters_equal(ctr, plain)
