"""Reuse-distance kernel + MRC exactness harness.

Three rings of defense around the one-pass miss-rate-curve engine:

1. **Kernel goldens** — the Pallas dominance-count kernel (interpret and,
   where a compiled backend exists, compiled mode) against the pure-jax
   oracle, and both against a brute-force python stack simulation;
   segmentation tests prove distances never leak across shard rows or
   into padding.
2. **Counter exactness** — :func:`repro.sim.mrc.mrc_tier1_counters` is
   bit-identical to the sequential scan engine for LRU at *every* cache
   size, whole-stream and per-window, on adversarial access patterns
   (all-unique, single hot key, cycles straddling the capacity) and on
   random traffic with writes (the write-back episode intervals).
3. **Domain fences** — sizes/policy/prefetch/windowed-write requests
   outside the exactness domain raise ``ValueError``.

Property-based fuzzing (hypothesis) deepens ring 2 when the library is
installed; the fixed-seed tests above always run.
"""
import numpy as np
import pytest

import jax

from repro.kernels import reuse_distance
from repro.kernels.ref import DIST_INF, reuse_distance_ref
from repro.kernels.reuse_distance import (
    prev_occurrence,
    reuse_distance_kernel,
    reuse_distances,
)
from repro.sim import SimSpec, mrc_tier1_counters, mrc_unsupported_reason
from repro.sim.engine import tier1_counters
from repro.sim.spec import StoreConfig, TrafficSpec

# ---------------------------------------------------------------------------
# brute-force oracles


def _brute_distances(pages):
    """Set-based Mattson stack distances for one flat stream."""
    last = {}
    out = np.empty(len(pages), np.int64)
    for j, p in enumerate(pages):
        if p in last:
            out[j] = len({pages[k] for k in range(last[p] + 1, j)})
        else:
            out[j] = DIST_INF
        last[p] = j
    return out


def _ragged_prev(rng, S, L, n_pages):
    """Random ragged shard rows (pads = repeats of the last page, like
    partition_streams) plus their prev/valid arrays."""
    counts = rng.integers(0, L + 1, S)
    counts[rng.integers(0, S)] = L          # at least one full row
    sh_pages = rng.integers(0, n_pages, (S, L)).astype(np.int32)
    for s in range(S):
        if counts[s] < L:
            fill = sh_pages[s, counts[s] - 1] if counts[s] else 0
            sh_pages[s, counts[s]:] = fill
    return sh_pages, counts


def test_prev_occurrence_bruteforce():
    rng = np.random.default_rng(0)
    for _ in range(5):
        sh_pages, counts = _ragged_prev(rng, S=3, L=41, n_pages=7)
        prev, valid = prev_occurrence(sh_pages, counts)
        for s in range(3):
            last = {}
            for j in range(41):
                if j >= counts[s]:
                    assert not valid[s, j] and prev[s, j] == -1
                    continue
                assert valid[s, j]
                assert prev[s, j] == last.get(sh_pages[s, j], -1)
                last[sh_pages[s, j]] = j


def test_ref_matches_bruteforce():
    rng = np.random.default_rng(1)
    for _ in range(5):
        sh_pages, counts = _ragged_prev(rng, S=3, L=57, n_pages=9)
        prev, valid = prev_occurrence(sh_pages, counts)
        d = np.asarray(reuse_distance_ref(prev, valid, block=16))
        for s in range(3):
            want = _brute_distances(sh_pages[s, : counts[s]].tolist())
            np.testing.assert_array_equal(d[s, : counts[s]], want)
            np.testing.assert_array_equal(d[s, counts[s]:], -1)


@pytest.mark.parametrize("seed,S,L,block", [(2, 1, 16, 8), (3, 4, 100, 16),
                                            (4, 2, 128, 128), (5, 3, 37, 32)])
def test_pallas_interpret_matches_ref(seed, S, L, block):
    """Golden: interpret-mode Pallas kernel == pure-jax oracle (query blocks
    of ``block``), bit for bit, across shapes that exercise padding."""
    rng = np.random.default_rng(seed)
    sh_pages, counts = _ragged_prev(rng, S=S, L=L, n_pages=11)
    prev, valid = prev_occurrence(sh_pages, counts)
    ref = np.asarray(reuse_distance_ref(prev, valid, block=block))
    ker = np.asarray(
        reuse_distance_kernel(prev, valid, interpret=True))
    np.testing.assert_array_equal(ker, ref)


def _kernel_with_chunks(monkeypatch, key_rows, q_rows, prev, valid, *,
                        interpret):
    """Run the kernel with ``key_rows``-row key chunks and ``q_rows``-row
    query blocks; the traces made with them are dropped afterwards."""
    monkeypatch.setattr(reuse_distance, "KEY_ROWS", key_rows)
    monkeypatch.setattr(reuse_distance, "Q_ROWS", q_rows)
    jax.clear_caches()
    try:
        return np.asarray(
            reuse_distance_kernel(prev, valid, interpret=interpret))
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("seed,S,L,n_pages,key_rows,q_rows", [
    (7, 2, 300, 11, 1, 1),      # 3 key chunks of 128, 3 query cells
    (8, 3, 700, 200, 2, 1),     # long reuse gaps span several chunks
    (9, 1, 1000, 40, 4, 2),     # multi-row chunks and query blocks
])
def test_pallas_interpret_multi_chunk_matches_ref(monkeypatch, seed, S, L,
                                                  n_pages, key_rows, q_rows):
    """Golden with small key chunks: each row streams several key chunks
    through both buffer slots, skips the chunks before a column's smallest
    ``prev`` and spans several query-block grid cells."""
    rng = np.random.default_rng(seed)
    sh_pages, counts = _ragged_prev(rng, S=S, L=L, n_pages=n_pages)
    prev, valid = prev_occurrence(sh_pages, counts)
    ref = np.asarray(reuse_distance_ref(prev, valid))
    ker = _kernel_with_chunks(monkeypatch, key_rows, q_rows, prev, valid,
                              interpret=True)
    np.testing.assert_array_equal(ker, ref)


@pytest.mark.kernels
def test_pallas_compiled_matches_ref(monkeypatch):
    """Compiled-mode golden — only meaningful on an accelerator backend
    (deselect with ``-m 'not kernels'``; auto-skips on CPU, where
    non-interpret Pallas does not lower). The second case streams three
    key chunks of 1,024 positions over three query cells per row."""
    if jax.default_backend() == "cpu":
        pytest.skip("no accelerator backend: compiled Pallas needs TPU/GPU")
    rng = np.random.default_rng(6)
    sh_pages, counts = _ragged_prev(rng, S=2, L=100, n_pages=13)
    prev, valid = prev_occurrence(sh_pages, counts)
    ref = np.asarray(reuse_distance_ref(prev, valid))
    ker = np.asarray(
        reuse_distance_kernel(prev, valid, interpret=False))
    np.testing.assert_array_equal(ker, ref)
    sh_pages, counts = _ragged_prev(rng, S=3, L=3000, n_pages=500)
    prev, valid = prev_occurrence(sh_pages, counts)
    ref = np.asarray(reuse_distance_ref(prev, valid))
    ker = _kernel_with_chunks(monkeypatch, 8, 8, prev, valid,
                              interpret=False)
    np.testing.assert_array_equal(ker, ref)


def test_shard_segmentation_no_leaks():
    """A page ending one shard row and opening the next must be a
    compulsory miss in the second row, and pads (edge-repeats) must
    neither count toward gaps nor receive distances."""
    sh_pages = np.array([
        [5, 1, 2, 5, 5, 5],     # row 0: last real = page 5, then pads
        [5, 3, 5, 3, 3, 3],     # row 1 opens with page 5: must be INF
    ], np.int32)
    counts = np.array([4, 4])
    prev, valid = prev_occurrence(sh_pages, counts)
    d = np.asarray(reuse_distances(prev, valid, block=4))
    # Row 0: 5 reused at j=3 with gap {1, 2}.
    np.testing.assert_array_equal(d[0, :4], [DIST_INF, DIST_INF, DIST_INF, 2])
    # Row 1: page 5 did NOT carry over from row 0; the pad repeats of
    # page 3 (row 0's pads repeat page 5) contribute to nothing.
    np.testing.assert_array_equal(d[1, :4], [DIST_INF, DIST_INF, 1, 1])
    np.testing.assert_array_equal(d[:, 4:], -1)
    # Interpret-mode kernel agrees on the same segmentation case.
    ker = np.asarray(
        reuse_distance_kernel(prev, valid, interpret=True))
    np.testing.assert_array_equal(ker, d)


# ---------------------------------------------------------------------------
# MRC counter exactness vs the scan engine

_BASE = SimSpec(
    traffic=TrafficSpec(kind="irm", n_requests=240, n_pages=48,
                        write_fraction=0.0, seed=9),
    store=StoreConfig(n_lines=8, policy="lru"),
    n_shards=3,
    lam=120.0,
)


def _assert_counters_equal(spec, sizes, trace=None, ctx=""):
    got = mrc_tier1_counters(spec, sizes, trace=trace)
    for C in sizes:
        ref = tier1_counters(spec.replace(**{"store.n_lines": int(C)}),
                             trace=trace)
        g = got[int(C)]
        for f in ref._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(g, f)), np.asarray(getattr(ref, f)),
                err_msg=f"{ctx} C={C} field={f}")


def _adversarial_traces(n_lines, n):
    """Access patterns that sit exactly on the hit/miss boundary."""
    rng = np.random.default_rng(13)
    cyc = lambda period: np.arange(n) % period
    hot = rng.integers(0, 12, n)
    hot[rng.random(n) < 0.5] = 0                       # single hot key
    return {
        "all-unique": np.arange(n),
        "single-hot-key": hot,
        f"cycle-{n_lines - 1}": cyc(n_lines - 1),
        f"cycle-{n_lines}": cyc(n_lines),
        f"cycle-{n_lines + 1}": cyc(n_lines + 1),      # classic LRU thrash
    }


@pytest.mark.parametrize("pattern", ["all-unique", "single-hot-key",
                                     "cycle-7", "cycle-8", "cycle-9"])
def test_mrc_adversarial_patterns_whole_stream(pattern):
    n_lines = 8
    trace_pages = _adversarial_traces(n_lines, 160)[pattern]
    sizes = [1, n_lines - 1, n_lines, n_lines + 1, 64]
    trace = (trace_pages, np.zeros(len(trace_pages), bool))
    _assert_counters_equal(_BASE, sizes, trace=trace, ctx=pattern)


@pytest.mark.parametrize("pattern", ["all-unique", "cycle-8", "cycle-9"])
def test_mrc_adversarial_patterns_windowed(pattern):
    trace_pages = _adversarial_traces(8, 160)[pattern]
    spec = _BASE.replace(n_windows=5)
    trace = (trace_pages, np.zeros(len(trace_pages), bool))
    _assert_counters_equal(spec, [7, 8, 9], trace=trace, ctx=pattern)


def test_mrc_writes_whole_stream():
    """Random write traffic: the episode-interval write-back counts must
    equal the engine's dirty-eviction write-backs at every size —
    including sizes beyond the working set (no evictions at all)."""
    spec = _BASE.replace(**{"traffic.write_fraction": 0.35})
    _assert_counters_equal(spec, [1, 2, 5, 8, 11, 48, 200], ctx="writes")


def test_mrc_windowed_write_free_traffic():
    spec = _BASE.replace(n_windows=4, **{"traffic.kind": "markov"})
    _assert_counters_equal(spec, [1, 8, 16, 64], ctx="windowed")


def test_mrc_timed_windows():
    spec = _BASE.replace(window_dt=0.4)
    _assert_counters_equal(spec, [4, 8, 32], ctx="timed")


def test_mrc_trace_with_timestamps():
    rng = np.random.default_rng(3)
    pages = rng.integers(0, 30, 300)
    times = np.sort(rng.uniform(0.0, 2.0, 300))
    spec = _BASE.replace(window_dt=0.5)
    trace = (pages, np.zeros(300, bool), times)
    _assert_counters_equal(spec, [2, 8, 30], trace=trace, ctx="trace-timed")


# ---------------------------------------------------------------------------
# domain fences


def test_mrc_rejects_non_lru_policies():
    for policy in ("lfu", "ws", "random"):
        spec = _BASE.replace(**{"store.policy": policy})
        assert mrc_unsupported_reason(spec) is not None
        with pytest.raises(ValueError,
                           match="only for policy='lru'"):
            mrc_tier1_counters(spec, [8])


def test_mrc_rejects_prefetch():
    spec = _BASE.replace(**{"store.prefetch": True})
    with pytest.raises(ValueError, match="prefetch"):
        mrc_tier1_counters(spec, [8])


def test_mrc_rejects_windowed_writes():
    spec = _BASE.replace(n_windows=4,
                         **{"traffic.write_fraction": 0.3})
    assert "window" in mrc_unsupported_reason(spec)
    with pytest.raises(ValueError, match="write-free"):
        mrc_tier1_counters(spec, [8])


def test_mrc_rejects_bad_sizes():
    with pytest.raises(ValueError, match="non-empty"):
        mrc_tier1_counters(_BASE, [])
    with pytest.raises(ValueError, match=">= 1"):
        mrc_tier1_counters(_BASE, [0, 4])


# ---------------------------------------------------------------------------
# property-based fuzz (optional dependency)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        pages=st.lists(st.integers(0, 12), min_size=1, max_size=120),
        writes=st.lists(st.booleans(), min_size=120, max_size=120),
        n_lines=st.integers(1, 14),
    )
    def test_fuzz_mrc_matches_engine_whole_stream(pages, writes, n_lines):
        trace = (np.asarray(pages),
                 np.asarray(writes[: len(pages)], bool))
        sizes = [max(1, n_lines - 1), n_lines, n_lines + 1]
        _assert_counters_equal(_BASE, sizes, trace=trace, ctx="fuzz")

    @settings(max_examples=15, deadline=None)
    @given(pages=st.lists(st.integers(0, 9), min_size=4, max_size=80))
    def test_fuzz_distances_match_bruteforce(pages):
        arr = np.asarray(pages, np.int32)[None, :]
        counts = np.array([len(pages)])
        prev, valid = prev_occurrence(arr, counts)
        d = np.asarray(reuse_distances(prev, valid, block=16))
        np.testing.assert_array_equal(d[0], _brute_distances(pages))
