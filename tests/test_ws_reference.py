"""The chunked replay under the weight-sharing learner against a plain
reference of Algorithms 1-2 (``tests/ws_reference.py``): counters exact,
window weights within the reference's tolerance, across resumes at chunk
boundaries and pads in the last chunk."""
import os
import types
from collections import OrderedDict

import numpy as np
import pytest

import jax

from repro.core.traffic import TrafficSpec
from repro.kernels.ref import cache_scan_noise
from repro.sim import SimSpec, stream_tier1_counters
from repro.storage.tiered_store import StoreConfig

import ws_reference as ref

HERE = os.path.dirname(os.path.abspath(__file__))
N_SHARDS, N_WINDOWS, RATE = 4, 6, 100.0
CHUNK = 256


def make_trace(seed, n=3000, n_pages=600, zipf=0.9, write_fraction=0.2):
    rng = np.random.default_rng(seed)
    pop = np.arange(1, n_pages + 1, dtype=float) ** -zipf
    ranks = rng.choice(n_pages, n, p=pop / pop.sum())
    pages = rng.permutation(n_pages)[ranks].astype(np.int32)
    times = np.cumsum(rng.exponential(1.0, n)) / RATE
    return pages, rng.random(n) < write_fraction, times


def spec_for(n, n_lines, policy="ws", **knobs):
    return SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=n, n_pages=1, rate=RATE),
        store=StoreConfig(n_lines=n_lines, policy=policy, **knobs),
        n_shards=N_SHARDS, mapping="block", n_windows=N_WINDOWS,
        window_dt=n / RATE / N_WINDOWS)


def reference(trace, n_lines, policy="ws", **knobs):
    n = len(trace[0])
    return ref.Replay(*trace, n_shards=N_SHARDS, mapping="block",
                      n_lines=n_lines, n_windows=N_WINDOWS,
                      window_dt=n / RATE / N_WINDOWS, policy=policy,
                      learner=ref.Learner(**knobs))


def resumed(spec, trace, step, profile=None):
    """``(prefix, counters)`` after each call of a replay resumed every
    ``step`` requests (a multiple of the chunk, so the last chunk of the
    trace holds pads)."""
    out, ck, done = [], None, 0
    while done < len(trace[0]):
        ctr, _, ck = stream_tier1_counters(spec, trace, chunk=CHUNK,
                                           checkpoint=ck, max_requests=step,
                                           profile=profile)
        done = ck.offset
        out.append((done, ctr))
    return out


def assert_matches(ctr, want):
    got = ref.mismatches(ctr, want, ref.WEIGHT_TOL)
    assert got["counters"] == 0 and got["weights"] == 0, got


def as_program(ctrs: dict):
    """Reference counters in the program's ``[S, ...]`` layout."""
    return types.SimpleNamespace(**{
        name: np.stack([np.asarray(ctrs[s][name]) for s in sorted(ctrs)])
        for name in ref.TOTALS + ref.WINDOWED + ("win_weights",)})


@pytest.mark.parametrize("n_lines", [16, 64])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_ws_replay_matches_reference(seed, n_lines):
    trace = make_trace(seed)
    assert len(trace[0]) % CHUNK, "the last chunk should hold pads"
    want = reference(trace, n_lines)
    calls = resumed(spec_for(len(trace[0]), n_lines), trace, 3 * CHUNK)
    assert len(calls) == 4
    for prefix, ctr in calls:
        assert_matches(ctr, want.counters(prefix))
    use = np.asarray(calls[-1][1].win_expert_use).sum(axis=(0, 1))
    assert np.count_nonzero(use) >= 2, "the learner should leave LRU"


def test_random_expert_is_chosen():
    # A loop over slightly more pages than a shard holds: LRU and LFU both
    # evict the page that comes next, so the learner turns to Random.
    n_lines, n = 16, 4000
    rng = np.random.default_rng(7)
    loop = np.tile(np.arange(N_SHARDS * 20), n // (N_SHARDS * 20) + 1)[:n]
    noise = rng.integers(0, N_SHARDS * 20, n)
    pages = np.where(rng.random(n) < 0.9, loop, noise).astype(np.int32)
    trace = (pages, np.zeros(n, bool), np.arange(1, n + 1) / RATE)
    want = reference(trace, n_lines)
    final = want.counters(n)
    assert sum(c["win_expert_use"][:, 2].sum() for c in final.values()) > 0
    calls = resumed(spec_for(n, n_lines), trace, 4 * CHUNK)
    for prefix, ctr in calls:
        assert_matches(ctr, want.counters(prefix))
    assert np.asarray(calls[-1][1].win_expert_use)[..., 2].sum() > 0


def plain_lru(pages, n_lines):
    """Hit flags of one shard under LRU: the reference's self-check."""
    cache, hits = OrderedDict(), []
    for p in pages:
        hits.append(p in cache)
        if p in cache:
            cache.move_to_end(p)
        else:
            if len(cache) >= n_lines:
                cache.popitem(last=False)
            cache[p] = None
    return np.asarray(hits)


def test_threshold_above_one_is_plain_lru():
    """With ``threshold`` > 1 no expert reaches it, the weights stay equal,
    and the first expert, LRU, names every victim."""
    trace = make_trace(5, write_fraction=0.0)
    n, n_lines = len(trace[0]), 32
    ws = reference(trace, n_lines, threshold=1.5)
    lru = reference(trace, n_lines, policy="lru")
    own = ref.owners(trace[0], N_SHARDS, "block")
    for s, (a, b) in enumerate(zip(ws.shards, lru.shards)):
        np.testing.assert_array_equal(a.hit, b.hit)
        np.testing.assert_array_equal(a.expert, b.expert)
        np.testing.assert_array_equal(
            a.hit, plain_lru(trace[0][own == s].tolist(), n_lines))
        assert len(a.adjust_at) == 0
    program = stream_tier1_counters(spec_for(n, n_lines, threshold=1.5),
                                    trace, chunk=CHUNK)[0]
    assert_matches(program, ws.counters(n))
    assert_matches(as_program(lru.counters(n)), ws.counters(n))


def test_eviction_counters_are_per_call_deltas():
    trace = make_trace(3)
    want = reference(trace, 16)
    prof: dict = {}
    calls = resumed(spec_for(len(trace[0]), 16), trace, 3 * CHUNK, prof)
    use = sum(c["win_expert_use"].sum(axis=0)
              for c in want.counters(calls[-1][0]).values())
    assert prof["stream_evictions"] == use.sum() > 0
    for i, name in enumerate(ref.EXPERTS):
        assert prof["stream_evictions_" + name] == use[i]
    # One call alone counts its own evictions, not the replay's so far.
    ck = stream_tier1_counters(spec_for(len(trace[0]), 16), trace,
                               chunk=CHUNK, max_requests=3 * CHUNK)[2]
    one: dict = {}
    stream_tier1_counters(spec_for(len(trace[0]), 16), trace, chunk=CHUNK,
                          checkpoint=ck, max_requests=3 * CHUNK,
                          profile=one)
    a, b = want.counters(3 * CHUNK), want.counters(6 * CHUNK)
    assert one["stream_evictions"] == sum(
        b[s]["evictions"] - a[s]["evictions"] for s in a)


def test_key_rule_draws_match_the_program_noise():
    """Random's proposals, drawn in blocks, are the argmax of the rows the
    program's in-loop PRNG would draw from ``PRNGKey(0)``."""
    n, n_lines = 11, 32
    noise = cache_scan_noise(jax.random.PRNGKey(0), n, n_lines)
    want = np.asarray(np.argmax(np.asarray(noise), axis=1))
    np.testing.assert_array_equal(ref.random_lines(n, n_lines, block=4), want)


def test_tolerance_fails_bfloat16_weights(monkeypatch):
    """Weights rounded to bfloat16 at each adjust miss the tolerance."""
    trace = make_trace(1)
    n = len(trace[0])
    exact = reference(trace, 16).counters(n)
    adjust = ref._adjust

    def bf16(*args):
        w = np.asarray(adjust(*args), np.float32).view(np.uint32)
        w = ((w + np.uint32(0x7FFF) + ((w >> 16) & 1)) & 0xFFFF0000)
        return tuple(float(x) for x in w.view(np.float32))

    monkeypatch.setattr(ref, "_adjust", bf16)
    rounded = reference(trace, 16).counters(n)
    got = ref.mismatches(as_program(rounded), exact, ref.WEIGHT_TOL)
    assert got["weights"] > 0 and got["weight_err"] > 100 * ref.WEIGHT_TOL


def test_benchmark_copy_is_the_reference():
    with open(os.path.join(HERE, "ws_reference.py"), "rb") as a, open(
            os.path.join(HERE, os.pardir, "bench", "reference_ws.py"),
            "rb") as b:
        assert a.read() == b.read()
