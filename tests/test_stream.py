"""Chunked streaming replay: bit-exactness across adversarial chunkings,
checkpoint/resume, tenant-mix attribution, compile-count bounds."""
import numpy as np
import pytest

try:  # hypothesis fuzz tests are optional (requirements-dev.txt)
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import jax

from repro.core.traffic import (
    TenantSpec,
    TenantStream,
    TrafficSpec,
    tenant_mix,
    tenant_mix_stream,
)
from repro.sim import (
    FaultSpec,
    SimSpec,
    mrc_unsupported_reason,
    shard_down,
    simulate,
    simulate_stream,
    stream_tier1_counters,
    sweep,
    tier1_counters,
)
from repro.sim.engine import report_from_counters
from repro.sim.stream import MIN_CAP, _chunk_cap
from repro.storage.tiered_store import (
    StoreConfig,
    reset_stream_compile_count,
    run_stream,
    run_stream_chunked,
    stream_compile_count,
    timestamp_window_ids,
)


def assert_counters_equal(a, b):
    for f in a._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"Tier1Counters.{f} differs")


@pytest.fixture(scope="module")
def indexed_spec():
    return SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=1200, n_pages=512,
                            zipf_s=1.1, write_fraction=0.3, seed=3),
        store=StoreConfig(n_lines=64, policy="ws"),
        n_shards=4, n_windows=7,
    )


@pytest.fixture(scope="module")
def indexed_ref(indexed_spec):
    return tier1_counters(indexed_spec)


class TestRunStreamChunked:
    @pytest.mark.parametrize("policy", ["lru", "ws"])
    def test_bit_exact_vs_one_shot(self, policy):
        cfg = StoreConfig(n_lines=32, policy=policy, prefetch=True)
        rng = np.random.default_rng(11)
        pages = rng.integers(0, 200, size=600).astype(np.int32)
        writes = rng.random(600) < 0.25
        ref = run_stream(cfg, pages, writes, n_windows=5)
        for chunk in (7, 64, 600, 1024):
            got = run_stream_chunked(cfg, pages, writes, chunk=chunk,
                                     n_windows=5)
            for f in ref._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(ref, f)), np.asarray(getattr(got, f)),
                    err_msg=f"{policy} chunk={chunk}: {f}")

    def test_chunk_must_be_positive(self):
        cfg = StoreConfig(n_lines=8)
        with pytest.raises(ValueError, match="chunk"):
            run_stream_chunked(cfg, np.zeros(4, np.int32),
                               np.zeros(4, bool), chunk=0)


class TestStreamCountersBitExact:
    @pytest.mark.parametrize("chunk", [11, 173, 600, 1200, 2048])
    def test_window_edge_chunkings(self, indexed_spec, indexed_ref, chunk):
        # 1200 requests over 7 windows: these chunk sizes straddle window
        # edges, split windows across many chunks, and exceed the stream.
        ctr, tenant_ctr, ck = stream_tier1_counters(indexed_spec,
                                                    chunk=chunk)
        assert tenant_ctr is None and ck.done
        assert_counters_equal(indexed_ref, ctr)

    def test_chunk_of_one(self, indexed_spec):
        # Degenerate chunking on a short prefix of the same workload.
        spec = indexed_spec.replace(**{"traffic.n_requests": 40})
        assert_counters_equal(tier1_counters(spec),
                              stream_tier1_counters(spec, chunk=1)[0])

    def test_report_bit_exact(self, indexed_spec, indexed_ref):
        one = report_from_counters(indexed_spec, indexed_ref)
        assert simulate_stream(indexed_spec, chunk=173).to_dict() \
            == one.to_dict()

    def test_trace_override(self, indexed_spec):
        rng = np.random.default_rng(5)
        trace = (rng.integers(0, 300, size=500), rng.random(500) < 0.4)
        assert_counters_equal(
            tier1_counters(indexed_spec, trace),
            stream_tier1_counters(indexed_spec, trace, chunk=99)[0])


class TestWallClockAndFaults:
    @pytest.fixture(scope="class")
    def fault_spec(self):
        return SimSpec(
            traffic=TrafficSpec(kind="irm", n_requests=1500, n_pages=256,
                                zipf_s=1.2, rate=500.0, seed=5),
            store=StoreConfig(n_lines=32),
            n_shards=4, window_dt=0.25,
            faults=FaultSpec(events=(shard_down(1, 0.9, 1.7),)),
        )

    def test_fault_event_straddles_chunks(self, fault_spec):
        # chunk=250 at 500 req/s ~ 0.5 s of arrivals per chunk: the outage
        # window [0.9, 1.7) opens and closes mid-chunk, and wall-clock
        # window edges (0.25 s) never align with chunk edges.
        ref = report_from_counters(fault_spec, tier1_counters(fault_spec))
        for chunk in (250, 499):
            assert simulate_stream(fault_spec, chunk=chunk).to_dict() \
                == ref.to_dict()

    def test_no_donation_path_matches(self, fault_spec):
        ref = tier1_counters(fault_spec)
        assert_counters_equal(
            ref, stream_tier1_counters(fault_spec, chunk=300,
                                       donate=False)[0])


class TestCheckpointResume:
    def test_resume_bit_exact(self, indexed_spec, indexed_ref):
        ctr_p, _, ck = stream_tier1_counters(indexed_spec, chunk=150,
                                             max_requests=487)
        assert not ck.done and ck.offset == 487
        # Partial counters are exact for the consumed prefix.
        assert int(np.asarray(ctr_p.requests).sum()) == 487
        ctr, _, ck2 = stream_tier1_counters(indexed_spec, chunk=321,
                                            checkpoint=ck)
        assert ck2.done
        assert_counters_equal(indexed_ref, ctr)

    def test_partial_report_and_fluid_q0(self):
        spec = SimSpec(
            traffic=TrafficSpec(kind="irm", n_requests=1000, n_pages=256,
                                rate=400.0, seed=2),
            store=StoreConfig(n_lines=32), n_shards=2, window_dt=0.5,
        )
        rep, ck = simulate_stream(spec, chunk=256, max_requests=600)
        assert rep.requests == 600 and not ck.done
        assert ck.fluid_q0 is not None and len(ck.fluid_q0) == 2
        rep_full = simulate_stream(spec, chunk=200, checkpoint=ck)
        assert rep_full.to_dict() == simulate_stream(spec).to_dict()

    def test_resume_rejects_other_spec(self, indexed_spec):
        _, _, ck = stream_tier1_counters(indexed_spec, chunk=200,
                                         max_requests=200)
        other = indexed_spec.replace(**{"store.n_lines": 16})
        with pytest.raises(ValueError, match="cache_signature"):
            stream_tier1_counters(other, checkpoint=ck)


class TestTenantMix:
    @pytest.fixture(scope="class")
    def mix(self):
        return tenant_mix(
            TenantSpec(name="oltp", rate=300.0, n_pages=128, zipf_s=1.3,
                       write_fraction=0.4),
            TenantSpec(name="scan", rate=100.0, n_pages=384, zipf_s=0.9,
                       seed=1),
            n_requests=1600, seed=7)

    def test_generator_chunk_invariant(self, mix):
        full = tenant_mix_stream(mix)
        for chunks in ((1600,), (1, 1599), (7, 700, 893), (512,) * 4):
            gen = TenantStream(mix)
            parts = [gen.take(c) for c in chunks]
            for i in range(4):
                np.testing.assert_array_equal(
                    np.concatenate([p[i] for p in parts]), full[i])

    def test_generator_state_restore(self, mix):
        gen = TenantStream(mix)
        gen.take(700)
        snap = gen.state()
        tail = gen.take(900)
        gen2 = TenantStream(mix)
        gen2.restore(snap)
        for a, b in zip(tail, gen2.take(900)):
            np.testing.assert_array_equal(a, b)

    def test_attribution_reconciles(self, mix):
        spec = SimSpec(traffic=mix, store=StoreConfig(n_lines=64,
                                                      policy="ws"),
                       n_shards=4, window_dt=0.5)
        ref = tier1_counters(spec)  # one-shot drain of the same merge
        ctr, tc, _ = stream_tier1_counters(spec, chunk=300)
        assert_counters_equal(ref, ctr)
        assert tc.names == ("oltp", "scan")
        np.testing.assert_array_equal(
            tc.win_requests.sum(axis=0),
            np.asarray(ctr.win_requests).sum(axis=0))
        np.testing.assert_array_equal(
            tc.win_misses.sum(axis=0),
            np.asarray(ctr.win_misses).sum(axis=0))
        assert int(tc.win_requests.sum()) == mix.n_requests

    def test_simulate_delegates_with_tenant_reports(self, mix):
        spec = SimSpec(traffic=mix, store=StoreConfig(n_lines=64),
                       n_shards=2, window_dt=0.5)
        rep = simulate(spec)
        assert [t.name for t in rep.tenants] == ["oltp", "scan"]
        assert sum(t.requests for t in rep.tenants) == rep.requests
        assert sum(t.misses for t in rep.tenants) == rep.misses
        for t in rep.tenants:
            assert t.response_s.shape == (rep.n_windows,)
            assert t.mean_response_s >= 0.0
        d = rep.to_dict()
        assert len(d["tenants"]) == 2
        assert d["tenants"][0]["name"] == "oltp"

    def test_sweep_routes_tenant_mix(self, mix):
        spec = SimSpec(traffic=mix, store=StoreConfig(n_lines=32),
                       n_shards=2, window_dt=0.5)
        res = sweep(spec, {"lam": [50.0, 100.0]})
        assert all(len(r.tenants) == 2 for r in res.reports)
        off = sweep(spec, {"lam": [50.0, 100.0]}, stream="off")
        assert all(r.tenants == () for r in off.reports)
        for a, b in zip(res.reports, off.reports):
            assert (a.requests, a.misses) == (b.requests, b.misses)

    def test_mrc_fence(self, mix):
        # policy="lru" so the MRC pass is otherwise eligible: the reason
        # reported must be the tenant_mix streaming fence itself.
        spec = SimSpec(traffic=mix,
                       store=StoreConfig(n_lines=32, policy="lru"),
                       n_shards=2, window_dt=0.5)
        assert "tenant_mix" in mrc_unsupported_reason(spec)

    def test_spec_validation(self):
        with pytest.raises(ValueError, match="unique"):
            tenant_mix(TenantSpec(name="a", rate=1.0, n_pages=4),
                       TenantSpec(name="a", rate=1.0, n_pages=4),
                       n_requests=10)
        with pytest.raises(ValueError, match="rate"):
            TenantSpec(name="a", rate=0.0, n_pages=4)
        with pytest.raises(ValueError, match="tenant_mix"):
            TrafficSpec(kind="irm", n_requests=10, n_pages=8,
                        tenants=(TenantSpec(name="a", rate=1.0,
                                            n_pages=8),))


class TestCompileCount:
    def test_at_most_two_buckets(self):
        # Fresh structural config -> cold jit cache for this engine.
        spec = SimSpec(
            traffic=TrafficSpec(kind="irm", n_requests=4000, n_pages=512,
                                zipf_s=1.1, seed=17),
            store=StoreConfig(n_lines=48), n_shards=4, n_windows=3,
        )
        reset_stream_compile_count()
        stream_tier1_counters(spec, chunk=250)  # 16 chunks
        assert stream_compile_count() <= 2
        # More chunkings with the same chunk size: no further compiles.
        stream_tier1_counters(spec, chunk=250, max_requests=999)
        assert stream_compile_count() <= 2


def _old_two_bucket_cap(hot, chunk, n_shards):
    """The rule the ladder replaced: the primary bucket, else the
    fallback."""
    fallback = 1 << max(chunk - 1, 0).bit_length()
    primary = min(1 << (max(MIN_CAP, -(-2 * chunk // n_shards)) - 1)
                  .bit_length(), fallback)
    return primary if hot <= primary else fallback


def _skewed_trace(n, hot_of_20, *, n_pages=512, n_shards=8, seed=0):
    """Block-mapped trace whose first shard takes ``hot_of_20`` of every
    20 requests outright and its share of the rest, spread uniformly over
    all pages."""
    rng = np.random.default_rng(seed)
    per_shard = n_pages // n_shards
    hot = np.arange(n) % 20 < hot_of_20
    pages = np.where(hot, rng.integers(0, per_shard, n),
                     rng.integers(0, n_pages, n)).astype(np.int32)
    pages[-1] = n_pages - 1       # the trace's page space is all n_pages
    return pages, rng.random(n) < 0.3


class TestBucketLadder:
    # 8 shards, chunk 2,048: primary 512, rungs 512 / 1,024 / 2,048.
    CHUNK, N_SHARDS = 2048, 8

    @pytest.mark.parametrize(
        "policy,n_lines,hot_of_20,n,caps",
        [
            ("lru", 40, 0, 4 * 2048, [512] * 4),            # balanced
            ("lru", 44, 7, 4 * 2048, [1024] * 4),           # skewed
            ("lru", 52, 7, 3 * 2048 + 600, [1024] * 3 + [512]),   # tail
            ("lru", 56, 20, 2 * 2048 + 1500, [2048] * 3),   # one shard
            ("ws", 12, 7, 3 * 2048 + 600, [1024] * 3 + [512]),    # learner
        ],
        ids=["balanced", "skewed", "tail", "one_shard", "learner"])
    def test_chunk_takes_its_hot_shards_rung(self, policy, n_lines,
                                             hot_of_20, n, caps):
        trace = _skewed_trace(n, hot_of_20)
        spec = SimSpec(
            traffic=TrafficSpec(kind="irm", n_requests=n, n_pages=512,
                                seed=1),
            store=StoreConfig(n_lines=n_lines, policy=policy),
            n_shards=self.N_SHARDS, n_windows=5,
        )
        prof: dict = {}
        reset_stream_compile_count()
        ctr, _, ck = stream_tier1_counters(spec, trace, chunk=self.CHUNK,
                                           profile=prof)
        # A fresh n_lines per case: every rung compiles once, from cold.
        assert stream_compile_count() == len(set(caps))
        assert ck.done and prof["stream_chunks"] == len(caps)
        assert prof["stream_scan_steps"] == sum(caps) * self.N_SHARDS
        for cap, start in zip(caps, range(0, n, self.CHUNK)):
            hot = max(np.bincount(
                trace[0][start:start + self.CHUNK] // 64, minlength=8))
            assert cap <= _old_two_bucket_cap(hot, self.CHUNK,
                                              self.N_SHARDS)
        # Pads are masked no-ops at any bucket: counters and weights are
        # bit-equal to the one-shot scan and to another chunking's rungs.
        assert_counters_equal(tier1_counters(spec, trace), ctr)
        assert_counters_equal(
            stream_tier1_counters(spec, trace, chunk=2 * self.CHUNK)[0], ctr)
        if policy == "ws":
            assert int(np.sum(ctr.evictions)) > 0

    @pytest.mark.parametrize("chunk,n_shards",
                             [(1 << 18, 16), (2048, 8), (250, 4), (1000, 3),
                              (1, 1)])
    def test_no_cap_above_the_two_bucket_rule(self, chunk, n_shards):
        # Balanced, skewed and one-shard-owns-all hot counts, and every
        # power-of-two boundary between.
        hots = {1, -(-chunk // n_shards), chunk}
        hots |= {min(chunk, 1 << k) + d for k in range(chunk.bit_length())
                 for d in (0, 1)}
        for hot in sorted(h for h in hots if 1 <= h <= chunk):
            cap = _chunk_cap(hot, chunk, n_shards)
            assert hot <= cap <= _old_two_bucket_cap(hot, chunk, n_shards)
            assert cap & (cap - 1) == 0


def test_timestamp_binning_is_float64():
    # Long-horizon arrivals: f32 cannot represent 2^24 + 0.5-spaced times,
    # so f32 binning would collapse neighbouring bins. The host-side f64
    # path must keep them distinct.
    t0 = float(2 ** 24)
    times = t0 + 0.5 * np.arange(8)
    n_windows = 2 ** 26
    ids = timestamp_window_ids(times, n_windows, 0.5)
    np.testing.assert_array_equal(
        ids.astype(np.int64), (times / 0.5).astype(np.int64))
    assert len(set(ids.tolist())) == 8  # f32 would merge pairs


if HAVE_HYPOTHESIS:

    _PROP_SPEC = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=150, n_pages=64,
                            zipf_s=1.1, write_fraction=0.3, seed=23),
        store=StoreConfig(n_lines=16, policy="ws"),
        n_shards=2, n_windows=4,
    )
    _PROP_REF = None

    @given(chunk=st.integers(1, 160))
    @settings(max_examples=20, deadline=None)
    def test_streamed_equals_one_shot_fuzz(chunk):
        global _PROP_REF
        if _PROP_REF is None:
            _PROP_REF = tier1_counters(_PROP_SPEC)
        ctr, _, _ = stream_tier1_counters(_PROP_SPEC, chunk=chunk)
        assert_counters_equal(_PROP_REF, ctr)

    @given(split=st.integers(1, 149), chunk=st.integers(1, 80))
    @settings(max_examples=15, deadline=None)
    def test_resume_equals_one_shot_fuzz(split, chunk):
        global _PROP_REF
        if _PROP_REF is None:
            _PROP_REF = tier1_counters(_PROP_SPEC)
        _, _, ck = stream_tier1_counters(_PROP_SPEC, chunk=chunk,
                                         max_requests=split)
        ctr, _, _ = stream_tier1_counters(_PROP_SPEC, chunk=chunk,
                                          checkpoint=ck)
        assert_counters_equal(_PROP_REF, ctr)
