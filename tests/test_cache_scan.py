"""Tier-1 engine exactness harness.

Two rings of defense around the tier-1 request loop
(`repro.kernels.ref.cache_scan_ref`, wired in by
`repro.storage.tiered_store.run_stream`, the sweep's megabatch and the
chunked replay):

1. **Engine parity** — the engine is bit-identical to the per-step
   ``lax.scan`` reference (``run_stream_reference``; over a distributed
   workload, ``engine_reference.reference_tier1_counters``) over policy ×
   mapping × prefetch grids, on windowed, wall-clock-binned, faulted and
   chunk-streamed workloads, including per-tenant attribution and rows too
   long for the hoisted Random-expert table — every counter, not a
   statistical comparison.
2. **Invariance fences** — padding/bucketing choices change no windowed
   counter (pads scatter to the dropped id), and sweep results are
   identical with buffer donation on and off (the undonated path must stay
   available).

Property-based fuzzing (hypothesis) deepens ring 1 when the library is
installed; the fixed-seed tests always run.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from engine_reference import reference_tenant_counters, reference_tier1_counters
from repro.kernels.ref import NOISE_TABLE_MAX
from repro.sim.engine import batched_reports, tier1_counters
from repro.sim.spec import (
    FaultSpec,
    RetryPolicy,
    SimSpec,
    StoreConfig,
    TrafficSpec,
    device_degrade,
    shard_down,
)
from repro.sim.stream import stream_tier1_counters
from repro.sim.sweep import (
    engine_compile_count,
    expand_grid,
    reset_engine_compile_count,
    sweep,
)
from repro.core.traffic import TenantSpec
from repro.storage.tiered_store import run_stream, run_stream_reference

# ---------------------------------------------------------------------------
# helpers


def _assert_trees_equal(a, b, ctx="", skip=()):
    for f in a._fields:
        if f in skip:
            continue
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        np.testing.assert_array_equal(x, y, err_msg=f"{ctx} field={f}")


_REPORT_COUNTERS = ("requests", "hits", "misses", "prefetch_hits",
                    "tier2_reads", "tier2_writes", "evictions")


def _assert_reports_equal(a, b, ctx="", skip=()):
    """Integer counters + windowed telemetry of two SimReports, bit-exact."""
    for f in _REPORT_COUNTERS:
        assert getattr(a, f) == getattr(b, f), f"{ctx} field={f}"
    for f in a.windows._fields:
        if f in skip:
            continue
        np.testing.assert_array_equal(
            np.asarray(getattr(a.windows, f)),
            np.asarray(getattr(b.windows, f)),
            err_msg=f"{ctx} windows.{f}")


def _stream(seed, n=1200, n_pages=300, wf=0.3):
    rng = np.random.default_rng(seed)
    pages = jnp.asarray(rng.integers(0, n_pages, n), jnp.int32)
    writes = jnp.asarray(rng.random(n) < wf)
    return pages, writes


# ---------------------------------------------------------------------------
# ring 1: the engine vs the per-step scan reference


@pytest.mark.parametrize("policy", ["ws", "lru", "lfu", "random"])
@pytest.mark.parametrize("prefetch", [False, True])
def test_run_stream_fused_matches_scan(policy, prefetch):
    pages, writes = _stream(0)
    win = jnp.asarray(np.minimum(np.arange(1200) // 150, 7), jnp.int32)
    cfg = StoreConfig(n_lines=48, policy=policy, prefetch=prefetch)
    fused = run_stream(cfg, pages, writes, window_ids=win, n_windows=8,
                       seed=5)
    scan = run_stream_reference(cfg, pages, writes, window_ids=win,
                                n_windows=8, seed=5)
    _assert_trees_equal(fused, scan, ctx=f"{policy}/pf={prefetch}")


@pytest.mark.parametrize("policy", ["ws", "random"])
def test_run_stream_past_noise_table_matches_scan(policy):
    """A row whose Random-expert table would exceed NOISE_TABLE_MAX draws
    in the loop instead: the same draws, so the same counters."""
    n, n_lines = 65_537, 64
    assert n * n_lines > NOISE_TABLE_MAX
    pages, writes = _stream(2, n=n, n_pages=300)
    cfg = StoreConfig(n_lines=n_lines, policy=policy)
    fused = run_stream(cfg, pages, writes, n_windows=4, seed=3)
    scan = run_stream_reference(cfg, pages, writes, n_windows=4, seed=3)
    assert int(fused.evictions) > 0
    _assert_trees_equal(fused, scan, ctx=f"past-table/{policy}")


@pytest.mark.parametrize("mapping", ["block", "round_robin", "random",
                                     "block_cyclic"])
def test_engine_fused_matches_scan_across_mappings(mapping):
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=1500, n_pages=400,
                            rate=200.0, seed=3),
        store=StoreConfig(n_lines=32, policy="ws"),
        n_shards=3, n_windows=6, mapping=mapping,
    )
    _assert_trees_equal(tier1_counters(spec),
                        reference_tier1_counters(spec), ctx=mapping)


def test_engine_fused_matches_scan_faulted_timed():
    """Wall-clock windows + failover remap + retry storm: the fault
    schedule rides the engine as data, so the engine must reproduce the
    reference bit for bit on the degraded timeline too."""
    spec = SimSpec(
        traffic=TrafficSpec(kind="poisson", n_requests=1500, n_pages=400,
                            rate=200.0, seed=7),
        store=StoreConfig(n_lines=32, policy="ws"),
        n_shards=4, n_windows=16, window_dt=0.5,
        faults=FaultSpec(
            events=(shard_down(1, 0.8, 2.4),
                    device_degrade(2, 0.4, 1.5, 4.0)),
            retry=RetryPolicy(timeout=0.05, max_retries=2, backoff_init=0.4),
        ),
    )
    _assert_trees_equal(tier1_counters(spec),
                        reference_tier1_counters(spec), ctx="faulted")


@pytest.mark.parametrize("policy", ["ws", "lru", "lfu", "random"])
@pytest.mark.parametrize("prefetch", [False, True])
def test_chunked_fused_matches_one_shot_scan(policy, prefetch):
    """The masked, resumable chunk engine (in-loop Random draws, carried
    key) against the one-shot reference over the whole stream."""
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=3000, n_pages=400,
                            rate=200.0, seed=3),
        store=StoreConfig(n_lines=48, policy=policy, prefetch=prefetch),
        n_shards=4, n_windows=8,
    )
    chunked, _, _ = stream_tier1_counters(spec, chunk=128)
    one_shot = reference_tier1_counters(spec)
    assert int(one_shot.evictions.sum()) > 0
    _assert_trees_equal(chunked, one_shot, ctx=f"chunked/{policy}/{prefetch}")


def test_chunk_size_invariance_fused():
    spec = SimSpec(
        traffic=TrafficSpec(kind="markov", n_requests=2000, n_pages=300,
                            rate=150.0, seed=9),
        n_shards=2, n_windows=5,
    )
    a, _, _ = stream_tier1_counters(spec, chunk=100)
    b, _, _ = stream_tier1_counters(spec, chunk=512)
    _assert_trees_equal(a, b, ctx="chunk-size", skip=("tenants",))


def test_tenant_mix_chunked_fused_matches_scan():
    spec = SimSpec(
        traffic=TrafficSpec(
            kind="tenant_mix", n_requests=2000, n_pages=600, rate=300.0,
            seed=5,
            tenants=(TenantSpec("a", 180.0, 400, write_fraction=0.2),
                     TenantSpec("b", 120.0, 200, zipf_s=1.3, seed=9)),
        ),
        n_shards=2, n_windows=8,
    )
    ca, ta, _ = stream_tier1_counters(spec, chunk=256)
    _assert_trees_equal(ca, reference_tier1_counters(spec), ctx="tenant")
    _assert_trees_equal(ta, reference_tenant_counters(spec),
                        ctx="tenant-attribution")


def test_sweep_fused_matches_scan():
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=800, n_pages=256,
                            rate=150.0, seed=2),
        store=StoreConfig(n_lines=24),
        n_shards=2, n_windows=4,
    )
    axes = {"store.alpha": (0.3, 0.7), "store.policy": ("ws", "lfu")}
    fused = sweep(base, axes)
    specs = [base.replace(**pt) for pt in expand_grid(axes)]
    scan = batched_reports(
        [(spec, reference_tier1_counters(spec), None) for spec in specs])
    assert len(fused.reports) == len(scan) == 4
    for a, b in zip(fused.reports, scan):
        _assert_reports_equal(a, b, ctx="sweep")


# ---------------------------------------------------------------------------
# ring 2: invariance fences


def test_padding_does_not_leak_into_windows():
    """Bucket-style padding (edge-repeat pages, window id == n_windows)
    must leave every windowed counter untouched and add only pure hits to
    the whole-stream totals — the invariant the megabatch buckets and the
    chunk engine's masked tail both rely on."""
    pages, writes = _stream(4, n=600)
    win = jnp.asarray(np.minimum(np.arange(600) // 100, 5), jnp.int32)
    cfg = StoreConfig(n_lines=32, policy="ws")
    base = run_stream(cfg, pages, writes, window_ids=win, n_windows=6,
                      seed=3)
    for n_pad in (1, 37, 256):
        pad_pages = jnp.concatenate(
            [pages, jnp.full((n_pad,), pages[-1], jnp.int32)])
        pad_writes = jnp.concatenate(
            [writes, jnp.zeros((n_pad,), writes.dtype)])
        pad_win = jnp.concatenate(
            [win, jnp.full((n_pad,), 6, jnp.int32)])
        padded = run_stream(cfg, pad_pages, pad_writes, window_ids=pad_win,
                            n_windows=6, seed=3)
        for f in base._fields:
            x, y = np.asarray(getattr(base, f)), np.asarray(getattr(padded, f))
            if f == "requests":
                assert y - x == n_pad
            elif f == "hits":
                assert y - x == n_pad, "pads must be pure hits"
            else:
                np.testing.assert_array_equal(
                    y, x, err_msg=f"n_pad={n_pad} field={f}")


def test_sweep_donation_off_matches_on():
    """The undonated dispatch path must stay available and bit-identical
    — the donation is a pure buffer-lifetime optimization."""
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=600, n_pages=200,
                            rate=120.0, seed=8),
        store=StoreConfig(n_lines=16),
        n_shards=2, n_windows=4,
    )
    axes = {"store.policy": ("ws", "lru"), "store.beta": (0.5, 0.8)}
    donated = sweep(base, axes, donate=True)
    plain = sweep(base, axes, donate=False)
    assert len(donated.reports) == len(plain.reports) == 4
    for a, b in zip(donated.reports, plain.reports):
        _assert_reports_equal(a, b, ctx="donate")


def test_stream_donation_off_matches_on():
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=1000, n_pages=200,
                            rate=150.0, seed=6),
        n_shards=2, n_windows=4,
    )
    a, _, _ = stream_tier1_counters(spec, chunk=256, donate=True)
    b, _, _ = stream_tier1_counters(spec, chunk=256, donate=False)
    _assert_trees_equal(a, b, ctx="stream-donate", skip=("tenants",))


def test_sweep_profile_splits_engine_stage():
    """The engine stage reports its parts (the routed paths, submit and
    wait) as disjoint spans inside the whole call, and the chunked path
    reports per-chunk phases."""
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=400, n_pages=128,
                            rate=100.0, seed=5),
        store=StoreConfig(n_lines=16),
        n_shards=2, n_windows=4,
    )
    res = sweep(base, {"store.alpha": (0.4, 0.6)}, profile=True)
    prof = res.profile
    engine_parts = ("route_stream", "route_mrc", "engine_dispatch_submit",
                    "engine_dispatch_wait")
    assert set(engine_parts) <= set(prof)
    assert "engine_dispatch" not in prof
    parts = sum(prof[k] for k in engine_parts)
    assert prof["engine_dispatch_submit"] > 0
    assert prof["engine_dispatch_wait"] > 0
    # The stage spans do not overlap and all lie inside the whole call.
    stages = parts + sum(prof[k] for k in ("stream_gen", "report_solve",
                                            "assembly"))
    assert 0 < stages <= prof["total"]

    spec = SimSpec(traffic=TrafficSpec(kind="irm", n_requests=600,
                                       n_pages=128, rate=100.0, seed=5),
                   n_shards=2, n_windows=4)
    chunk_prof = {}
    stream_tier1_counters(spec, chunk=128, profile=chunk_prof)
    assert {"stream_chunk_host", "stream_chunk_dispatch",
            "stream_chunk_wait", "stream_chunks"} <= set(chunk_prof)
    assert chunk_prof["stream_chunks"] >= 4


def test_compile_count_small_traced_grid():
    """A traced-knob grid (alpha x policy) over one structural config must
    compile the megabatch engine at most twice (one length bucket + at most
    one extra), and a repeat of the grid not at all."""
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=500, n_pages=160,
                            rate=120.0, seed=12),
        store=StoreConfig(n_lines=20),  # distinct shape => own compile
        n_shards=2, n_windows=4,
    )
    axes = {"store.alpha": (0.3, 0.5, 0.7),
            "store.policy": ("ws", "lru", "lfu")}
    reset_engine_compile_count()
    sweep(base, axes)
    cold = engine_compile_count()
    assert cold <= 2
    sweep(base, axes)
    assert engine_compile_count() == cold


# ---------------------------------------------------------------------------
# property-based fuzz (optional dependency)

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:            # pragma: no cover - optional dependency
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        seed=st.integers(0, 2**16),
        n=st.integers(1, 200),
        n_pages=st.integers(1, 64),
        n_lines=st.integers(1, 24),
        policy=st.sampled_from(["ws", "lru", "lfu", "random"]),
        prefetch=st.booleans(),
    )
    def test_fuzz_fused_matches_scan(seed, n, n_pages, n_lines, policy,
                                     prefetch):
        rng = np.random.default_rng(seed)
        pages = jnp.asarray(rng.integers(0, n_pages, n), jnp.int32)
        writes = jnp.asarray(rng.random(n) < 0.4)
        win = jnp.asarray(rng.integers(0, 4, n), jnp.int32)
        cfg = StoreConfig(n_lines=n_lines, policy=policy, prefetch=prefetch)
        fused = run_stream(cfg, pages, writes, window_ids=win, n_windows=4,
                           seed=seed % 7)
        scan = run_stream_reference(cfg, pages, writes, window_ids=win,
                                    n_windows=4, seed=seed % 7)
        _assert_trees_equal(fused, scan, ctx=f"fuzz-{seed}")
