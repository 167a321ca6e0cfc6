"""Fused cache-scan engine exactness harness.

Three rings of defense around the fused tier-1 request loop
(`repro.kernels.cache_scan.fused_cache_scan` and its megabatch/chunked
wiring):

1. **Engine parity** — the fused engine is bit-identical to the original
   per-step ``lax.scan`` reference (``engine="scan"``) over policy ×
   mapping × prefetch grids, on windowed, wall-clock-binned, faulted and
   chunk-streamed workloads, including per-tenant attribution — every
   counter, not a statistical comparison.
2. **Kernel goldens** — the Pallas ``cache_scan_kernel`` (interpret mode
   everywhere; compiled mode under the ``kernels`` marker where a real
   accelerator backend exists) against the pure-jax oracle
   ``cache_scan_ref``, the default engine on every platform.
3. **Invariance fences** — padding/bucketing choices change no windowed
   counter (pads scatter to the dropped id), sweep results are identical
   with buffer donation on and off (the undonated path must stay
   available), and unknown engine names fail fast.

Property-based fuzzing (hypothesis) deepens ring 1 when the library is
installed; the fixed-seed tests always run.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.backend import engine_path_counts, reset_engine_path_counts
from repro.kernels.cache_scan import (
    KERNEL_WEIGHT_ULP,
    _chunk_rows,
    cache_scan_compile_count,
    cache_scan_kernel,
    cache_scan_noise,
    reset_cache_scan_compile_count,
)
from repro.kernels.ref import cache_scan_ref
from repro.sim.engine import tier1_counters
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
from repro.sim.sweep import sweep
from repro.core.traffic import TenantSpec
from repro.storage.tiered_store import init_store, run_stream, _init_accum

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
# ring 1: fused engine vs the per-step scan reference


@pytest.mark.parametrize("policy", ["ws", "lru", "lfu", "random"])
@pytest.mark.parametrize("prefetch", [False, True])
def test_run_stream_fused_matches_scan(policy, prefetch):
    pages, writes = _stream(0)
    win = jnp.asarray(np.minimum(np.arange(1200) // 150, 7), jnp.int32)
    cfg = StoreConfig(n_lines=48, policy=policy, prefetch=prefetch)
    fused = run_stream(cfg, pages, writes, window_ids=win, n_windows=8,
                       seed=5, engine="fused")
    scan = run_stream(cfg, pages, writes, window_ids=win, n_windows=8,
                      seed=5, engine="scan")
    _assert_trees_equal(fused, scan, ctx=f"{policy}/pf={prefetch}")


@pytest.mark.parametrize("mapping", ["block", "round_robin", "random",
                                     "block_cyclic"])
def test_engine_fused_matches_scan_across_mappings(mapping):
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=1500, n_pages=400,
                            rate=200.0, seed=3),
        store=StoreConfig(n_lines=32, policy="ws"),
        n_shards=3, n_windows=6, mapping=mapping,
    )
    _assert_trees_equal(tier1_counters(spec, engine="fused"),
                        tier1_counters(spec, engine="scan"), ctx=mapping)


def test_engine_fused_matches_scan_faulted_timed():
    """Wall-clock windows + failover remap + retry storm: the fault
    schedule rides the engine as data, so the fused path must reproduce
    the scan bit for bit on the degraded timeline too."""
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
    _assert_trees_equal(tier1_counters(spec, engine="fused"),
                        tier1_counters(spec, engine="scan"), ctx="faulted")


def test_chunked_fused_matches_one_shot_scan():
    spec = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=3000, n_pages=400,
                            rate=200.0, seed=3),
        n_shards=4, n_windows=8,
    )
    chunked, _, _ = stream_tier1_counters(spec, chunk=128, engine="fused")
    one_shot = tier1_counters(spec, engine="scan")
    _assert_trees_equal(chunked, one_shot, ctx="chunked",
                        skip=("final_weights", "tenants"))


def test_chunk_size_invariance_fused():
    spec = SimSpec(
        traffic=TrafficSpec(kind="markov", n_requests=2000, n_pages=300,
                            rate=150.0, seed=9),
        n_shards=2, n_windows=5,
    )
    a, _, _ = stream_tier1_counters(spec, chunk=100, engine="fused")
    b, _, _ = stream_tier1_counters(spec, chunk=512, engine="fused")
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
    ca, ta, _ = stream_tier1_counters(spec, chunk=256, engine="fused")
    cb, tb, _ = stream_tier1_counters(spec, chunk=256, engine="scan")
    _assert_trees_equal(ca, cb, ctx="tenant", skip=("tenants",))
    _assert_trees_equal(ta, tb, ctx="tenant-attribution")


def test_sweep_fused_matches_scan():
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=800, n_pages=256,
                            rate=150.0, seed=2),
        store=StoreConfig(n_lines=24),
        n_shards=2, n_windows=4,
    )
    axes = {"store.alpha": (0.3, 0.7), "store.policy": ("ws", "lfu")}
    fused = sweep(base, axes, engine="fused")
    scan = sweep(base, axes, engine="scan")
    assert len(fused.reports) == len(scan.reports) == 4
    for a, b in zip(fused.reports, scan.reports):
        _assert_reports_equal(a, b, ctx="sweep")


def test_sweep_pallas_matches_scan():
    """``engine="pallas"`` runs the kernel on a TPU and the XLA engine
    elsewhere — the path counters say which. Counters equal the scan
    engine's; the weights too, but for the kernel's measured
    :data:`KERNEL_WEIGHT_ULP` on a TPU."""
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=800, n_pages=256,
                            rate=150.0, seed=3),
        store=StoreConfig(n_lines=24),
        n_shards=2, n_windows=4,
    )
    axes = {"store.alpha": (0.3, 0.7), "store.policy": ("ws", "lru")}
    reset_engine_path_counts()
    pallas = sweep(base, axes, engine="pallas")
    on_tpu = jax.default_backend() == "tpu"
    assert engine_path_counts()["cache_scan"][
        "pallas" if on_tpu else "xla"] == 4 * 2
    scan = sweep(base, axes, engine="scan")
    for a, b in zip(pallas.reports, scan.reports):
        _assert_reports_equal(a, b, ctx="sweep-pallas", skip=("weights",))
        np.testing.assert_array_max_ulp(
            np.asarray(a.windows.weights, np.float32),
            np.asarray(b.windows.weights, np.float32),
            maxulp=KERNEL_WEIGHT_ULP if on_tpu else 0)


# ---------------------------------------------------------------------------
# ring 2: Pallas kernel goldens


def _kernel_case(policy, prefetch, seed=1, L=512, N=32, W=8):
    rng = np.random.default_rng(seed)
    pages = jnp.asarray(rng.integers(0, 200, L), jnp.int32)
    writes = jnp.asarray((rng.random(L) < 0.3).astype(np.int32))
    win = jnp.asarray(np.minimum(np.arange(L) // (L // W), W - 1), jnp.int32)
    cfg = StoreConfig(n_lines=N, policy=policy, prefetch=prefetch)
    hyper = cfg.hyper()
    st0 = init_store(cfg, 9)
    noise = cache_scan_noise(st0.key, L, N)
    return cfg, hyper, st0, noise, pages, writes, win, W


def _kernel_vs_ref(policy, prefetch, interpret, L=512):
    cfg, hyper, st0, noise, pages, writes, win, W = _kernel_case(
        policy, prefetch, L=L)
    final, acc = cache_scan_ref(
        st0, _init_accum(W), pages, writes, win, hyper, noise,
        epoch_width=cfg.epoch_width, pred_cap=cfg.pred_cap,
        prefetch=cfg.prefetch, prefetch_width=cfg.prefetch_width,
        n_windows=W)
    out = cache_scan_kernel(
        pages[None], writes[None], win[None], noise,
        hyper.alpha, hyper.beta, hyper.threshold, hyper.policy_idx,
        n_lines=cfg.n_lines, epoch_width=cfg.epoch_width,
        pred_cap=cfg.pred_cap, prefetch=cfg.prefetch,
        prefetch_width=cfg.prefetch_width,
        prefetch_buf=st0.pf.ptags.shape[-1], n_windows=W,
        interpret=interpret)
    for f in acc._fields:
        x = np.asarray(getattr(acc, f))
        y = np.asarray(out[f][0]).reshape(x.shape)
        np.testing.assert_array_equal(
            y, x, err_msg=f"{policy}/pf={prefetch} field={f}")
    np.testing.assert_array_equal(np.asarray(out["final_weights"][0]),
                                  np.asarray(final.ols.weights))


@pytest.mark.parametrize("policy,prefetch",
                         [("ws", False), ("lru", False), ("lfu", False),
                          ("random", False), ("ws", True), ("random", True)])
def test_pallas_interpret_matches_ref(policy, prefetch):
    """Golden: interpret-mode Pallas kernel == pure-jax oracle, bit for
    bit — counters, windowed telemetry and final expert weights."""
    _kernel_vs_ref(policy, prefetch, interpret=True)


def test_pallas_interpret_multi_chunk_matches_ref():
    """A row longer than one chunk runs over several grid steps: the state,
    counters and window weights carried across them match the oracle."""
    assert _chunk_rows(2500, 32) < 2500
    _kernel_vs_ref("ws", False, interpret=True, L=2500)


def test_pallas_interpret_batched_rows_independent():
    """Rows of one grid launch must not bleed VMEM scratch state into each
    other: a [2, L] batch equals two independent single-row launches."""
    cfg, hyper, st0, noise, pages, writes, win, W = _kernel_case("ws", False)
    pages2 = jnp.stack([pages, pages[::-1]])
    writes2 = jnp.stack([writes, writes[::-1]])
    win2 = jnp.stack([win, win])
    both = cache_scan_kernel(
        pages2, writes2, win2, noise,
        hyper.alpha, hyper.beta, hyper.threshold, hyper.policy_idx,
        n_lines=cfg.n_lines, epoch_width=cfg.epoch_width,
        pred_cap=cfg.pred_cap, prefetch=False,
        prefetch_width=cfg.prefetch_width,
        prefetch_buf=st0.pf.ptags.shape[-1], n_windows=W, interpret=True)
    for r in range(2):
        solo = cache_scan_kernel(
            pages2[r:r + 1], writes2[r:r + 1], win2[r:r + 1], noise,
            hyper.alpha, hyper.beta, hyper.threshold, hyper.policy_idx,
            n_lines=cfg.n_lines, epoch_width=cfg.epoch_width,
            pred_cap=cfg.pred_cap, prefetch=False,
            prefetch_width=cfg.prefetch_width,
            prefetch_buf=st0.pf.ptags.shape[-1], n_windows=W,
            interpret=True)
        for f in both:
            np.testing.assert_array_equal(
                np.asarray(both[f][r]), np.asarray(solo[f][0]),
                err_msg=f"row={r} field={f}")


@pytest.mark.kernels
def test_pallas_compiled_matches_ref():
    """Compiled-mode golden — only meaningful on an accelerator backend
    (deselect with ``-m 'not kernels'``; auto-skips on CPU, where
    non-interpret Pallas does not lower)."""
    if jax.default_backend() == "cpu":
        pytest.skip("no accelerator backend: compiled Pallas needs TPU/GPU")
    _kernel_vs_ref("ws", False, interpret=False)
    _kernel_vs_ref("lru", True, interpret=False)
    _kernel_vs_ref("ws", False, interpret=False, L=5000)


# ---------------------------------------------------------------------------
# ring 3: invariance fences


def test_padding_does_not_leak_into_windows():
    """Bucket-style padding (edge-repeat pages, window id == n_windows)
    must leave every windowed counter untouched and add only pure hits to
    the whole-stream totals — the invariant the megabatch buckets and the
    chunk engine's masked tail both rely on."""
    pages, writes = _stream(4, n=600)
    win = jnp.asarray(np.minimum(np.arange(600) // 100, 5), jnp.int32)
    cfg = StoreConfig(n_lines=32, policy="ws")
    base = run_stream(cfg, pages, writes, window_ids=win, n_windows=6,
                      seed=3, engine="fused")
    for n_pad in (1, 37, 256):
        pad_pages = jnp.concatenate(
            [pages, jnp.full((n_pad,), pages[-1], jnp.int32)])
        pad_writes = jnp.concatenate(
            [writes, jnp.zeros((n_pad,), writes.dtype)])
        pad_win = jnp.concatenate(
            [win, jnp.full((n_pad,), 6, jnp.int32)])
        padded = run_stream(cfg, pad_pages, pad_writes, window_ids=pad_win,
                            n_windows=6, seed=3, engine="fused")
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


def test_unknown_engine_rejected():
    pages, writes = _stream(1, n=64)
    with pytest.raises(ValueError, match="unknown engine"):
        run_stream(StoreConfig(n_lines=8), pages, writes, engine="bogus")
    spec = SimSpec(traffic=TrafficSpec(kind="irm", n_requests=64,
                                       n_pages=32, rate=50.0, seed=1),
                   n_shards=1)
    with pytest.raises(ValueError, match="unknown engine"):
        stream_tier1_counters(spec, engine="bogus")


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
    trace the fused engine at most twice (one-shot megabatch + at most one
    extra length bucket)."""
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=500, n_pages=160,
                            rate=120.0, seed=12),
        store=StoreConfig(n_lines=20),  # distinct shape => own compile
        n_shards=2, n_windows=4,
    )
    axes = {"store.alpha": (0.3, 0.5, 0.7),
            "store.policy": ("ws", "lru", "lfu")}
    sweep(base, axes)  # warm the jit/engine caches
    reset_cache_scan_compile_count()
    sweep(base, axes)
    assert cache_scan_compile_count() <= 2


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
                           seed=seed % 7, engine="fused")
        scan = run_stream(cfg, pages, writes, window_ids=win, n_windows=4,
                          seed=seed % 7, engine="scan")
        _assert_trees_equal(fused, scan, ctx=f"fuzz-{seed}")
