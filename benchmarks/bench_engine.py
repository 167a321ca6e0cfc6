"""Fused cache-scan engine benchmark: exactness, compile count, speedup.

  PYTHONPATH=src python benchmarks/bench_engine.py [--smoke]

Measures the fused tier-1 request-loop engine (``engine="fused"`` —
``repro.kernels.cache_scan.fused_cache_scan``: the whole request loop with
cache state, recency metadata and online-learning expert weights carried
through one fused scan, windowed counters folded in a dense post-pass)
against the original per-step ``lax.scan`` engine it replaces, and writes a
``BENCH_engine.json`` artifact at the repo root.

Gates:

- **equivalence** — the fused engine is *bit-exact* against the scan engine
  on every counter: one-shot streams over policy × prefetch, sharded
  scenarios over every mapping policy, a faulted wall-clock-binned timeline
  (failover remap + retry storm + degraded tier-2), and a chunk-streamed
  multi-tenant replay including per-tenant attribution. Zero tolerance —
  any differing field fails the gate.
- **interpret parity** — the Pallas ``cache_scan_kernel`` in interpret mode
  reproduces the pure-jax oracle ``cache_scan_ref`` bit for bit over a
  policy × prefetch sample (the compiled TPU path shares the same body).
- **compile gate** — a 288-point traced-knob sweep (alpha × beta ×
  threshold × policy) × 32 windows over the faulted workload traces the
  fused engine at most :data:`COMPILE_LIMIT` times
  (``cache_scan_compile_count()``): the megabatch dispatch traces once per
  structural shape, and traced hyperparameters ride as operands.
- **speedup** (full mode only) — ≥ :data:`MIN_SPEEDUP`x engine-stage
  points/sec over the scan engine on the same 288-point × 32-window grid
  (the engine stage of ``sweep(profile=True)``: the sum of its
  :data:`ENGINE_STAGES` spans, warm jit caches; each engine runs at its
  best unroll).

``--smoke`` runs reduced grids for CI (equivalence + interpret parity +
compile gates only).
"""
from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core.traffic import TenantSpec, TrafficSpec  # noqa: E402
from repro.kernels.cache_scan import (  # noqa: E402
    cache_scan_compile_count,
    cache_scan_kernel,
    cache_scan_noise,
    reset_cache_scan_compile_count,
)
from repro.kernels.ref import cache_scan_ref  # noqa: E402
from repro.sim import (  # noqa: E402
    FaultSpec,
    RetryPolicy,
    SimSpec,
    device_degrade,
    shard_down,
    sweep,
    tier1_counters,
)
from repro.sim.spec import StoreConfig  # noqa: E402
from repro.sim.stream import stream_tier1_counters  # noqa: E402
from repro.storage.tiered_store import (  # noqa: E402
    _init_accum,
    init_store,
    run_stream,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(ROOT, "BENCH_engine.json")
COMPILE_LIMIT = 2   # megabatch dispatch trace + at most one length bucket
MIN_SPEEDUP = 3.0   # engine-stage points/sec, fused vs scan
# The engine stage's spans in SweepResult.profile.
ENGINE_STAGES = ("route_stream", "route_mrc", "engine_dispatch_submit",
                 "engine_dispatch_wait")

N_WINDOWS = 32
WINDOW_DT = 0.3
# Engine-side knobs only (all traced operands): 4 x 4 x 6 x 3 = 288 points.
FULL_AXES = {
    "store.alpha": tuple(np.linspace(0.2, 0.8, 4)),
    "store.beta": tuple(np.linspace(0.4, 0.9, 4)),
    "store.threshold": tuple(np.linspace(0.05, 0.45, 6)),
    "store.policy": ("ws", "lru", "lfu"),
}
SMOKE_AXES = {
    "store.alpha": (0.3, 0.6),
    "store.beta": (0.5, 0.8),
    "store.policy": ("ws", "lru"),
}

FAULTS = FaultSpec(
    events=(shard_down(1, 0.8, 2.4),
            device_degrade(2, 0.4, 1.5, 4.0)),
    retry=RetryPolicy(timeout=0.05, max_retries=2, backoff_init=0.4),
)


def base_spec(n_windows: int, faults) -> SimSpec:
    return SimSpec(
        traffic=TrafficSpec(kind="poisson", n_requests=2000, n_pages=512,
                            rate=240.0, seed=11),
        store=StoreConfig(n_lines=64),
        n_shards=4,
        n_windows=n_windows,
        window_dt=WINDOW_DT,
        faults=faults,
    )


def _diff_fields(a, b, skip=()) -> list[str]:
    """Names of fields on which two counter trees disagree (bit-exact)."""
    bad = []
    for f in a._fields:
        if f in skip:
            continue
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            bad.append(f)
    return bad


def bench_equivalence(smoke: bool) -> dict:
    n = 800 if smoke else 2000
    mismatches: list[str] = []
    cases = 0

    # One-shot streams: policy x prefetch.
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.integers(0, 400, n), jnp.int32)
    writes = jnp.asarray(rng.random(n) < 0.3)
    win = jnp.asarray(np.minimum(np.arange(n) // (n // 8), 7), jnp.int32)
    policies = ("ws", "lru") if smoke else ("ws", "lru", "lfu", "random")
    for policy in policies:
        for prefetch in (False, True):
            cfg = StoreConfig(n_lines=48, policy=policy, prefetch=prefetch)
            fused = run_stream(cfg, pages, writes, window_ids=win,
                               n_windows=8, seed=5, engine="fused")
            scan = run_stream(cfg, pages, writes, window_ids=win,
                              n_windows=8, seed=5, engine="scan")
            cases += 1
            mismatches += [f"stream/{policy}/pf={prefetch}:{f}"
                           for f in _diff_fields(fused, scan)]

    # Sharded scenarios: every mapping policy.
    mappings = ("block",) if smoke else ("block", "round_robin", "random",
                                         "block_cyclic")
    for mapping in mappings:
        spec = SimSpec(
            traffic=TrafficSpec(kind="irm", n_requests=n, n_pages=400,
                                rate=200.0, seed=3),
            store=StoreConfig(n_lines=32, policy="ws"),
            n_shards=3, n_windows=6, mapping=mapping,
        )
        cases += 1
        mismatches += [f"mapping/{mapping}:{f}"
                       for f in _diff_fields(
                           tier1_counters(spec, engine="fused"),
                           tier1_counters(spec, engine="scan"),
                           skip=("tenants",))]

    # Faulted wall-clock timeline.
    spec = base_spec(8 if smoke else 16, FAULTS).replace(
        **{"traffic.n_requests": n})
    cases += 1
    mismatches += [f"faulted:{f}"
                   for f in _diff_fields(
                       tier1_counters(spec, engine="fused"),
                       tier1_counters(spec, engine="scan"),
                       skip=("tenants",))]

    # Chunk-streamed multi-tenant replay, incl. per-tenant attribution.
    spec = SimSpec(
        traffic=TrafficSpec(
            kind="tenant_mix", n_requests=n, n_pages=600, rate=300.0, seed=5,
            tenants=(TenantSpec("a", 180.0, 400, write_fraction=0.2),
                     TenantSpec("b", 120.0, 200, zipf_s=1.3, seed=9)),
        ),
        n_shards=2, n_windows=8,
    )
    ca, ta, _ = stream_tier1_counters(spec, chunk=256, engine="fused")
    cb, tb, _ = stream_tier1_counters(spec, chunk=256, engine="scan")
    cases += 1
    mismatches += [f"tenant:{f}"
                   for f in _diff_fields(ca, cb, skip=("tenants",))]
    mismatches += [f"tenant-attr:{f}" for f in _diff_fields(ta, tb)]

    return {
        "cases": cases,
        "mismatched_fields": mismatches,
        "ok": not mismatches,
    }


def bench_interpret_parity(smoke: bool) -> dict:
    L, N, W = (256, 32, 8) if smoke else (512, 32, 8)
    combos = [("ws", False), ("ws", True)] if smoke else [
        ("ws", False), ("lru", False), ("lfu", True), ("random", True)]
    rng = np.random.default_rng(1)
    pages = jnp.asarray(rng.integers(0, 200, L), jnp.int32)
    writes = jnp.asarray((rng.random(L) < 0.3).astype(np.int32))
    win = jnp.asarray(np.minimum(np.arange(L) // (L // W), W - 1), jnp.int32)
    mismatches = []
    for policy, prefetch in combos:
        cfg = StoreConfig(n_lines=N, policy=policy, prefetch=prefetch)
        hyper = cfg.hyper()
        st0 = init_store(cfg, 9)
        noise = cache_scan_noise(st0.key, L, N)
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
            interpret=True)
        for f in acc._fields:
            x = np.asarray(getattr(acc, f))
            if not np.array_equal(np.asarray(out[f][0]).reshape(x.shape), x):
                mismatches.append(f"{policy}/pf={prefetch}:{f}")
        if not np.array_equal(np.asarray(out["final_weights"][0]),
                              np.asarray(final.ols.weights)):
            mismatches.append(f"{policy}/pf={prefetch}:final_weights")
    return {
        "combos": len(combos),
        "mismatched_fields": mismatches,
        "ok": not mismatches,
    }


def bench_compile_gate(smoke: bool) -> dict:
    axes = SMOKE_AXES if smoke else FULL_AXES
    n_windows = 6 if smoke else N_WINDOWS
    # n_lines distinct from the equivalence workloads so this sweep counts
    # its own traces rather than inheriting a warm engine cache.
    base = base_spec(n_windows, FAULTS).replace(**{"store.n_lines": 80})
    n_points = int(np.prod([len(v) for v in axes.values()]))
    reset_cache_scan_compile_count()
    res = sweep(base, axes, engine="fused", unroll=1, profile=True)
    compiles = cache_scan_compile_count()
    assert len(res.reports) == n_points
    return {
        "n_points": n_points,
        "n_windows": n_windows,
        "compiles": compiles,
        "limit": COMPILE_LIMIT,
        "profile": {k: round(v, 4) if isinstance(v, float) else v
                    for k, v in res.profile.items()},
        "ok": compiles <= COMPILE_LIMIT,
    }


def bench_speedup(smoke: bool) -> dict:
    if smoke:
        return {"skipped": True, "ok": True}
    base = base_spec(N_WINDOWS, FAULTS).replace(**{"store.n_lines": 80})
    n_points = int(np.prod([len(v) for v in FULL_AXES.values()]))

    def engine_time(engine: str, unroll: int) -> float:
        sweep(base, FULL_AXES, engine=engine, unroll=unroll)  # warm
        res = sweep(base, FULL_AXES, engine=engine, unroll=unroll,
                    profile=True)
        return sum(res.profile[k] for k in ENGINE_STAGES)

    # Each engine at its best unroll on this grid: the per-step scan
    # amortises loop overhead with unroll=4; the fused engine's single
    # pass gains nothing from unrolling.
    t_scan = engine_time("scan", unroll=4)
    t_fused = engine_time("fused", unroll=1)
    speedup = t_scan / t_fused if t_fused > 0 else float("inf")
    return {
        "n_points": n_points,
        "n_windows": N_WINDOWS,
        "fused_s": round(t_fused, 4),
        "scan_s": round(t_scan, 4),
        "fused_points_per_sec": round(n_points / t_fused, 1),
        "scan_points_per_sec": round(n_points / t_scan, 1),
        "speedup": round(speedup, 2),
        "min_speedup": MIN_SPEEDUP,
        "ok": speedup >= MIN_SPEEDUP,
    }


def main() -> None:
    smoke = "--smoke" in sys.argv
    artifact = {
        "mode": "smoke" if smoke else "full",
        "devices": jax.local_device_count(),
        "equivalence": bench_equivalence(smoke),
        "interpret_parity": bench_interpret_parity(smoke),
        "compile_gate": bench_compile_gate(smoke),
        "speedup": bench_speedup(smoke),
    }
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")

    eq, ip, cg, sp = (artifact["equivalence"], artifact["interpret_parity"],
                      artifact["compile_gate"], artifact["speedup"])
    print(f"equivalence: {eq['cases']} cases, "
          f"{len(eq['mismatched_fields'])} mismatched fields ok={eq['ok']}")
    print(f"interpret parity: {ip['combos']} combos, "
          f"{len(ip['mismatched_fields'])} mismatched fields ok={ip['ok']}")
    print(f"compile gate: {cg['n_points']} points x {cg['n_windows']} "
          f"windows -> {cg['compiles']} engine traces "
          f"(limit {COMPILE_LIMIT}) ok={cg['ok']}")
    if sp.get("skipped"):
        print("speedup: skipped (--smoke)")
    else:
        print(f"speedup: fused {sp['fused_points_per_sec']} pts/s vs "
              f"scan {sp['scan_points_per_sec']} pts/s -> "
              f"{sp['speedup']}x (min {MIN_SPEEDUP}) ok={sp['ok']}")
    print(f"artifact: {ARTIFACT}")
    failures = [k for k in ("equivalence", "interpret_parity",
                            "compile_gate", "speedup")
                if not artifact[k]["ok"]]
    if failures:
        raise SystemExit(f"bench_engine gates failed: {failures}")


if __name__ == "__main__":
    main()
