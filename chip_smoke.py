"""Bring-up smoke run of the simulator's main path on a TPU.

  python chip_smoke.py [--seed N]     # phases A, B, C on one chip
  python chip_smoke.py --chips 4      # phase B sharded over four chips
                                      # against the same grid on one chip

One process, no child processes; it needs a TPU and fails without one.

- **Phase A** replays the paper's workload2 (8,000,000 reads over 32,768
  pages, IRM traffic from ``--seed``) through ``simulate_stream`` on 16
  shards of 2,048 lines under LRU, with 32 timed windows. The chunked
  replay runs the XLA engine (its only implementation).
- **Phase B** runs the 288-point x 32-window faulted traced-knob grid of
  ``benchmarks/bench_engine.py`` through ``sweep()`` on the Pallas cache-scan
  kernel (``engine="pallas"``), scaled to the largest stream and cache the
  kernel's rule admits, and again on the default engine (XLA's
  ``cache_scan_ref``). It checks both sweeps' counters and expert weights
  against the scan engine on the same chip, and their batched reports
  against ``report="scalar"``.
- **Phase C** builds a 64-size LRU miss-rate curve of phase A's workload with
  the Pallas reuse-distance kernel; its counters at 2,048 lines must equal
  phase A's, per shard and per window.

Every time printed names the device it ran on; compile time (JAX's trace,
lowering and backend-compile events) is reported apart. The last line of
standard output is one JSON object with the device. A failed check lets the
remaining phases run, then the run exits 1 without that line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, os.path.join(ROOT, "benchmarks"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

# Phase A/C: workload2 (benchmarks/paper_tables.py) at the paper's smallest
# process count, one 2,048-line tier-1 cache per process.
WL2_REQUESTS, WL2_PAGES, WL2_SHARDS, WL2_LINES = 8_000_000, 32_768, 16, 2048
N_WINDOWS = 32
MRC_SIZES = tuple(64 * i for i in range(1, 65))   # 64 sizes, 2,048 among them
# Phase B: bench_engine's grid with stream, page space, rate and cache all
# scaled by PHASE_B_SCALE. x4 puts the busiest shard in the 8,192-request
# bucket with 256 lines (a 2M-element noise table); x8 would need 16,384 x
# 512, past the kernel's NOISE_TABLE_MAX.
PHASE_B_SCALE = 4
REPORT_TOL = 1e-10   # batched vs scalar reports (tests/test_report_batch.py)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class Clock:
    """Wall time of a phase with JAX's compile time split out. Traces nest
    (a jit traced inside another's trace reports its own span), so compile
    time is the union of the reported spans, not their sum."""

    spans: list = []

    @classmethod
    def listen(cls):
        def on_event(event, duration, **_):
            if event in _COMPILE_EVENTS:
                end = time.perf_counter()
                cls.spans.append((end - duration, end))
        jax.monitoring.register_event_duration_secs_listener(on_event)

    def __enter__(self):
        self.n0, self.t0 = len(Clock.spans), time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.wall = time.perf_counter() - self.t0
        self.compile, reach = 0.0, self.t0
        for start, end in sorted(Clock.spans[self.n0:]):
            start = max(start, reach)
            if end > start:
                self.compile += end - start
                reach = end
        self.warm = self.wall - self.compile


def say(msg: str) -> None:
    print(msg, flush=True)


FAILED: list[str] = []


def check(ok: bool, what: str) -> None:
    """Print a check's outcome. A failed check lets the remaining phases run
    (one chip call shows every failure), and the run then exits 1."""
    say(f"  check {'ok' if ok else 'FAILED'}: {what}")
    if not ok:
        FAILED.append(what)


def workload2_spec(seed: int):
    from repro.sim import SimSpec
    from repro.sim.spec import StoreConfig
    from repro.core.traffic import TrafficSpec
    base = SimSpec(
        traffic=TrafficSpec(kind="irm", n_requests=WL2_REQUESTS,
                            n_pages=WL2_PAGES, seed=seed),
        store=StoreConfig(n_lines=WL2_LINES, policy="lru"),
        n_shards=WL2_SHARDS,
    )
    # 32 windows over the nominal horizon of the aggregate arrival rate.
    horizon = WL2_REQUESTS / base.agg_rate()
    return base.replace(n_windows=N_WINDOWS, window_dt=horizon / N_WINDOWS)


def phase_b_grid(seed: int):
    from bench_engine import FAULTS, FULL_AXES, base_spec
    k = PHASE_B_SCALE
    b = base_spec(N_WINDOWS, FAULTS)
    base = b.replace(**{
        "traffic.n_requests": b.traffic.n_requests * k,
        "traffic.n_pages": b.traffic.n_pages * k,
        "traffic.rate": b.traffic.rate * k,
        "traffic.seed": b.traffic.seed + seed,
        "store.n_lines": b.store.n_lines * k,
    })
    return base, FULL_AXES


def phase_a(seed: int, kind: str):
    from repro.kernels.backend import (engine_path_counts,
                                       reset_engine_path_counts)
    from repro.sim import simulate_stream
    from repro.storage.tiered_store import (reset_stream_compile_count,
                                            stream_compile_count)
    spec = workload2_spec(seed)
    say(f"phase A: workload2 replay, {WL2_REQUESTS} reads over {WL2_PAGES} "
        f"pages, irm seed {seed}, {WL2_SHARDS} shards x {WL2_LINES} lines, "
        f"lru, {N_WINDOWS} timed windows of {spec.window_dt:.3f} s")
    reset_engine_path_counts()
    reset_stream_compile_count()
    prof: dict = {}
    with Clock() as c:
        rep = simulate_stream(spec, profile=prof)
    n_chunks = prof["stream_chunks"]
    paths = engine_path_counts()["cache_scan"]
    say(f"  [{kind}] wall {c.wall:.3f} s, compile {c.compile:.3f} s, "
        f"warm {c.warm:.3f} s -> {WL2_REQUESTS / c.warm:.1f} requests/s "
        "(warm; stream generation included)")
    say(f"  [{kind}] {n_chunks} chunks, {stream_compile_count()} chunk "
        f"compiles; per chunk: host {prof['stream_chunk_host'] / n_chunks:.4f}"
        f" s, dispatch {prof['stream_chunk_dispatch'] / n_chunks:.4f} s, "
        f"device wait {prof['stream_chunk_wait'] / n_chunks:.4f} s")
    say(f"  engine paths: {paths} (the chunked replay runs the XLA engine; "
        "moving it to Pallas is ROADMAP S4)")
    check(rep.requests == WL2_REQUESTS, "every request replayed")
    check(stream_compile_count() <= 2, "chunk engine compiled at most twice")
    check(paths == {"pallas": 0, "xla": n_chunks * WL2_SHARDS},
          "chunk rows ran the XLA engine")
    return spec, rep, {"wall_s": c.wall, "compile_s": c.compile,
                       "requests_per_s": WL2_REQUESTS / c.warm,
                       "chunks": n_chunks, "profile": prof}


def _report_diff(a, b) -> float:
    """Largest absolute difference of the transient series and response of
    two reports; inf where finiteness or onsets disagree."""
    worst = abs(a.response_s - b.response_s)
    for name in ("q1", "q2", "w1", "w2", "response", "rho1", "rho2"):
        xa = np.asarray(getattr(a.transient, name), float)
        xb = np.asarray(getattr(b.transient, name), float)
        fa, fb = np.isfinite(xa), np.isfinite(xb)
        if not np.array_equal(fa, fb):
            return float("inf")
        if fa.any():
            worst = max(worst, float(np.max(np.abs(xa[fa] - xb[fb]))))
    onsets = [(r.saturation_onset, r.metastable_onset) for r in (a, b)]
    shard_onsets = [[(s.saturation_onset, s.metastable_onset)
                     for s in r.shards] for r in (a, b)]
    if onsets[0] != onsets[1] or shard_onsets[0] != shard_onsets[1]:
        return float("inf")
    return worst


_COUNTERS = ("requests", "hits", "misses", "prefetch_hits", "tier2_reads",
             "tier2_writes", "evictions")
_WIN_COUNTERS = ("requests", "hits", "misses", "prefetch_hits",
                 "tier2_reads", "tier2_writes", "evictions", "expert_use")


def _counters_equal(a, b) -> bool:
    return (all(getattr(a, f) == getattr(b, f) for f in _COUNTERS)
            and all(np.array_equal(getattr(a.windows, f),
                                   getattr(b.windows, f))
                    for f in _WIN_COUNTERS))


def _max_ulp(a, b) -> int:
    a = np.asarray(a, np.float32).reshape(-1)
    b = np.asarray(b, np.float32).reshape(-1)
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib))) if a.size else 0


def _show_weight_diffs(reps, refs, points, limit: int = 8) -> None:
    """Print the first ``limit`` (point, shard, window) cells whose expert
    weights differ, with both values and the window's request count."""
    shown = 0
    for i, (a, b) in enumerate(zip(reps, refs)):
        wa = np.asarray(a.windows.weights, np.float32)
        wb = np.asarray(b.windows.weights, np.float32)
        req = np.asarray(a.windows.requests)
        for s, w in sorted({(s, w) for s, w, _ in np.argwhere(wa != wb)}):
            if shown == limit:
                return
            shown += 1
            say(f"    point {i} {points[i]} shard {s} window {w} "
                f"({req[s, w]} requests): pallas {wa[s, w].tolist()} "
                f"xla {wb[s, w].tolist()}")


def _run_sweep(base, axes, devices, kind, label, **kw):
    from repro.kernels.backend import (engine_path_counts,
                                       reset_engine_path_counts)
    from repro.sim import sweep
    reset_engine_path_counts()
    with Clock() as c:
        res = sweep(base, axes, devices=devices, profile=True, **kw)
    p = res.profile
    say(f"  {label} [{kind} x{len(devices)}]: wall {c.wall:.3f} s, compile "
        f"{c.compile:.3f} s, warm {c.warm:.3f} s -> "
        f"{len(res.reports) / c.warm:.2f} points/s; stages: stream_gen "
        f"{p['stream_gen']:.3f} s, engine "
        f"{p['engine_dispatch_submit'] + p['engine_dispatch_wait']:.3f} s "
        f"(submit {p['engine_dispatch_submit']:.3f} s, wait "
        f"{p['engine_dispatch_wait']:.3f} s), report_solve "
        f"{p['report_solve']:.3f} s, assembly {p['assembly']:.3f} s")
    paths = engine_path_counts().get("cache_scan", {})
    say(f"  {label} engine paths: {paths}")
    return res, paths, {"wall_s": c.wall, "compile_s": c.compile,
                        "profile": p, "paths": paths}


def _compare_to_reference(label, res, ref, n_points, max_ulp):
    """Counters equal, weights within ``max_ulp``, reports within
    REPORT_TOL of the reference sweep's; returns (largest ulp, largest
    report difference)."""
    same = [_counters_equal(a, b) for a, b in zip(res.reports, ref.reports)]
    check(all(same), f"{label}: integer counters equal the scan engine's "
          f"({sum(same)}/{n_points} points)")
    ulps = [_max_ulp(a.windows.weights, b.windows.weights)
            for a, b in zip(res.reports, ref.reports)]
    ulp = max(ulps)
    say(f"  {label}: expert weights differ from the scan engine's by at "
        f"most {ulp} ulp (f32); {sum(u > 0 for u in ulps)}/{n_points} "
        "points differ")
    _show_weight_diffs(res.reports, ref.reports, points=res.points)
    check(ulp <= max_ulp, f"{label}: expert weights within {max_ulp} ulp "
          "of the scan engine's")
    diff = max(_report_diff(a, b) for a, b in zip(res.reports, ref.reports))
    say(f"  {label}: reports, largest |batched - scalar| = {diff:.3e}")
    check(diff <= REPORT_TOL, f"{label}: batched reports within "
          f"{REPORT_TOL} of report='scalar'")
    return ulp, diff


def phase_b(seed: int, kind: str, one):
    from repro.core.queuing import (fluid_compile_count, fluid_solve_device,
                                    reset_fluid_compile_count)
    from repro.kernels.cache_scan import KERNEL_WEIGHT_ULP, kernel_fits
    from repro.sim.sweep import _bucket_cap
    base, axes = phase_b_grid(seed)
    n_points = int(np.prod([len(v) for v in axes.values()]))
    rows = n_points * base.n_shards
    say(f"phase B: {n_points}-point x {N_WINDOWS}-window faulted grid, "
        f"bench_engine sizes x{PHASE_B_SCALE}: {base.traffic.n_requests} "
        f"requests over {base.traffic.n_pages} pages, {base.n_shards} "
        f"shards x {base.store.n_lines} lines")
    reset_fluid_compile_count()
    res, paths, rec = _run_sweep(base, axes, one, kind,
                                 "pallas sweep (engine=pallas)",
                                 engine="pallas")
    fluid_dev = fluid_solve_device()
    busiest = max(max(s.requests for s in r.shards) for r in res.reports)
    bucket = _bucket_cap(busiest)
    say(f"  busiest shard {busiest} requests -> bucket {bucket}; "
        f"kernel_fits({bucket}, {base.store.n_lines}) = "
        f"{kernel_fits(bucket, base.store.n_lines)}")
    say(f"  fluid compiles {fluid_compile_count()}, batched fluid solve "
        f"ran on {fluid_dev.platform}:{fluid_dev.device_kind}")
    check(paths == {"pallas": rows, "xla": 0},
          f"all {rows} stream rows ran the Pallas cache-scan kernel")
    dflt, dflt_paths, dflt_rec = _run_sweep(
        base, axes, one, kind, "default sweep (engine=fused)")
    check(dflt_paths == {"pallas": 0, "xla": rows},
          "the default engine ran XLA's cache_scan_ref")
    ref, ref_paths, ref_rec = _run_sweep(
        base, axes, one, kind, "reference (engine=scan, report=scalar)",
        engine="scan", report="scalar")
    check(ref_paths == {"pallas": 0, "xla": rows},
          "the reference ran the XLA scan engine")
    # The kernel's weights may differ by its measured bound; the default
    # engine is XLA like the reference, so its weights must be equal.
    ulp, diff = _compare_to_reference("pallas", res, ref, n_points,
                                      KERNEL_WEIGHT_ULP)
    dflt_ulp, dflt_diff = _compare_to_reference("default", dflt, ref,
                                                n_points, 0)
    return res, {"pallas": rec, "default": dflt_rec, "reference": ref_rec,
                 "weights_ulp": max(ulp, dflt_ulp),
                 "report_diff": max(diff, dflt_diff),
                 "fluid_device": str(fluid_dev)}


def phase_c(seed: int, kind: str, spec, rep_a):
    from repro.kernels.backend import (engine_path_counts,
                                       reset_engine_path_counts)
    from repro.sim import mrc_tier1_counters
    say(f"phase C: {len(MRC_SIZES)}-size LRU curve ({MRC_SIZES[0]}.."
        f"{MRC_SIZES[-1]} lines per shard) of phase A's workload")
    reset_engine_path_counts()
    with Clock() as c:
        ctrs = mrc_tier1_counters(spec, MRC_SIZES)
    paths = engine_path_counts()["reuse_distance"]
    say(f"  [{kind}] wall {c.wall:.3f} s, compile {c.compile:.3f} s, warm "
        f"{c.warm:.3f} s (stream generation and host histogram included)")
    say(f"  engine paths: {paths}")
    check(paths == {"pallas": 1, "xla": 0},
          "the distance pass ran the Pallas reuse-distance kernel")
    m = ctrs[WL2_LINES]
    w = rep_a.windows
    pairs = {"requests": m.win_requests, "hits": m.win_hits,
             "misses": m.win_misses, "tier2_reads": m.win_tier2_reads,
             "evictions": m.win_evictions, "expert_use": m.win_expert_use}
    bad = [f for f, v in pairs.items()
           if not np.array_equal(np.asarray(getattr(w, f)), v)]
    check(not bad, f"MRC counters at {WL2_LINES} lines equal phase A's per "
          f"shard and window (mismatched: {bad or 'none'})")
    curve = [float(ctrs[s].misses.sum() / ctrs[s].requests.sum())
             for s in MRC_SIZES]
    say(f"  miss rate {curve[0]:.4f} at {MRC_SIZES[0]} lines, "
        f"{curve[31]:.4f} at {MRC_SIZES[31]}, {curve[-1]:.4f} at "
        f"{MRC_SIZES[-1]}")
    return {"wall_s": c.wall, "compile_s": c.compile, "miss_rate": curve}


def chips4(seed: int, kind: str, devs):
    base, axes = phase_b_grid(seed)
    say(f"--chips 4: phase B's grid sharded over {len(devs)} chips against "
        "one chip")
    rows = int(np.prod([len(v) for v in axes.values()])) * base.n_shards
    four, paths4, rec4 = _run_sweep(base, axes, devs, kind, "pallas sweep",
                                    engine="pallas")
    one, paths1, rec1 = _run_sweep(base, axes, devs[:1], kind, "pallas sweep",
                                   engine="pallas")
    check(paths4 == paths1 == {"pallas": rows, "xla": 0},
          f"both sweeps ran all {rows} stream rows on the Pallas kernel")
    pairs = list(zip(four.reports, one.reports))
    counters = sum(_counters_equal(a, b) for a, b in pairs)
    ulp = max(_max_ulp(a.windows.weights, b.windows.weights) for a, b in pairs)
    diff = max(_report_diff(a, b) for a, b in pairs)
    say(f"  four vs one chip: counters equal at {counters}/{len(pairs)} "
        f"points, weights differ by at most {ulp} ulp, reports by at most "
        f"{diff:.3e}")
    _show_weight_diffs(four.reports, one.reports, points=four.points)
    check(counters == len(pairs) and ulp == 0 and diff == 0.0,
          "four-chip results equal one-chip results")
    return {"four": rec4, "one": rec1, "weights_ulp": ulp,
            "report_diff": diff}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    args = ap.parse_args()

    devs = jax.devices()
    d0 = devs[0]
    say(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"device_count={len(devs)}")
    if d0.platform != "tpu":
        say("chip_smoke: no TPU found; this run needs one")
        return 1
    if len(devs) < args.chips:
        say(f"chip_smoke: --chips {args.chips} needs {args.chips} devices")
        return 1
    from repro.launch.compat import use_compile_cache
    say(f"compile cache: {use_compile_cache(ROOT)}")
    Clock.listen()
    kind = d0.device_kind
    used = devs[:args.chips]
    summary: dict = {"device_kind": kind, "chips": args.chips,
                     "seed": args.seed}
    with jax.default_device(d0):
        if args.chips == 4:
            summary["chips4"] = chips4(args.seed, kind, used)
        else:
            spec, rep_a, summary["phase_a"] = phase_a(args.seed, kind)
            _, summary["phase_b"] = phase_b(args.seed, kind, used)
            summary["phase_c"] = phase_c(args.seed, kind, spec, rep_a)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    summary["failed"] = FAILED
    with open(os.path.join(out, f"chip_smoke_x{args.chips}.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if FAILED:
        say(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": kind, "count": len(used)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
