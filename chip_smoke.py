"""Bring-up smoke run of the simulator's main path on a TPU.

  python chip_smoke.py [--seed N]     # phases A and C on one chip

One process, no child processes; it needs a TPU and fails without one.
The sweep runs on one and on four chips, checked against a plain
reference, are the benchmark's ``olgrid_p4`` cells (``bench/run.py``).

- **Phase A** replays the paper's workload2 (8,000,000 reads over 32,768
  pages, IRM traffic from ``--seed``) through ``simulate_stream`` on 16
  shards of 2,048 lines under LRU, with 32 timed windows.
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

import numpy as np  # noqa: E402

import jax  # noqa: E402

# Phase A/C: workload2 (benchmarks/paper_tables.py) at the paper's smallest
# process count, one 2,048-line tier-1 cache per process.
WL2_REQUESTS, WL2_PAGES, WL2_SHARDS, WL2_LINES = 8_000_000, 32_768, 16, 2048
N_WINDOWS = 32
MRC_SIZES = tuple(64 * i for i in range(1, 65))   # 64 sizes, 2,048 among them
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


def phase_a(seed: int, kind: str):
    from repro.sim import simulate_stream
    from repro.storage.tiered_store import (reset_stream_compile_count,
                                            stream_compile_count)
    spec = workload2_spec(seed)
    say(f"phase A: workload2 replay, {WL2_REQUESTS} reads over {WL2_PAGES} "
        f"pages, irm seed {seed}, {WL2_SHARDS} shards x {WL2_LINES} lines, "
        f"lru, {N_WINDOWS} timed windows of {spec.window_dt:.3f} s")
    reset_stream_compile_count()
    prof: dict = {}
    with Clock() as c:
        rep = simulate_stream(spec, profile=prof)
    n_chunks = prof["stream_chunks"]
    say(f"  [{kind}] wall {c.wall:.3f} s, compile {c.compile:.3f} s, "
        f"warm {c.warm:.3f} s -> {WL2_REQUESTS / c.warm:.1f} requests/s "
        "(warm; stream generation included)")
    say(f"  [{kind}] {n_chunks} chunks, {stream_compile_count()} chunk "
        f"compiles; per chunk: host {prof['stream_chunk_host'] / n_chunks:.4f}"
        f" s, dispatch {prof['stream_chunk_dispatch'] / n_chunks:.4f} s, "
        f"device wait {prof['stream_chunk_wait'] / n_chunks:.4f} s")
    check(rep.requests == WL2_REQUESTS, "every request replayed")
    check(stream_compile_count() <= 2, "chunk engine compiled at most twice")
    return spec, rep, {"wall_s": c.wall, "compile_s": c.compile,
                       "requests_per_s": WL2_REQUESTS / c.warm,
                       "chunks": n_chunks, "profile": prof}


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


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = jax.devices()
    d0 = devs[0]
    say(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"device_count={len(devs)}")
    if d0.platform != "tpu":
        say("chip_smoke: no TPU found; this run needs one")
        return 1
    from repro.launch.compat import use_compile_cache
    say(f"compile cache: {use_compile_cache(ROOT)}")
    Clock.listen()
    kind = d0.device_kind
    summary: dict = {"device_kind": kind, "seed": args.seed}
    with jax.default_device(d0):
        spec, rep_a, summary["phase_a"] = phase_a(args.seed, kind)
        summary["phase_c"] = phase_c(args.seed, kind, spec, rep_a)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    summary["failed"] = FAILED
    with open(os.path.join(out, "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1, default=str)
    if FAILED:
        say(f"chip_smoke: {len(FAILED)} check(s) failed: {FAILED}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": kind, "count": 1}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
