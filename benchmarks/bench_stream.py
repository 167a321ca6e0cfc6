"""Chunked streaming replay benchmark: the four PR gates, in one artifact.

  PYTHONPATH=src python benchmarks/bench_stream.py [--smoke]

Produces repo-root ``BENCH_stream.json`` with:

- ``bit_exact``: streamed counters and full ``SimReport`` vs the one-shot
  engine — request-index windows, wall-clock windows with a fault
  schedule straddling chunk boundaries, and a ``tenant_mix`` workload
  whose per-tenant series must reconcile with the pooled windows.
- ``compile_count``: a fresh >= 32-chunk replay of a >= 1M-request stream
  (smoke: scaled down) must compile the chunk engine at most twice: at
  four shards the bucket ladder has two rungs, the primary and the
  fallback (``repro.sim.stream._chunk_cap``).
- ``memory``: peak live device bytes sampled across replays of two
  streams 8x apart in length must stay flat (the whole point of
  streaming: footprint is O(chunk), not O(trace)).
- ``throughput``: the optimized replay (balanced-load bucket sizing +
  donated buffers + async dispatch) vs a naive chunked baseline that
  pads every shard to the worst case (the whole chunk on one shard), runs
  without donation and synchronizes + round-trips the carry to host after
  every chunk. Gate: >= 2x requests/second.

``--smoke`` shrinks every stream so the whole file runs in CI seconds;
gates keep their structure (the compile-count and flatness assertions are
scale-free).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

import jax  # noqa: E402

from repro.core.traffic import (  # noqa: E402
    TenantSpec,
    TrafficSpec,
    tenant_mix,
)
from repro.sim import SimSpec, simulate_stream, stream_tier1_counters  # noqa: E402
from repro.sim.engine import report_from_counters, tier1_counters  # noqa: E402
from repro.sim.spec import FaultSpec, StoreConfig, shard_down  # noqa: E402
from repro.sim.stream import _chunk_cap  # noqa: E402
from repro.storage.tiered_store import (  # noqa: E402
    init_stream_carry,
    partition_streams,
    reset_stream_compile_count,
    stream_chunk_engine,
    stream_compile_count,
    stream_window_ids,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ARTIFACT = os.path.join(ROOT, "BENCH_stream.json")

COMPILE_LIMIT = 2       # chunk-engine compiles per replay (rungs at 4 shards)
MEM_FLAT_RATIO = 1.25   # peak(8x stream) / peak(1x stream) must stay under
MIN_SPEEDUP = 2.0       # optimized vs naive chunked replay
LEN_RATIO = 8           # memory gate: long stream / short stream


def _irm(n_requests: int, *, n_pages: int = 4096, seed: int = 7,
         rate: float = 0.0) -> TrafficSpec:
    return TrafficSpec(kind="irm", n_requests=n_requests, n_pages=n_pages,
                       zipf_s=1.1, write_fraction=0.3, seed=seed, rate=rate)


def _live_device_bytes() -> int:
    return int(sum(a.nbytes for a in jax.live_arrays()))


def _ctr_equal(a, b) -> list:
    """Field names on which two Tier1Counters disagree."""
    bad = []
    for f in a._fields:
        if not np.array_equal(np.asarray(getattr(a, f)),
                              np.asarray(getattr(b, f))):
            bad.append(f)
    return bad


def bench_bit_exact(smoke: bool) -> dict:
    n = 20_000 if smoke else 200_000
    chunk = 1_999 if smoke else 19_993  # prime: boundaries straddle windows
    cases = {}

    # Request-index windows.
    spec = SimSpec(traffic=_irm(n), store=StoreConfig(n_lines=256,
                                                      policy="ws"),
                   n_shards=4, n_windows=12)
    ref = tier1_counters(spec)
    ctr, _, ck = stream_tier1_counters(spec, chunk=chunk)
    rep_eq = (report_from_counters(spec, ref).to_dict()
              == simulate_stream(spec, chunk=chunk).to_dict())
    cases["indexed"] = {"counter_mismatches": _ctr_equal(ref, ctr),
                       "report_equal": rep_eq, "chunks": -(-n // chunk)}

    # Wall-clock windows + a fault schedule: failover reroutes and the
    # cold-refill correction must survive chunk boundaries.
    spec_f = SimSpec(
        traffic=_irm(n // 4, rate=float(n // 4) / 60.0, seed=9),
        store=StoreConfig(n_lines=128), n_shards=4, window_dt=2.0,
        faults=FaultSpec(events=(shard_down(1, 10.0, 25.0),)),
    )
    ref_f = tier1_counters(spec_f)
    ctr_f, _, _ = stream_tier1_counters(spec_f, chunk=chunk // 4 + 1)
    rep_f_eq = (report_from_counters(spec_f, ref_f).to_dict()
                == simulate_stream(spec_f, chunk=chunk // 4 + 1).to_dict())
    cases["faulted"] = {"counter_mismatches": _ctr_equal(ref_f, ctr_f),
                        "report_equal": rep_f_eq}

    # Tenant mix: streamed counters equal the one-shot merge, per-tenant
    # series reconcile with the pooled windows.
    mix = tenant_mix(
        TenantSpec(name="oltp", rate=600.0, n_pages=1024, zipf_s=1.3,
                   write_fraction=0.4),
        TenantSpec(name="analytics", rate=200.0, n_pages=4096, zipf_s=0.9),
        n_requests=n // 4, seed=3)
    spec_t = SimSpec(traffic=mix, store=StoreConfig(n_lines=256,
                                                    policy="ws"),
                     n_shards=4, window_dt=1.0)
    ref_t = tier1_counters(spec_t)
    ctr_t, tc, _ = stream_tier1_counters(spec_t, chunk=chunk // 3 + 1)
    recon = bool(
        np.array_equal(tc.win_requests.sum(axis=0),
                       np.asarray(ctr_t.win_requests).sum(axis=0))
        and np.array_equal(tc.win_misses.sum(axis=0),
                           np.asarray(ctr_t.win_misses).sum(axis=0))
        and int(tc.win_requests.sum()) == mix.n_requests)
    cases["tenant_mix"] = {"counter_mismatches": _ctr_equal(ref_t, ctr_t),
                           "attribution_reconciles": recon,
                           "tenants": list(tc.names)}

    ok = all(
        not c["counter_mismatches"] and c.get("report_equal", True)
        and c.get("attribution_reconciles", True)
        for c in cases.values())
    return {**cases, "ok": bool(ok)}


def bench_compile_count(smoke: bool) -> dict:
    n = 65_536 if smoke else 1_048_576
    chunk = 2_048 if smoke else 32_768          # 32 chunks either way
    # A store shape no other section uses, so the jit cache starts cold
    # and the counter measures this replay's compiles alone.
    spec = SimSpec(traffic=_irm(n, n_pages=8192, seed=13),
                   store=StoreConfig(n_lines=192), n_shards=4, n_windows=8)
    reset_stream_compile_count()
    t0 = time.perf_counter()
    ctr, _, ck = stream_tier1_counters(spec, chunk=chunk)
    wall = time.perf_counter() - t0
    compiles = stream_compile_count()
    return {
        "n_requests": n,
        "chunks": n // chunk,
        "compiles": compiles,
        "wall_s": round(wall, 3),
        "requests_per_sec": round(n / wall),
        "ok": bool(compiles <= COMPILE_LIMIT and ck.done
                   and int(np.asarray(ctr.requests).sum()) == n),
    }


def _replay_peak_bytes(cfg: StoreConfig, pages, writes, *, chunk: int,
                       n_shards: int) -> int:
    """Drive the chunk engine directly, sampling live device bytes after
    every (synchronized) chunk — the measured peak of a replay."""
    eng = stream_chunk_engine(cfg, n_windows=1)
    hyper = cfg.hyper()
    carry = init_stream_carry(cfg, n_shards, n_windows=1)
    n = pages.shape[0]
    zeros = np.zeros(chunk, np.int32)
    peak = 0
    for start in range(0, n, chunk):
        sl = slice(start, min(start + chunk, n))
        m = sl.stop - sl.start
        own = (pages[sl] % n_shards).astype(np.int32)  # round-robin owners
        cnt = np.bincount(own, minlength=n_shards)
        sh_p, sh_w, _, _, sh_win = partition_streams(
            pages[sl], writes[sl], n_shards=n_shards,
            n_pages=int(pages.max()) + 1,
            cap=_chunk_cap(int(cnt.max()), chunk, n_shards),
            n_windows=1, window_ids=zeros[:m], owner=own)
        carry = eng(hyper, carry, *jax.device_put((sh_p, sh_w, sh_win)))
        jax.block_until_ready(carry)
        peak = max(peak, _live_device_bytes())
    return peak


def bench_memory(smoke: bool) -> dict:
    n_short = 16_384 if smoke else 131_072
    n_long = n_short * LEN_RATIO
    chunk = 2_048 if smoke else 16_384
    cfg = StoreConfig(n_lines=256)
    rng = np.random.default_rng(5)
    pages = rng.integers(0, 4096, size=n_long).astype(np.int32)
    writes = rng.random(n_long) < 0.3
    peak_short = _replay_peak_bytes(cfg, pages[:n_short], writes[:n_short],
                                    chunk=chunk, n_shards=4)
    peak_long = _replay_peak_bytes(cfg, pages, writes,
                                   chunk=chunk, n_shards=4)
    ratio = peak_long / max(peak_short, 1)
    return {
        "n_short": n_short,
        "n_long": n_long,
        "peak_bytes_short": peak_short,
        "peak_bytes_long": peak_long,
        "ratio": round(ratio, 4),
        "ok": bool(ratio <= MEM_FLAT_RATIO),
    }


def bench_throughput(smoke: bool) -> dict:
    n = 65_536 if smoke else 524_288
    chunk = 4_096 if smoke else 16_384
    # round_robin spreads the zipf head across shards, so chunks land in
    # the primary (balanced-load) bucket — chunk/4 per shard at S=8 —
    # while the naive baseline scans the full worst-case chunk per shard.
    spec = SimSpec(traffic=_irm(n, n_pages=8192, seed=21),
                   store=StoreConfig(n_lines=256), n_shards=8,
                   mapping="round_robin", n_windows=4)

    # Optimized streamed replay (warm the engine once, then time).
    stream_tier1_counters(spec, chunk=chunk, max_requests=chunk)
    t0 = time.perf_counter()
    ctr, _, _ = stream_tier1_counters(spec, chunk=chunk)
    t_stream = time.perf_counter() - t0

    # Naive chunked baseline: worst-case padding (every shard sized to the
    # whole chunk), no donation, a hard sync + carry round-trip per chunk.
    from repro.sim.engine import fault_owner, stream_for_spec
    pages, is_write, times, n_pages, n_windows, _ = stream_for_spec(spec)
    gwin = stream_window_ids(n, n_windows)
    owner = fault_owner(spec, pages, times, n_pages)
    cap = 1
    while cap < chunk:
        cap <<= 1
    eng = stream_chunk_engine(spec.store, n_windows=n_windows, donate=False)
    hyper = spec.store.hyper()
    # Warm the naive shape too: the gate measures steady-state throughput.
    carry = init_stream_carry(spec.store, spec.n_shards, n_windows=n_windows)
    sh = partition_streams(pages[:chunk], is_write[:chunk],
                           n_shards=spec.n_shards, n_pages=n_pages, cap=cap,
                           n_windows=n_windows, window_ids=gwin[:chunk],
                           owner=owner[:chunk])
    jax.block_until_ready(eng(hyper, carry, sh[0], sh[1], sh[4]))
    t0 = time.perf_counter()
    carry = init_stream_carry(spec.store, spec.n_shards, n_windows=n_windows)
    for start in range(0, n, chunk):
        sl = slice(start, min(start + chunk, n))
        sh_p, sh_w, _, _, sh_win = partition_streams(
            pages[sl], is_write[sl], n_shards=spec.n_shards,
            n_pages=n_pages, cap=cap, n_windows=n_windows,
            window_ids=gwin[sl], owner=owner[sl])
        carry = eng(hyper, carry, sh_p, sh_w, sh_win)
        jax.tree.map(np.asarray, carry)  # sync + host round-trip
    t_naive = time.perf_counter() - t0

    speedup = t_naive / t_stream
    return {
        "n_requests": n,
        "chunk": chunk,
        "stream_wall_s": round(t_stream, 3),
        "stream_requests_per_sec": round(n / t_stream),
        "naive_wall_s": round(t_naive, 3),
        "naive_requests_per_sec": round(n / t_naive),
        "speedup": round(speedup, 2),
        "ok": bool(speedup >= MIN_SPEEDUP
                   and int(np.asarray(ctr.requests).sum()) == n),
    }


def main() -> None:
    smoke = "--smoke" in sys.argv
    artifact = {
        "mode": "smoke" if smoke else "full",
        "devices": jax.local_device_count(),
        "bit_exact": bench_bit_exact(smoke),
        "compile_count": bench_compile_count(smoke),
        "memory": bench_memory(smoke),
        "throughput": bench_throughput(smoke),
    }
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=1)
        f.write("\n")

    be, cc = artifact["bit_exact"], artifact["compile_count"]
    mem, tp = artifact["memory"], artifact["throughput"]
    print(f"devices: {artifact['devices']}")
    print(f"bit-exact: indexed/faulted/tenant ok={be['ok']}")
    print(f"compile count: {cc['compiles']} compiles over {cc['chunks']} "
          f"chunks of {cc['n_requests']} requests "
          f"({cc['requests_per_sec']} req/s) ok={cc['ok']}")
    print(f"memory: peak {mem['peak_bytes_short']}B @ {mem['n_short']} vs "
          f"{mem['peak_bytes_long']}B @ {mem['n_long']} "
          f"(ratio {mem['ratio']}) ok={mem['ok']}")
    print(f"throughput: {tp['stream_requests_per_sec']} req/s streamed vs "
          f"{tp['naive_requests_per_sec']} req/s naive -> "
          f"{tp['speedup']}x ok={tp['ok']}")
    print(f"artifact: {ARTIFACT}")
    failures = [k for k in ("bit_exact", "compile_count", "memory",
                            "throughput") if not artifact[k]["ok"]]
    if failures:
        raise SystemExit(f"bench_stream gates failed: {failures}")


if __name__ == "__main__":
    main()
