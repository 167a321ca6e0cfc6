"""Faults planted in the weight-sharing replay's timed path, for the test
that each reads ``correct: false``; and a runner that plants one and runs a
cell on the CPU, as ``cpu_run.py`` does:

  python bench/tests/ws_plants.py <checkout root> --plant <fault> \
      --workload <cell> --seed <n> --seconds <s> --trace <0|1>

- ``frozen_lru`` — the learner frozen to LRU: the replay runs under the
  fixed ``lru`` policy whatever the configuration says;
- ``key_on_pad`` — the Random expert's key advanced on pads: after each
  chunk every shard's key is split once more for each pad of its row;
- ``weight_rounded`` — one weight rounded to bfloat16: the first shard's
  weight of the first expert in the last window with requests, in every
  counter set returned.
"""
from __future__ import annotations

import dataclasses
import os
import sys
import time

import numpy as np

FAULTS = ("frozen_lru", "key_on_pad", "weight_rounded")


def bfloat16(x: float) -> float:
    b = np.array([x], np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & 1)) & np.uint32(0xFFFF0000)
    return float(b.view(np.float32)[0])


def plant(name: str) -> None:
    import repro.sim.stream as stream
    import repro.storage.tiered_store as store
    replay, scan = stream.stream_tier1_counters, store.cache_scan_ref

    def bad_replay(spec, trace=None, **kw):
        if name == "frozen_lru":
            spec = dataclasses.replace(spec, store=dataclasses.replace(
                spec.store, policy="lru"))
        out = replay(spec, trace, **kw)
        if name == "weight_rounded":
            ctr = out[0]
            w = np.array(ctr.win_weights, copy=True)
            last = np.nonzero(np.asarray(ctr.win_requests)[0])[0][-1]
            w[0, last, 0] = bfloat16(w[0, last, 0])
            out = (ctr._replace(win_weights=w),) + out[1:]
        return out

    def bad_scan(state0, acc0, pages, writes, win, hyper, noise, **kw):
        import jax
        import jax.numpy as jnp
        final, acc = scan(state0, acc0, pages, writes, win, hyper, noise,
                          **kw)
        pads = jnp.sum(win >= kw["n_windows"])
        key = jax.lax.fori_loop(0, pads, lambda i, k: jax.random.split(k)[0],
                                final.key)
        return final._replace(key=key), acc

    stream.stream_tier1_counters = bad_replay
    if name == "key_on_pad":
        store.cache_scan_ref = bad_scan


if __name__ == "__main__":
    T_START = time.perf_counter()
    root = os.path.abspath(sys.argv[1])
    if sys.argv[2] != "--plant":
        raise SystemExit("usage: ws_plants.py <root> --plant <fault> ...")
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, os.path.join(root, "bench"))
    import jax

    import harness
    harness.find_devices = lambda chips: jax.devices()[:chips]
    plant(sys.argv[3])
    sys.exit(harness.main(sys.argv[4:], root, T_START))
