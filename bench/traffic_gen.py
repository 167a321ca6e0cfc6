"""The benchmark's one traffic generator: a configuration's ``stream`` block
in, a request trace ``(pages, is_write, times)`` out, from a seed.

The program under test receives only the generated arrays (as a ``trace``);
the plain reference (:mod:`reference`) reads the same arrays. Nothing here
imports the program.

Kinds:

- ``irm`` — the independent reference model: every request draws a page
  from ``n_pages`` pages, independently, with probability proportional to
  ``rank ** -zipf_s``. Ranks map to page ids by YCSB's scramble (its
  ScrambledZipfianGenerator hashes a rank with FNV-1a 64), here made a
  bijection: the page of rank ``r`` is the position of ``fnv1a64(r)`` among
  the hashes of all ranks. So the page space is exactly ``n_pages``, the
  popular pages are spread over the id space the same way for every seed,
  and the seed draws only the request sequence. Built in bulk with numpy.

Arrival times are a Poisson process at ``rate`` requests/s; writes are
Bernoulli(``write_fraction``).

- ``poisson_decay`` — the paper's Poisson traffic model (§VI): pages
  become active in a Poisson process of ``arrival_rate`` per request, and
  each request picks an active page with weight ``exp(-age / decay_tau)``.
  This kind is the stream the program makes itself from a spec (a copy of
  ``repro.core.traffic.poisson_stream`` and ``arrival_times``, draw for
  draw), because a sweep generates its own traffic: the reference replays
  that stream from the same seed. The seed is the program's traffic seed.
"""
from __future__ import annotations

import numpy as np

FNV_OFFSET_64 = np.uint64(0xCBF29CE484222325)
FNV_PRIME_64 = np.uint64(0x100000001B3)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """A generator for ``seed`` and a sub-stream path (any non-negative
    integers, beyond 64 bits included)."""
    return np.random.default_rng([int(seed), *map(int, stream)])


def fnv1a64(values: np.ndarray) -> np.ndarray:
    """FNV-1a 64 of each value's eight bytes, lowest first (YCSB's
    ``Utils.fnvhash64`` before it takes the absolute value)."""
    v = np.asarray(values).astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_64, np.uint64)
    for _ in range(8):
        h ^= v & np.uint64(0xFF)
        h *= FNV_PRIME_64
        v >>= np.uint64(8)
    return h


def page_of_rank(n_pages: int) -> np.ndarray:
    """The scrambled page id of each popularity rank (0 the most popular)."""
    order = np.argsort(fnv1a64(np.arange(n_pages)), kind="stable")
    pages = np.empty(n_pages, np.int64)
    pages[order] = np.arange(n_pages)
    return pages


def irm_pages(rng: np.random.Generator, n: int, n_pages: int,
              zipf_s: float) -> np.ndarray:
    pop = np.arange(1, n_pages + 1, dtype=np.float64) ** (-float(zipf_s))
    ranks = rng.choice(n_pages, size=n, p=pop / pop.sum())
    return page_of_rank(n_pages)[ranks].astype(np.int32)


TIME_SEED = 0x7157     # the program's tag for its arrival-time stream


def poisson_decay_pages(n: int, n_pages: int, decay_tau: float,
                        arrival_rate: float, seed: int) -> np.ndarray:
    """The Poisson decay model's page sequence, drawn as the program draws
    it (one request at a time)."""
    rng = np.random.default_rng(seed)
    arrival_t = np.full(n_pages, np.inf)
    n_seed = max(1, n_pages // 16)
    arrival_t[:n_seed] = 0.0
    next_page = n_seed
    pages = np.empty(n, dtype=np.int32)
    for t in range(n):
        for _ in range(rng.poisson(arrival_rate)):
            if next_page < n_pages:
                arrival_t[next_page] = t
                next_page += 1
        active = np.isfinite(arrival_t)
        w = np.exp(-(t - arrival_t[active]) / decay_tau)
        w_sum = w.sum()
        if w_sum <= 0:
            w = np.ones_like(w)
            w_sum = w.sum()
        pages[t] = rng.choice(np.nonzero(active)[0], p=w / w_sum)
    return pages


def poisson_decay_trace(stream: dict, seed: int):
    """``(pages, is_write, times)`` of a ``poisson_decay`` stream, as the
    program makes it from its traffic seed ``seed``."""
    n = int(stream["n_requests"])
    if float(stream.get("write_fraction", 0.0)) != 0.0:
        raise ValueError("the poisson_decay copy covers read-only streams")
    pages = poisson_decay_pages(n, int(stream["n_pages"]),
                                float(stream["decay_tau"]),
                                float(stream["arrival_rate"]), seed)
    gaps = np.random.default_rng([seed, TIME_SEED]).exponential(1.0, size=n)
    times = np.cumsum(gaps / np.full(n, float(stream["rate"])))
    return pages, np.zeros(n, bool), times


def make_trace(stream: dict, seed: int):
    """``(pages int32[n], is_write bool[n], times float64[n])`` for a
    configuration's ``stream`` block."""
    kind = stream["kind"]
    n = int(stream["n_requests"])
    if kind != "irm":
        raise ValueError(f"unknown stream kind {kind!r}")
    if stream.get("scramble") != "fnv1a64":
        raise ValueError("an irm stream names its scramble: fnv1a64")
    pages = irm_pages(rng_for(seed, 0), n, int(stream["n_pages"]),
                      float(stream["zipf_s"]))
    frac = float(stream.get("write_fraction", 0.0))
    is_write = (rng_for(seed, 1).random(n) < frac if frac > 0
                else np.zeros(n, bool))
    times = np.cumsum(rng_for(seed, 2).exponential(1.0, n)
                      / float(stream["rate"]))
    return pages, is_write, times
