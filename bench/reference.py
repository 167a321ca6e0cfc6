"""Plain reference of the tier-1 counters a sharded LRU store gives a trace.

Written from the semantics alone (paper §III–V) and importing nothing of
the program:

- **Page space and shards.** A trace's page space is ``max(page) + 1``.
  Under ``block`` mapping shard ``s`` owns the ``s``-th block of
  ``ceil(n_pages / n_shards)`` pages (the last shard takes any rest).
- **Windows.** Arrival times start at the trace's first arrival; a request
  belongs to window ``floor(t / window_dt)``, the last window taking any
  later arrival.
- **Cache.** Each shard runs a fully associative LRU cache of ``n_lines``
  lines, kept as an ordered dict: a hit moves the page to the most recent
  end; a miss inserts it there and, when the cache is full, evicts the
  least recent page first (a tier-2 write when that page is dirty). With no
  prefetch every miss is one tier-2 read. Every eviction is the LRU
  expert's, and a fixed policy never moves the expert weights from their
  uniform start (``1/3`` in float32); a window's weights are those after its
  last request, zero where it had none.

``control`` names one guarantee to break, for the benchmark's control runs:
``"fifo"`` (a hit does not refresh recency) or ``"reset"`` (the cache is
emptied at each resume boundary in ``resets``).

:func:`fault_counters` covers a deployment with a fault schedule and a
fixed LRU or LFU policy, as a report of the program's ``sweep`` gives its
counters (paper §III, §VI):

- **Mapping.** ``round_robin``: page ``p`` lives on shard ``p % n_shards``;
  ``block`` as above over the declared page space.
- **Failover.** A request whose shard is down at its arrival (``t0 <= t <
  t1``) goes to the next shard up, cyclically, that is alive then.
- **Windows.** Window ``floor(t / window_dt)`` of the arrival time itself,
  the last window taking any later arrival.
- **LFU.** A shard's lines fill in order; a hit adds one to its line's
  count, an inserted page starts at one; the victim is the line with the
  smallest count, the first such line on a tie, and the new page takes its
  line. Evictions count for the policy's expert (LRU 0, LFU 1).
- **Cold refill.** A shard that comes back from an outage is cold: from
  the window its outage ends in, its first ``n_lines`` requests cannot hit,
  so that many of those windows' hits (at most each window's hits) are
  counted as misses and tier-2 reads instead.
- **Weights** do not move under a fixed policy: uniform (``1/3`` in
  float32) in a window with requests; a report carries a window without
  requests over from the window before it, and ``1/3`` before the first.

``control="fifo"`` breaks the policy's use of hits there too: a hit leaves
its line's recency (LRU) or count (LFU) as it was.
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Optional, Sequence

import numpy as np

N_EXPERTS = 3            # lru, lfu, random
LRU_EXPERT = 0
UNIFORM = np.float32(1.0) / np.float32(N_EXPERTS)

# Counter arrays compared, per shard and per (shard, window).
TOTALS = ("requests", "reads", "writes", "hits", "misses", "prefetch_hits",
          "tier2_reads", "tier2_writes", "evictions")
WINDOWED = ("win_requests", "win_hits", "win_misses", "win_prefetch_hits",
            "win_tier2_reads", "win_tier2_writes", "win_evictions",
            "win_expert_use")


def owners(pages: np.ndarray, n_shards: int, mapping: str) -> np.ndarray:
    if mapping != "block":
        raise ValueError(f"reference knows block mapping only, not {mapping}")
    n_pages = int(pages.max()) + 1
    block = -(-n_pages // n_shards)
    return np.minimum(pages.astype(np.int64) // block, n_shards - 1)


def window_ids(times: np.ndarray, n_windows: int,
               window_dt: float) -> np.ndarray:
    t = np.asarray(times, np.float64)
    t = t - t.min()
    return np.minimum(np.floor(t / window_dt), n_windows - 1).astype(np.int64)


def lru_flags(pages: Sequence[int], writes: Sequence[bool], n_lines: int,
              control: Optional[str] = None, resets: Sequence[int] = ()):
    """Per-request ``(hit, evict, writeback)`` byte flags of one shard;
    ``resets`` are positions in this shard's sequence."""
    n = len(pages)
    hit = bytearray(n)
    evict = bytearray(n)
    wb = bytearray(n)
    refresh = control != "fifo"
    cuts = sorted(set(resets)) if control == "reset" else []
    bounds = [0, *[c for c in cuts if 0 < c < n], n]
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        cache: OrderedDict = OrderedDict()
        for i in range(lo, hi):
            p = pages[i]
            if p in cache:
                hit[i] = 1
                if refresh:
                    cache.move_to_end(p)
                if writes[i]:
                    cache[p] = True
            else:
                if len(cache) >= n_lines:
                    _, dirty = cache.popitem(last=False)
                    evict[i] = 1
                    wb[i] = dirty
                cache[p] = bool(writes[i])
    return hit, evict, wb


def shard_counters(win: np.ndarray, hit, evict, wb, n_windows: int) -> dict:
    """Windowed counters ``[W]`` (and ``[W, E]`` expert use) of one shard."""
    h = np.frombuffer(bytes(hit), np.uint8).astype(bool)
    e = np.frombuffer(bytes(evict), np.uint8).astype(bool)
    b = np.frombuffer(bytes(wb), np.uint8).astype(bool)

    def count(mask=None):
        return np.bincount(win if mask is None else win[mask],
                           minlength=n_windows).astype(np.int64)

    req = count()
    hits = count(h)
    ev = count(e)
    use = np.zeros((n_windows, N_EXPERTS), np.int64)
    use[:, LRU_EXPERT] = ev
    return {"win_requests": req, "win_hits": hits,
            "win_misses": req - hits,
            "win_prefetch_hits": np.zeros(n_windows, np.int64),
            "win_tier2_reads": req - hits, "win_tier2_writes": count(b),
            "win_evictions": ev, "win_expert_use": use}


def counters(pages, is_write, times, *, n_shards: int, mapping: str,
             n_lines: int, n_windows: int, window_dt: float,
             shards: Optional[Sequence[int]] = None, prefix: Optional[int]
             = None, control: Optional[str] = None,
             resets: Sequence[int] = ()) -> dict:
    """Reference counters of the first ``prefix`` requests of a trace (the
    whole trace by default), for the shards in ``shards`` (all by default):
    ``{shard: {counter: array}}``. Page space and window origin are the
    whole trace's. ``resets`` are request offsets into the trace."""
    pairs = [(int(s), n_lines) for s in (range(n_shards) if shards is None
                                         else shards)]
    got = pair_counters(pages, is_write, times, pairs, n_shards=n_shards,
                        mapping=mapping, n_windows=n_windows,
                        window_dt=window_dt, prefix=prefix, control=control,
                        resets=resets)
    return {s: got[(s, n)] for s, n in pairs}


def pair_counters(pages, is_write, times, pairs, *, n_shards: int,
                  mapping: str, n_windows: int, window_dt: float,
                  prefix: Optional[int] = None, control: Optional[str] = None,
                  resets: Sequence[int] = ()) -> dict:
    """As :func:`counters`, for each ``(shard, n_lines)`` of ``pairs``:
    ``{(shard, n_lines): {counter: array}}``; the trace's owners and
    windows are worked out once for all of them."""
    own = owners(np.asarray(pages), n_shards, mapping)
    win = window_ids(times, n_windows, window_dt)
    n = len(pages) if prefix is None else int(prefix)
    own, win = own[:n], win[:n]
    pages = np.asarray(pages)[:n]
    is_write = np.asarray(is_write, bool)[:n]
    out = {}
    for s, n_lines in pairs:
        idx = np.nonzero(own == s)[0]
        cuts = np.searchsorted(idx, np.asarray(resets, np.int64))
        hit, ev, wb = lru_flags(pages[idx].tolist(), is_write[idx].tolist(),
                                int(n_lines), control, cuts.tolist())
        ctr = shard_counters(win[idx], hit, ev, wb, n_windows)
        for name in TOTALS:
            if "win_" + name in ctr:
                ctr[name] = ctr["win_" + name].sum()
        ctr["writes"] = np.int64(is_write[idx].sum())
        ctr["reads"] = ctr["requests"] - ctr["writes"]
        ctr["win_weights"] = np.where(
            (ctr["win_requests"] > 0)[:, None],
            np.full((1, N_EXPERTS), UNIFORM, np.float64), 0.0)
        out[(int(s), int(n_lines))] = ctr
    return out


def mismatches(program, ref: dict) -> dict:
    """Cells in which a program's counters (an object with the counter
    attributes, ``[S]`` totals and ``[S, W...]`` windowed arrays) differ
    from the reference's, over the reference's shards:
    ``{"counters": n, "weights": n, "cells": n compared}``."""
    bad_c = bad_w = cells = 0
    for s, ctr in ref.items():
        for name in TOTALS + WINDOWED:
            got = np.asarray(getattr(program, name))[s]
            want = np.asarray(ctr[name])
            bad_c += int(np.sum(got != want))
            cells += want.size
        got = np.asarray(program.win_weights, np.float64)[s]
        bad_w += int(np.sum(got != ctr["win_weights"]))
        cells += ctr["win_weights"].size
    return {"counters": bad_c, "weights": bad_w, "cells": cells}


EXPERT_OF = {"lru": 0, "lfu": 1}


def lfu_flags(pages: Sequence[int], writes: Sequence[bool], n_lines: int,
              control: Optional[str] = None):
    """Per-request ``(hit, evict, writeback)`` byte flags of one LFU shard."""
    n = len(pages)
    hit = bytearray(n)
    evict = bytearray(n)
    wb = bytearray(n)
    line_page: list = []
    count: list = []
    dirty: list = []
    where: dict = {}
    for i in range(n):
        p = pages[i]
        k = where.get(p)
        if k is not None:
            hit[i] = 1
            if control != "fifo":
                count[k] += 1
            if writes[i]:
                dirty[k] = True
            continue
        if len(line_page) < n_lines:
            where[p] = len(line_page)
            line_page.append(p)
            count.append(1)
            dirty.append(bool(writes[i]))
            continue
        k = count.index(min(count))
        evict[i] = 1
        wb[i] = dirty[k]
        del where[line_page[k]]
        where[p] = k
        line_page[k], count[k], dirty[k] = p, 1, bool(writes[i])
    return hit, evict, wb


def failover(owner: np.ndarray, times: np.ndarray, down, n_shards: int):
    """Owners after failover: ``down`` is ``(shard, t0, t1)`` outages."""
    def is_down(shard, t):
        return any(s == shard and t0 <= t < t1 for s, t0, t1 in down)
    out = owner.copy()
    for i, (home, t) in enumerate(zip(owner.tolist(), times.tolist())):
        if not is_down(home, t):
            continue
        for off in range(1, n_shards):
            cand = (home + off) % n_shards
            if not is_down(cand, t):
                out[i] = cand
                break
    return out


def fault_counters(pages, is_write, times, *, n_shards: int, mapping: str,
                   n_pages: int, n_lines: int, policy: str, n_windows: int,
                   window_dt: float, down=(), refill_cold: bool = True,
                   control: Optional[str] = None) -> dict:
    """Counters of every shard of a faulted deployment under a fixed
    ``policy``: ``{shard: {counter: array}}``, with the report's
    ``win_weights`` (carried over empty windows)."""
    pages = np.asarray(pages, np.int64)
    is_write = np.asarray(is_write, bool)
    times = np.asarray(times, np.float64)
    if mapping == "round_robin":
        own = pages % n_shards
    else:
        own = np.minimum(pages // -(-n_pages // n_shards), n_shards - 1)
    own = failover(own, times, down, n_shards)
    win = np.minimum(np.floor(times / window_dt), n_windows - 1).astype(
        np.int64)
    out = {}
    for s in range(n_shards):
        idx = np.nonzero(own == s)[0]
        if policy == "lru":
            flags = lru_flags(pages[idx].tolist(), is_write[idx].tolist(),
                              n_lines, control)
        else:
            flags = lfu_flags(pages[idx].tolist(), is_write[idx].tolist(),
                              n_lines, control)
        ctr = shard_counters(win[idx], *flags, n_windows)
        use = np.zeros((n_windows, N_EXPERTS), np.int64)
        use[:, EXPERT_OF[policy]] = ctr["win_evictions"]
        ctr["win_expert_use"] = use
        out[s] = ctr
    if refill_cold:
        for shard, _, t1 in down:
            budget = n_lines
            for w in range(max(int(np.floor(t1 / window_dt)), 0), n_windows):
                if budget <= 0:
                    break
                c = out[shard]
                cold = min(budget, int(c["win_requests"][w]))
                extra = min(int(c["win_hits"][w]), cold)
                c["win_hits"][w] -= extra
                c["win_misses"][w] += extra
                c["win_tier2_reads"][w] += extra
                budget -= cold
    for s, ctr in out.items():
        for name in TOTALS:
            if "win_" + name in ctr:
                ctr[name] = ctr["win_" + name].sum()
        ctr["writes"] = np.int64(is_write[own == s].sum())
        ctr["reads"] = ctr["requests"] - ctr["writes"]
        w = np.empty((n_windows, N_EXPERTS), np.float64)
        prev = np.full(N_EXPERTS, 1.0 / N_EXPERTS)
        for t in range(n_windows):
            if ctr["win_requests"][t] > 0:
                prev = np.full(N_EXPERTS, UNIFORM, np.float64)
            w[t] = prev
        ctr["win_weights"] = w
    return out


REPORT_TOTALS = TOTALS
REPORT_WINDOWED = {"win_requests": "requests", "win_hits": "hits",
                   "win_misses": "misses",
                   "win_prefetch_hits": "prefetch_hits",
                   "win_tier2_reads": "tier2_reads",
                   "win_tier2_writes": "tier2_writes",
                   "win_evictions": "evictions",
                   "win_expert_use": "expert_use"}


def report_mismatches(report, ref: dict) -> dict:
    """Cells in which a program's report (per-shard counters in
    ``report.shards``, windowed ones in ``report.windows``) differs from
    the reference's: ``{"counters": n, "weights": n, "cells": n}``."""
    bad_c = bad_w = cells = 0
    if len(report.shards) != len(ref):
        return {"counters": 1, "weights": 1, "cells": 1}
    for s, ctr in ref.items():
        sh = report.shards[s]
        for name in REPORT_TOTALS:
            bad_c += int(getattr(sh, name) != ctr[name])
            cells += 1
        for name, field in REPORT_WINDOWED.items():
            bad_c += _cells_differ(getattr(report.windows, field), s,
                                   ctr[name])
            cells += ctr[name].size
        bad_w += _cells_differ(report.windows.weights, s, ctr["win_weights"])
        cells += ctr["win_weights"].size
    return {"counters": bad_c, "weights": bad_w, "cells": cells}


def _cells_differ(got, shard: int, want: np.ndarray) -> int:
    """Differing cells of row ``shard`` of ``got`` against ``want``; every
    cell where the shapes disagree."""
    got = np.asarray(got)
    if got.ndim == 0 or got.shape[1:] != want.shape or shard >= len(got):
        return int(want.size)
    return int(np.sum(got[shard] != want))
