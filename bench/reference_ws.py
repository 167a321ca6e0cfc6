"""Plain reference of a sharded tier-1 replay under the weight-sharing
learner (arXiv:2503.08966 §III-A, Algorithms 1-2), one shard at a time.

Written from the paper and the semantics the program documents, importing
nothing of the program: numpy and Python, except the Random expert's draws,
which come from JAX's PRNG under the key rule below.

- **Shards and windows.** A trace's page space is ``max(page) + 1``; under
  ``block`` mapping shard ``s`` owns the ``s``-th block of
  ``ceil(n_pages / n_shards)`` pages (the last shard takes any rest).
  Arrival times start at the trace's first arrival and a request belongs to
  window ``floor(t / window_dt)``, the last window taking any later one.
- **Cache.** Each shard is a fully associative cache of ``n_lines`` lines
  that fill in line order. A request is step ``t`` of its shard (its real
  requests counted from 0). A hit sets the line's timestamp to ``t``, adds
  one to its count and ORs the write into its dirty bit. A miss takes the
  lowest free line; with none free it evicts (Algorithm 1): LRU proposes the
  line with the oldest timestamp, LFU the line with the smallest count (the
  first such line on a tie), Random the line the key rule draws; each
  expert's proposed page goes into its prediction vector; the expert with
  the highest probability (its weight over the weights' sum, the first
  expert on a tie) names the victim, a dirty victim costing one tier-2
  write. The new page's line takes timestamp ``t``, count 1 and the write
  as its dirty bit.
- **Mispredictions.** A miss adds one to the epoch's miss count and one to
  the misprediction count of each expert whose prediction vector holds the
  page, before the step's own proposals are recorded. A vector holds every
  proposal of its epoch (the program's rings hold ``pred_cap`` of them,
  64 by default, against at most ``epoch_width`` evictions an epoch).
- **WeightAdjust** (Algorithm 2, with the repo's documented departure
  ``w <- w * beta^l``) runs after step ``t`` when ``(t + 1) % epoch_width
  == 0``: ``l_i`` is expert ``i``'s misprediction count where it reaches
  ``threshold * misses``, else 0; ``w_i <- w_i * beta^l_i``; the mean lost
  weight times ``alpha`` is added to each; each weight is floored at
  ``1e-8`` and the weights are divided by their sum. The prediction
  vectors, the misprediction counts and the epoch's miss count are then
  cleared. Weights are float32, every operation rounded in the program's
  order (sums left to right); ``beta^l`` is float32 ``power``.
- **Key rule.** Every shard's key starts at ``PRNGKey(0)``; each real
  request of the shard splits it once (``key, vkey = split(key)``) and
  Random proposes ``argmax(uniform(vkey, (n_lines,)))``, whether or not the
  request evicts. So step ``t`` of every shard draws the same line; the
  draws are made in blocks on JAX's default device (:func:`random_lines`).
- **Fixed policies** (``lru``, ``lfu``, ``random``): that expert names
  every victim and the weights stay at their uniform start (``1/3``).

A window's weights are those after its last request, zero where it had
none. No prefetch.
"""
from __future__ import annotations

import heapq
import struct
from collections import OrderedDict
from typing import NamedTuple, Optional, Sequence

import numpy as np

N_EXPERTS = 3
EXPERTS = ("lru", "lfu", "random")
TOTALS = ("requests", "reads", "writes", "hits", "misses", "prefetch_hits",
          "tier2_reads", "tier2_writes", "evictions")
WINDOWED = ("win_requests", "win_hits", "win_misses", "win_prefetch_hits",
            "win_tier2_reads", "win_tier2_writes", "win_evictions",
            "win_expert_use")
_PACK = struct.Struct("f")


def f32(x: float) -> float:
    """``x`` rounded to float32 (as a Python float). One float32 operation
    is its double result rounded once, which is exact for + - * /."""
    return _PACK.unpack(_PACK.pack(x))[0]


UNIFORM = f32(1.0 / 3.0)
FLOOR = f32(1e-8)

# How far a window's weights may lie from this reference's: 2^-20, eight
# float32 steps at 1.0. The program's compiler is free to evaluate
# ``power`` by its own approximation and to fold the mean's division by
# three into ``alpha`` (on the CPU both happen: weights up to 3 steps off);
# a TPU divides through a refined reciprocal. Such differences of an ulp
# an adjust do not grow along a replay, since each adjust renormalizes.
# Rounding the weights to bfloat16 at each adjust moves them by ~1e-3.
WEIGHT_TOL = 2.0 ** -20


class Learner(NamedTuple):
    epoch_width: int = 4
    alpha: float = 0.5
    beta: float = 0.7
    threshold: float = 0.25


class ShardReplay(NamedTuple):
    """One shard's per-request outcomes and its weight trajectory."""

    hit: np.ndarray        # bool[n]
    evict: np.ndarray      # bool[n]
    expert: np.ndarray     # int8[n], the evicting expert, -1 where none
    writeback: np.ndarray  # bool[n]
    adjust_at: np.ndarray  # int64[k], steps after which the weights changed
    weights: np.ndarray    # float32[k, 3], the weights after each


def random_lines(n: int, n_lines: int, block: int = 4096) -> np.ndarray:
    """Random's proposed line at steps ``0 .. n-1`` under the key rule,
    drawn on JAX's default device ``block`` steps at a time."""
    import jax
    import jax.numpy as jnp

    def draw(key):
        def split(k, _):
            k, vk = jax.random.split(k)
            return k, vk
        key, vkeys = jax.lax.scan(split, key, None, length=block)
        lines = jax.vmap(
            lambda vk: jnp.argmax(jax.random.uniform(vk, (n_lines,))))(vkeys)
        return key, lines

    draw = jax.jit(draw)
    key = jax.random.PRNGKey(0)
    out = []
    for _ in range(-(-n // block)):
        key, lines = draw(key)
        out.append(lines)
    if not out:
        return np.zeros(0, np.int32)
    return np.concatenate([np.asarray(x) for x in out])[:n].astype(np.int32)


def _adjust(w: tuple, mispred: list, misses: int, pw: list,
            learner: Learner) -> tuple:
    thresh = f32(f32(learner.threshold) * misses)
    new = [f32(w[i] * pw[mispred[i] if mispred[i] >= thresh else 0])
           for i in range(N_EXPERTS)]
    d = [f32(w[i] - new[i]) for i in range(N_EXPERTS)]
    shared = f32(f32(f32(d[0] + d[1]) + d[2]) / 3.0)
    give = f32(f32(learner.alpha) * shared)
    new = [max(f32(x + give), FLOOR) for x in new]
    s = f32(f32(new[0] + new[1]) + new[2])
    return tuple(f32(x / s) for x in new)


def _choice(w: tuple) -> int:
    s = f32(f32(w[0] + w[1]) + w[2])
    p = [f32(x / s) for x in w] if s > 0 else [UNIFORM] * N_EXPERTS
    return p.index(max(p))


def replay_shard(pages: Sequence[int], writes: Sequence[bool], n_lines: int,
                 rnd: np.ndarray, *, policy: str = "ws",
                 learner: Learner = Learner()) -> ShardReplay:
    """Replay one shard's requests from a cold cache. ``rnd[t]`` is
    Random's proposal at step ``t`` (:func:`random_lines`)."""
    n = len(pages)
    hit = np.zeros(n, bool)
    evict = np.zeros(n, bool)
    expert = np.full(n, -1, np.int8)
    wback = np.zeros(n, bool)
    adjust_at: list = []
    traj: list = []
    learn = policy == "ws"
    ew = int(learner.epoch_width)
    pw = [f32(float(np.power(np.float32(learner.beta), np.float32(k))))
          for k in range(ew + 1)]
    rnd = rnd.tolist() if isinstance(rnd, np.ndarray) else list(rnd)

    tags: list = []            # page of each filled line
    dirty: list = []
    freq: list = []
    where: dict = {}           # page -> line
    recency: OrderedDict = OrderedDict()   # lines, least recent first
    by_count: dict = {}        # count -> heap of lines (stale entries skipped)
    n_at: dict = {}            # count -> lines holding it
    least = 1                  # smallest count among the lines
    w = (UNIFORM,) * N_EXPERTS
    chosen = _choice(w) if learn else EXPERTS.index(policy)
    pred: list = [[] for _ in range(N_EXPERTS)]
    mispred = [0] * N_EXPERTS
    misses = 0
    memo: dict = {}

    def count_to(line, c):
        freq[line] = c
        n_at[c] = n_at.get(c, 0) + 1
        heapq.heappush(by_count.setdefault(c, []), line)

    def count_from(c):
        left = n_at[c] - 1
        n_at[c] = left
        if not left:
            del by_count[c]

    for t in range(n):
        p = pages[t]
        line = where.get(p)
        if line is not None:
            hit[t] = True
            recency.move_to_end(line)
            c = freq[line]
            count_from(c)
            count_to(line, c + 1)
            if c == least and c not in by_count:
                least = c + 1
            if writes[t]:
                dirty[line] = True
        else:
            if learn:
                misses += 1
                for i in range(N_EXPERTS):
                    if p in pred[i]:
                        mispred[i] += 1
            if len(tags) < n_lines:
                line = len(tags)
                tags.append(p)
                dirty.append(bool(writes[t]))
                freq.append(0)
                recency[line] = None
            else:
                lru = next(iter(recency))
                heap = by_count[least]
                while freq[heap[0]] != least:
                    heapq.heappop(heap)
                prop = (lru, heap[0], rnd[t])
                if learn:
                    for i in range(N_EXPERTS):
                        pred[i].append(tags[prop[i]])
                line = prop[chosen]
                evict[t] = True
                expert[t] = chosen
                wback[t] = dirty[line]
                del where[tags[line]]
                count_from(freq[line])
                tags[line] = p
                dirty[line] = bool(writes[t])
                recency.move_to_end(line)
            where[p] = line
            count_to(line, 1)
            least = 1
        if learn and (t + 1) % ew == 0:
            k = (w, tuple(mispred), misses)
            got = memo.get(k)
            if got is None:
                new = _adjust(w, mispred, misses, pw, learner)
                got = memo[k] = (new, _choice(new))
            if got[0] != w:
                w = got[0]
                adjust_at.append(t)
                traj.append(w)
            chosen = got[1]
            pred = [[] for _ in range(N_EXPERTS)]
            mispred = [0] * N_EXPERTS
            misses = 0
    return ShardReplay(hit, evict, expert, wback,
                       np.asarray(adjust_at, np.int64),
                       np.asarray(traj, np.float32).reshape(-1, N_EXPERTS))


def owners(pages: np.ndarray, n_shards: int, mapping: str) -> np.ndarray:
    if mapping != "block":
        raise ValueError(f"the reference knows block mapping only, not "
                         f"{mapping}")
    block = -(-(int(pages.max()) + 1) // n_shards)
    return np.minimum(pages.astype(np.int64) // block, n_shards - 1)


def window_ids(times: np.ndarray, n_windows: int,
               window_dt: float) -> np.ndarray:
    t = np.asarray(times, np.float64)
    t = t - t.min()
    return np.minimum(np.floor(t / window_dt), n_windows - 1).astype(np.int64)


class Replay:
    """A whole trace replayed shard by shard, once, over its first
    ``upto`` requests; :meth:`counters` then gives the counters of any
    shorter prefix, as a replay that stopped there would."""

    def __init__(self, pages, is_write, times, *, n_shards: int, mapping: str,
                 n_lines: int, n_windows: int, window_dt: float,
                 policy: str = "ws", learner: Learner = Learner(),
                 upto: Optional[int] = None):
        pages = np.asarray(pages)
        n = len(pages) if upto is None else int(upto)
        own = owners(pages, n_shards, mapping)[:n]
        self.win = window_ids(times, n_windows, window_dt)[:n]
        self.is_write = np.asarray(is_write, bool)[:n]
        self.n_windows = n_windows
        self.idx = [np.nonzero(own == s)[0] for s in range(n_shards)]
        rnd = random_lines(max((len(i) for i in self.idx), default=0),
                           n_lines)
        self.shards = [replay_shard(pages[i].tolist(),
                                    self.is_write[i].tolist(), n_lines,
                                    rnd, policy=policy, learner=learner)
                       for i in self.idx]

    def counters(self, prefix: int) -> dict:
        """``{shard: {counter: array}}`` of the trace's first ``prefix``
        requests."""
        out = {}
        W = self.n_windows
        for s, (idx, r) in enumerate(zip(self.idx, self.shards)):
            m = int(np.searchsorted(idx, prefix))
            win = self.win[idx[:m]]

            def count(mask=None):
                sel = win if mask is None else win[mask[:m]]
                return np.bincount(sel, minlength=W).astype(np.int64)

            req, hits = count(), count(r.hit)
            ctr = {"win_requests": req, "win_hits": hits,
                   "win_misses": req - hits,
                   "win_prefetch_hits": np.zeros(W, np.int64),
                   "win_tier2_reads": req - hits,
                   "win_tier2_writes": count(r.writeback),
                   "win_evictions": count(r.evict)}
            use = np.zeros((W, N_EXPERTS), np.int64)
            ev = r.evict[:m]
            np.add.at(use, (win[ev], r.expert[:m][ev].astype(np.int64)), 1)
            ctr["win_expert_use"] = use
            for name in TOTALS:
                if "win_" + name in ctr:
                    ctr[name] = ctr["win_" + name].sum()
            ctr["writes"] = np.int64(self.is_write[idx[:m]].sum())
            ctr["reads"] = ctr["requests"] - ctr["writes"]
            # The weights after each window's last request.
            last = np.full(W, -1, np.int64)
            last[win] = np.arange(m)
            k = np.searchsorted(r.adjust_at, last, side="right") - 1
            table = np.vstack([np.full((1, N_EXPERTS), UNIFORM, np.float32),
                               r.weights])
            ww = table[k + 1].astype(np.float64)
            ctr["win_weights"] = np.where((last >= 0)[:, None], ww, 0.0)
            out[s] = ctr
        return out


def mismatches(program, ref: dict, weight_tol: float) -> dict:
    """Cells in which a program's counters (``[S]`` totals and ``[S, W...]``
    windowed arrays as attributes) differ from the reference's: counters
    exactly, weights beyond ``weight_tol`` (absolute). ``{"counters": n,
    "weights": n, "cells": n compared, "weight_err": largest difference}``."""
    bad_c = bad_w = cells = 0
    err = 0.0
    for s, ctr in ref.items():
        for name in TOTALS + WINDOWED:
            got = np.asarray(getattr(program, name))
            want = np.asarray(ctr[name])
            if got.shape[1:] != want.shape or s >= len(got):
                bad_c += want.size
            else:
                bad_c += int(np.sum(got[s] != want))
            cells += want.size
        got = np.asarray(program.win_weights, np.float64)
        want = ctr["win_weights"]
        if got.shape[1:] != want.shape or s >= len(got):
            bad_w += want.size
        else:
            diff = np.abs(got[s] - want)
            bad_w += int(np.sum(~(diff <= weight_tol)))
            err = max(err, float(np.max(diff, initial=0.0)))
        cells += want.size
    return {"counters": bad_c, "weights": bad_w, "cells": cells,
            "weight_err": err}
