"""The two-tier storage engine (paper §III), as a jitted ``lax.scan``.

Semantics per request (page, is_write), faithful to the paper:

1. **Lookup** in the fully-associative tier-1 cache. A hit updates the
   timestamp (LRU), frequency counter (LFU) and dirty bit.
2. A **miss** first probes the prefetch buffer; a buffered page is promoted
   to the cache without a tier-2 access. Otherwise the page is fetched from
   tier 2 (one tier-2 read).
3. Insertion uses a free line if one exists; otherwise **GetVictim**
   (Algorithm 1) selects the eviction expert by probability, every expert's
   proposal is recorded in its prediction vector, and the chosen victim is
   evicted (a dirty victim costs one tier-2 write-back).
4. The **stream identifier** observes the miss stream and issues prefetches
   into free buffer slots ("page misses are prioritized over prefetches").
5. Every ``epoch_width`` iterations, **WeightAdjust** (Algorithm 2) runs and
   prediction vectors are cleared.

The engine is branchless (computed-both-paths + select) so it vmaps across
distributed cache shards (paper's per-process caches). Tier-2 is counted
here (reads / write-backs); converting counts to time is the queuing and
device-model layer (:mod:`repro.core.queuing`, :mod:`repro.core.device_models`).

**One engine, one reference.** Every path (one-shot rows, the sweep's
megabatch, the chunked replay) runs its request loop through
:func:`repro.kernels.ref.cache_scan_ref`. The per-step scan it was derived
from (:func:`_step`, :func:`_fold`) is kept as :func:`run_stream_reference`,
the reference the engine is tested against bit for bit.

**Windowed telemetry.** The scan folds every per-request outcome into
``n_windows`` accumulator slots carried through the loop (scatter-add by the
request's time-window id) instead of materializing ``[T]`` per-request
outputs — memory is O(n_windows), not O(stream length), on the megabatch
sweep path. A request's window is either its **wall-clock time bin**
(``timestamps``/``window_dt`` operands: bin = ``t // window_dt``, clipped
into the last bin — per-window arrival rates are then *measured*, not flat
by construction) or, on the historic request-index path, its *global*
stream position ``g`` mapped to ``g * n_windows // T``. Padding positions
carry the out-of-range id ``n_windows`` (timestamp ``-1`` on the timed
path) and are dropped by the scatter, so windowed counters count real
requests only and are bit-identical across padding/bucketing choices.
Whole-stream counters are still accumulated separately (pads included,
corrected by :func:`correct_padded_stats` exactly as before), so windowed
totals reconcile exactly: ``win_*.sum(-1)`` equals every corrected counter.
The windowed accumulators also resolve the online learner over time:
``win_expert_use`` counts evictions per expert per window and
``win_weights`` snapshots the expert weights at each window's last real
request (zeros where a window saw none), so adaptation at phase boundaries
is observable.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import online_learning as ol
from repro.core import prefetch as pfm
from repro.core.mapping import page_to_shard
from repro.kernels.ref import (
    NOISE_TABLE_MAX,
    cache_scan_noise,
    cache_scan_ref,
    vary_like,
)
from repro.storage.cache_state import CacheState, init_cache

__all__ = [
    "StoreConfig",
    "StoreHyper",
    "StoreState",
    "StreamStats",
    "run_stream",
    "run_stream_reference",
    "run_stream_chunked",
    "run_distributed",
    "partition_streams",
    "partition_window_ids",
    "stream_window_ids",
    "timestamp_window_ids",
    "correct_padded_stats",
    "init_stream_carry",
    "stream_chunk_engine",
    "stream_stats_from_carry",
    "stream_compile_count",
    "reset_stream_compile_count",
]

# Traced policy selector convention: ws (online learning) = -1, experts by
# their index in ol.EXPERTS. Part of the public contract (sweep stacking).
WS_POLICY_IDX = -1
POLICY_TO_IDX = {"ws": WS_POLICY_IDX,
                 **{name: i for i, name in enumerate(ol.EXPERTS)}}


class StoreHyper(NamedTuple):
    """The scalar online-learning knobs of a :class:`StoreConfig`, as traced
    operands of the engine rather than compile-time constants.

    Points of a sweep that differ only in these fields share one compiled
    engine: the sweep stacks ``StoreHyper`` leaves on a vmap axis next to the
    stream data instead of splitting per-config jit caches. ``policy_idx``
    follows :data:`POLICY_TO_IDX` (``-1`` = weight-sharing online learning).
    """

    alpha: jnp.ndarray      # f32[] weight-share rate
    beta: jnp.ndarray       # f32[] multiplicative penalty base
    threshold: jnp.ndarray  # f32[] misprediction threshold fraction
    policy_idx: jnp.ndarray  # i32[] expert index, -1 = online learning


@dataclasses.dataclass(frozen=True)
class StoreConfig:
    n_lines: int = 64
    policy: str = "ws"  # ws | lru | lfu | random
    epoch_width: int = 4
    alpha: float = 0.5
    beta: float = 0.7
    threshold: float = 0.25
    pred_cap: int = 64
    prefetch: bool = False
    prefetch_width: int = 4
    prefetch_buf: int = 16

    def ol_config(self) -> ol.OLConfig:
        return ol.OLConfig(
            epoch_width=self.epoch_width,
            alpha=self.alpha,
            beta=self.beta,
            threshold=self.threshold,
            pred_cap=self.pred_cap,
        )

    def policy_idx(self) -> Optional[int]:
        if self.policy == "ws":
            return None
        return ol.EXPERTS.index(self.policy)

    def hyper(self) -> StoreHyper:
        """This config's scalar knobs as concrete :class:`StoreHyper` leaves."""
        try:
            idx = POLICY_TO_IDX[self.policy]
        except KeyError:
            raise ValueError(
                f"unknown policy {self.policy!r}; "
                f"options: {sorted(POLICY_TO_IDX)}"
            ) from None
        return StoreHyper(
            alpha=jnp.asarray(self.alpha, jnp.float32),
            beta=jnp.asarray(self.beta, jnp.float32),
            threshold=jnp.asarray(self.threshold, jnp.float32),
            policy_idx=jnp.asarray(idx, jnp.int32),
        )

    def static_config(self) -> "StoreConfig":
        """The structural residue of this config: every field that shapes the
        compiled engine (array sizes, scan structure), with the traced knobs
        (:class:`StoreHyper` fields) reset to class defaults. Two configs with
        equal ``static_config()`` share one compiled engine."""
        defaults = {
            f.name: f.default
            for f in dataclasses.fields(StoreConfig)
            if f.name in ("alpha", "beta", "threshold", "policy")
        }
        return dataclasses.replace(self, **defaults)


class StoreState(NamedTuple):
    cache: CacheState
    ols: ol.OLState
    pf: pfm.PrefetchState
    t: jnp.ndarray          # int32 iteration counter
    key: jax.Array          # PRNG for the Random expert


class StreamStats(NamedTuple):
    """Aggregated counters for a processed request stream.

    Scalar fields are whole-stream totals (padding included, exactly the
    historic semantics); ``win_*`` fields resolve the same counters over
    ``n_windows`` time windows of the stream (last axis; padding excluded
    by construction, see the module docstring).
    """

    requests: jnp.ndarray
    hits: jnp.ndarray
    misses: jnp.ndarray
    prefetch_hits: jnp.ndarray   # misses serviced from the prefetch buffer
    tier2_reads: jnp.ndarray     # demand fetches + prefetch fetches
    tier2_writes: jnp.ndarray    # dirty write-backs
    evictions: jnp.ndarray
    expert_use: jnp.ndarray      # int32[E] evictions issued per expert
    final_weights: jnp.ndarray   # f32[E]
    # Windowed telemetry: int32[..., n_windows], real (unpadded) requests.
    win_requests: jnp.ndarray
    win_hits: jnp.ndarray
    win_misses: jnp.ndarray
    win_prefetch_hits: jnp.ndarray
    win_tier2_reads: jnp.ndarray
    win_tier2_writes: jnp.ndarray
    win_evictions: jnp.ndarray
    # Windowed online-learning telemetry: per-window evictions per expert
    # (int32[..., n_windows, E]) and the expert weights at each window's
    # last real request (f32[..., n_windows, E]; zeros where the window saw
    # no real request).
    win_expert_use: jnp.ndarray
    win_weights: jnp.ndarray

    @property
    def miss_rate(self):
        return self.misses / jnp.maximum(self.requests, 1)

    @property
    def n_windows(self) -> int:
        return self.win_requests.shape[-1]


def init_store(cfg: StoreConfig, seed: int = 0) -> StoreState:
    return StoreState(
        cache=init_cache(cfg.n_lines),
        ols=ol.init_ol(cfg.ol_config()),
        pf=pfm.init_prefetch(cfg.prefetch_buf),
        t=jnp.zeros((), jnp.int32),
        key=jax.random.PRNGKey(seed),
    )


def _step(cfg: StoreConfig, hyper: StoreHyper, state: StoreState, req,
          real=True):
    # ``cfg`` carries only structural knobs here (shapes, scan layout,
    # prefetcher wiring); the scalar learning knobs come from ``hyper`` so
    # they may be traced (one compile serves a grid of settings). ``real``
    # is False at padding positions (window id == n_windows).
    ol_cfg = ol.OLConfig(
        epoch_width=cfg.epoch_width,
        alpha=hyper.alpha,
        beta=hyper.beta,
        threshold=hyper.threshold,
        pred_cap=cfg.pred_cap,
    )
    page, is_write = req
    page = page.astype(jnp.int32)
    cache, ols, pf = state.cache, state.ols, state.pf
    t = state.t
    key, vkey = jax.random.split(state.key)

    # --- 1. lookup -------------------------------------------------------
    match = cache.valid & (cache.tags == page)
    hit = jnp.any(match)
    hit_idx = jnp.argmax(match).astype(jnp.int32)

    # Hit path metadata updates.
    ts_hit = cache.ts.at[hit_idx].set(t)
    freq_hit = cache.freq.at[hit_idx].add(1)
    dirty_hit = cache.dirty.at[hit_idx].set(cache.dirty[hit_idx] | is_write)

    # --- 2/3. miss path ---------------------------------------------------
    miss = ~hit
    ols = jax.tree.map(
        lambda new, old: jnp.where(miss, new, old), ol.note_miss(ols, page), ols
    )
    # Prefetch buffer probe (only meaningful on a miss).
    pf_probed, in_buf = pfm.probe_and_promote(pf, page)
    pf = jax.tree.map(lambda new, old: jnp.where(miss, new, old), pf_probed, pf)
    promoted = miss & in_buf

    free = ~cache.valid
    has_free = jnp.any(free)
    free_idx = jnp.argmax(free).astype(jnp.int32)

    # GetVictim: every expert proposes; chosen expert's proposal is used.
    proposals = ol.propose_victims(cache, vkey)          # int32[E] line idx
    victim_pages = cache.tags[proposals]                  # int32[E]
    chosen = ol.choose_expert(ols, hyper.policy_idx)
    victim_idx = proposals[chosen]

    evict = miss & ~has_free
    slot = jnp.where(has_free, free_idx, victim_idx)
    writeback = evict & cache.dirty[slot]

    # Record prediction vectors only when an eviction actually happens.
    ols_pred = ol.record_predictions(ols, ol_cfg, victim_pages)
    ols = jax.tree.map(lambda new, old: jnp.where(evict, new, old), ols_pred, ols)
    ols = ols._replace(chosen=jnp.where(evict, chosen, ols.chosen[0])[None])

    # Insert the missed page.
    tags_miss = cache.tags.at[slot].set(page)
    valid_miss = cache.valid.at[slot].set(True)
    dirty_miss = cache.dirty.at[slot].set(is_write)
    freq_miss = cache.freq.at[slot].set(1)
    ts_miss = cache.ts.at[slot].set(t)

    cache = CacheState(
        tags=jnp.where(miss, tags_miss, cache.tags),
        valid=jnp.where(miss, valid_miss, cache.valid),
        dirty=jnp.where(miss, dirty_miss, jnp.where(hit, dirty_hit, cache.dirty)),
        freq=jnp.where(miss, freq_miss, jnp.where(hit, freq_hit, cache.freq)),
        ts=jnp.where(miss, ts_miss, jnp.where(hit, ts_hit, cache.ts)),
    )

    # --- 4. stream identifier + prefetch issue ----------------------------
    if cfg.prefetch:
        pf_obs = pfm.observe_miss(pf, page)
        pf = jax.tree.map(lambda new, old: jnp.where(miss, new, old), pf_obs, pf)
        n_before = pf.issued
        pf_issued = pfm.issue_prefetches(
            pf, page, cache.tags, cache.valid, cfg.prefetch_width
        )
        pf = jax.tree.map(lambda new, old: jnp.where(miss, new, old), pf_issued, pf)
        prefetch_fetches = jnp.where(miss, pf.issued - n_before, 0)
    else:
        prefetch_fetches = jnp.zeros((), jnp.int32)

    # --- 5. epoch boundary -------------------------------------------------
    # WeightAdjust fires only for the weight-sharing policy (policy_idx < 0);
    # fixed-expert baselines keep their initial weights, exactly as when the
    # policy was a compile-time constant. Padding never fires it: pads are
    # pure hits that leave the learner state alone, but an epoch boundary
    # among them would renormalize unchanged weights, which is not
    # idempotent in f32 — so final_weights would depend on the pad length.
    epoch_end = (t + 1) % cfg.epoch_width == 0
    is_ws = hyper.policy_idx < 0
    ols_adj = ol.weight_adjust(ols, ol_cfg)
    ols = jax.tree.map(
        lambda new, old: jnp.where(epoch_end & is_ws & real, new, old),
        ols_adj, ols,
    )

    out = dict(
        hit=hit,
        miss=miss,
        prefetch_hit=promoted,
        tier2_read=(miss & ~promoted).astype(jnp.int32) + prefetch_fetches,
        tier2_write=writeback.astype(jnp.int32),
        evict=evict,
        chosen=jnp.where(evict, chosen, -1),
    )
    return StoreState(cache=cache, ols=ols, pf=pf, t=t + 1, key=key), out


class _Accum(NamedTuple):
    """Scan-carried counter accumulators: scalar whole-stream totals plus
    ``n_windows`` windowed slots (pads scatter to the out-of-range id and
    are dropped)."""

    hits: jnp.ndarray
    misses: jnp.ndarray
    prefetch_hits: jnp.ndarray
    tier2_reads: jnp.ndarray
    tier2_writes: jnp.ndarray
    evictions: jnp.ndarray
    expert_use: jnp.ndarray      # int32[E]
    win_requests: jnp.ndarray    # int32[W]
    win_hits: jnp.ndarray
    win_misses: jnp.ndarray
    win_prefetch_hits: jnp.ndarray
    win_tier2_reads: jnp.ndarray
    win_tier2_writes: jnp.ndarray
    win_evictions: jnp.ndarray
    win_expert_use: jnp.ndarray  # int32[W, E]
    win_weights: jnp.ndarray     # f32[W, E]


def _init_accum(n_windows: int) -> _Accum:
    zero = jnp.zeros((), jnp.int32)
    zw = jnp.zeros((n_windows,), jnp.int32)
    return _Accum(
        hits=zero, misses=zero, prefetch_hits=zero, tier2_reads=zero,
        tier2_writes=zero, evictions=zero,
        expert_use=jnp.zeros((ol.N_EXPERTS,), jnp.int32),
        win_requests=zw, win_hits=zw, win_misses=zw, win_prefetch_hits=zw,
        win_tier2_reads=zw, win_tier2_writes=zw, win_evictions=zw,
        win_expert_use=jnp.zeros((n_windows, ol.N_EXPERTS), jnp.int32),
        win_weights=jnp.zeros((n_windows, ol.N_EXPERTS), jnp.float32),
    )


def _fold(acc: _Accum, out: dict, win: jnp.ndarray,
          weights: jnp.ndarray) -> _Accum:
    """Fold one request's outcome into the accumulators. ``win`` is the
    request's window id; ``win == n_windows`` (padding) drops out of the
    windowed scatter but still counts toward the scalar totals.
    ``weights`` is the post-step expert weight vector: overwriting the
    window's row every step leaves each row holding the weights at that
    window's *last* real request."""
    hit = out["hit"].astype(jnp.int32)
    miss = out["miss"].astype(jnp.int32)
    pfh = out["prefetch_hit"].astype(jnp.int32)
    t2r = out["tier2_read"].astype(jnp.int32)
    t2w = out["tier2_write"].astype(jnp.int32)
    ev = out["evict"].astype(jnp.int32)
    expert = jnp.where(out["evict"], out["chosen"], 0)
    return _Accum(
        hits=acc.hits + hit,
        misses=acc.misses + miss,
        prefetch_hits=acc.prefetch_hits + pfh,
        tier2_reads=acc.tier2_reads + t2r,
        tier2_writes=acc.tier2_writes + t2w,
        evictions=acc.evictions + ev,
        expert_use=acc.expert_use.at[expert].add(ev),
        win_requests=acc.win_requests.at[win].add(1, mode="drop"),
        win_hits=acc.win_hits.at[win].add(hit, mode="drop"),
        win_misses=acc.win_misses.at[win].add(miss, mode="drop"),
        win_prefetch_hits=acc.win_prefetch_hits.at[win].add(pfh, mode="drop"),
        win_tier2_reads=acc.win_tier2_reads.at[win].add(t2r, mode="drop"),
        win_tier2_writes=acc.win_tier2_writes.at[win].add(t2w, mode="drop"),
        win_evictions=acc.win_evictions.at[win].add(ev, mode="drop"),
        win_expert_use=acc.win_expert_use.at[win, expert].add(ev,
                                                              mode="drop"),
        # A select, not a scatter-set: compiled for a TPU v5e, the scatter-set
        # left all-zero weight rows for the last 46 points of a
        # [288, 4, 8192] megabatch, with buffer donation on and off, while
        # every scatter-add counter was right (repro: PERF.md, open
        # questions).
        win_weights=jnp.where(
            (jnp.arange(acc.win_weights.shape[0]) == win)[:, None],
            weights, acc.win_weights),
    )


def stream_window_ids(n: int, n_windows: int) -> np.ndarray:
    """Window id per stream position: position ``g`` of an ``n``-long stream
    belongs to window ``g * n_windows // n`` (equal request-count slices of
    the global timeline)."""
    if n_windows < 1:
        raise ValueError("n_windows must be >= 1")
    if n == 0:
        return np.zeros(0, np.int32)
    return (np.arange(n, dtype=np.int64) * n_windows // n).astype(np.int32)


def timestamp_window_ids(times: np.ndarray, n_windows: int,
                         window_dt: float) -> np.ndarray:
    """Wall-clock window id per request: arrival time ``t`` belongs to bin
    ``t // window_dt``, clipped into the last bin (arrivals past the nominal
    horizon still count — windowed counters always reconcile exactly with
    the whole-stream totals). Negative times mark padding and map to the
    dropped id ``n_windows``.

    Binning happens host-side in float64: an f32 ratio loses whole-integer
    resolution past ~2^24, so multi-hour streamed traces (epoch-style or
    simply long horizons) would drift across bin edges. The int32 ids are
    what the engine consumes (``window_ids=`` operand), so the scan itself
    never touches arrival-time floats."""
    if n_windows < 1:
        raise ValueError("n_windows must be >= 1")
    if window_dt <= 0:
        raise ValueError("window_dt must be positive")
    t = np.asarray(times, np.float64)
    # Clip in float space *before* the integer cast: a ratio beyond int32
    # (epoch-style absolute times) must saturate into the last bin, not
    # wrap.
    ids = np.clip(t / np.float64(window_dt), 0,
                  np.float64(n_windows - 1)).astype(np.int32)
    return np.where(t >= 0, ids, n_windows).astype(np.int32)


def _window_operands(cfg, pages, is_write, hyper, n_windows, window_ids,
                     timestamps, window_dt):
    """The one-shot engines' operands: ``(pages, is_write, hyper,
    window_ids)`` as device arrays, the window of each request resolved as
    :func:`run_stream` describes."""
    pages = jnp.asarray(pages, jnp.int32)
    is_write = jnp.asarray(is_write, bool)
    if hyper is None:
        hyper = cfg.hyper()
    if timestamps is not None:
        if window_dt is None:
            raise ValueError("timestamps need a window_dt (seconds per bin)")
        ts = jnp.asarray(timestamps, jnp.float32)
        wdt = jnp.asarray(window_dt, jnp.float32)
        # Float-space clip before the cast (see timestamp_window_ids).
        ids = jnp.clip(ts / wdt, 0.0, float(n_windows - 1)).astype(jnp.int32)
        window_ids = jnp.where(ts >= 0, ids, n_windows)
    elif window_ids is None:
        window_ids = stream_window_ids(pages.shape[0], n_windows)
    return pages, is_write, hyper, jnp.asarray(window_ids, jnp.int32)


def _stream_stats(requests, acc: _Accum, weights) -> StreamStats:
    """:class:`StreamStats` from an engine's accumulators and final expert
    weights."""
    return StreamStats(
        requests=jnp.asarray(requests, jnp.int32),
        hits=acc.hits,
        misses=acc.misses,
        prefetch_hits=acc.prefetch_hits,
        tier2_reads=acc.tier2_reads,
        tier2_writes=acc.tier2_writes,
        evictions=acc.evictions,
        expert_use=acc.expert_use,
        final_weights=weights,
        win_requests=acc.win_requests,
        win_hits=acc.win_hits,
        win_misses=acc.win_misses,
        win_prefetch_hits=acc.win_prefetch_hits,
        win_tier2_reads=acc.win_tier2_reads,
        win_tier2_writes=acc.win_tier2_writes,
        win_evictions=acc.win_evictions,
        win_expert_use=acc.win_expert_use,
        win_weights=acc.win_weights,
    )


def run_stream(
    cfg: StoreConfig,
    pages: jnp.ndarray,
    is_write: jnp.ndarray,
    *,
    seed: int = 0,
    hyper: Optional[StoreHyper] = None,
    unroll: int = 1,
    n_windows: int = 1,
    window_ids: Optional[jnp.ndarray] = None,
    timestamps: Optional[jnp.ndarray] = None,
    window_dt=None,
) -> StreamStats:
    """Process a request stream through one tier-1 shard. Jitted scan.

    The request loop is :func:`repro.kernels.ref.cache_scan_ref`, the
    tier-1 engine on every platform: one-hot elementwise state updates,
    with the Random expert's draws hoisted into a
    :func:`~repro.kernels.ref.cache_scan_noise` table when the row's
    ``length * n_lines`` fits :data:`~repro.kernels.ref.NOISE_TABLE_MAX`
    and drawn in the loop otherwise (the same draws either way). It is
    bit-exact against :func:`run_stream_reference`.

    ``hyper`` overrides the scalar learning knobs of ``cfg`` with (possibly
    traced) :class:`StoreHyper` operands — the sweep engine's third vmap
    axis. When traced hypers are supplied, only ``cfg.static_config()``
    shapes the computation. ``unroll`` chunks the per-request scan body
    (semantics-preserving; larger values trade compile time for fewer loop
    iterations on wide batches).

    ``n_windows`` resolves the counters over time windows (carried
    accumulators — O(n_windows) memory, no per-request outputs). The window
    of a request is, in precedence order:

    - its wall-clock time bin ``t // window_dt`` when ``timestamps``
      (f32[T] arrival seconds, ``-1`` marking padding) and ``window_dt``
      are given — both are *data* operands (traced, so one compile serves
      any timestamp layout and window duration; only ``n_windows`` is
      structural), and arrivals past ``n_windows * window_dt`` clip into
      the last bin;
    - an explicit ``window_ids`` assignment (int32[T], values in
      [0, n_windows]; ``n_windows`` marks padding, dropped from the
      windowed counters);
    - by default, equal request-count slices of this stream's own length.
    """
    pages, is_write, hyper, window_ids = _window_operands(
        cfg, pages, is_write, hyper, n_windows, window_ids, timestamps,
        window_dt)
    state0 = init_store(cfg, seed)
    length = pages.shape[0]
    noise = (cache_scan_noise(state0.key, length, cfg.n_lines)
             if length * cfg.n_lines <= NOISE_TABLE_MAX else None)
    final, acc = cache_scan_ref(
        state0, _init_accum(n_windows), pages, is_write, window_ids, hyper,
        noise, epoch_width=cfg.epoch_width, pred_cap=cfg.pred_cap,
        prefetch=cfg.prefetch, prefetch_width=cfg.prefetch_width,
        n_windows=n_windows, unroll=unroll)
    return _stream_stats(length, acc, final.ols.weights)


def run_stream_reference(
    cfg: StoreConfig,
    pages: jnp.ndarray,
    is_write: jnp.ndarray,
    *,
    seed: int = 0,
    hyper: Optional[StoreHyper] = None,
    n_windows: int = 1,
    window_ids: Optional[jnp.ndarray] = None,
    timestamps: Optional[jnp.ndarray] = None,
    window_dt=None,
    unroll: int = 1,
) -> StreamStats:
    """The per-step reference the tier-1 engine is tested against:
    :func:`run_stream`'s arguments and result, computed by the original
    ``lax.scan`` over :func:`_step` (gathers, scatters and an in-loop PRNG
    split per request) and :func:`_fold`. No production path calls it."""
    pages, is_write, hyper, window_ids = _window_operands(
        cfg, pages, is_write, hyper, n_windows, window_ids, timestamps,
        window_dt)

    def scan_fn(carry, req):
        state, acc = carry
        page, write, win = req
        state, out = _step(cfg, hyper, state, (page, write),
                           real=win < n_windows)
        return (state, _fold(acc, out, win, state.ols.weights)), None

    carry0 = (init_store(cfg, seed), _init_accum(n_windows))
    (final, acc), _ = jax.lax.scan(
        scan_fn, vary_like(carry0, pages), (pages, is_write, window_ids),
        unroll=unroll,
    )
    return _stream_stats(pages.shape[0], acc, final.ols.weights)


def partition_streams(
    pages: np.ndarray,
    is_write: np.ndarray,
    *,
    n_shards: int,
    mapping: str = "block",
    n_pages: Optional[int] = None,
    cap: Optional[int] = None,
    n_windows: Optional[int] = None,
    window_ids: Optional[np.ndarray] = None,
    times: Optional[np.ndarray] = None,
    owner: Optional[np.ndarray] = None,
):
    """Partition a request stream into per-shard substreams (§III mapping).

    Each shard's substream is padded to ``cap`` (default: the max shard load)
    with repeats of its own last page — pure hits, so every counter except
    ``requests``/``hits`` is unaffected and those two are correctable from
    the pad length. Returns ``(sh_pages [S, cap], sh_writes [S, cap],
    counts [S], owner [n])``; with ``n_windows`` set, additionally returns
    ``sh_win [S, cap]`` window ids (see :func:`partition_window_ids`),
    reusing this call's shard sort instead of re-sorting. ``window_ids``
    (int32[n], values in [0, n_windows]) overrides the default equal-count
    ids with precomputed *global* per-request window assignments — the
    wall-clock paths pass :func:`timestamp_window_ids` output here, so the
    float64 host binning is the only time→window mapping and the engine
    only ever sees int ids. With ``times`` set (wall-clock arrival seconds,
    float[n]), additionally returns ``sh_times [S, cap]`` float32 per-shard
    arrival timestamps (padding positions carry ``-1``, which the engine's
    in-graph time binning drops).

    ``owner`` overrides the §III mapping with a precomputed per-request
    owner array (int[n]) — the fault-injection path passes owners already
    rerouted around down shards (:func:`repro.core.mapping.apply_failover`).
    """
    pages = np.asarray(pages)
    is_write = np.asarray(is_write, bool)
    n_pages = int(n_pages if n_pages is not None else (pages.max() + 1))
    if owner is None:
        owner = np.asarray(
            page_to_shard(jnp.asarray(pages), n_shards, n_pages, mapping)
        )
    else:
        owner = np.asarray(owner)
        if owner.shape != pages.shape:
            raise ValueError("owner must align with the request stream")
    counts = np.bincount(owner, minlength=n_shards)
    cap = int(cap if cap is not None else max(int(counts.max()), 1))
    if cap < counts.max():
        raise ValueError(f"cap={cap} < max shard load {int(counts.max())}")
    # Argsort-based scatter (stable sort preserves per-shard request order):
    # request j lands at row owner[j], column = its rank within its shard.
    order, row, col = _shard_positions(owner, counts)
    sh_pages = np.zeros((n_shards, cap), np.int32)
    sh_writes = np.zeros((n_shards, cap), bool)
    sh_pages[row, col] = pages[order]
    sh_writes[row, col] = is_write[order]
    # Pad each shard with its own last page — pure hits (empty shards keep
    # page 0, whose first access is the phantom miss correct_padded_stats
    # zeroes out).
    last = sh_pages[np.arange(n_shards), np.maximum(counts - 1, 0)]
    pad = np.arange(cap)[None, :] >= counts[:, None]
    sh_pages = np.where(pad, last[:, None], sh_pages)
    out = [sh_pages, sh_writes, counts, owner]
    if window_ids is not None:
        if n_windows is None:
            raise ValueError("window_ids need n_windows (the dropped pad id)")
        window_ids = np.asarray(window_ids, np.int32)
        if window_ids.shape != owner.shape:
            raise ValueError("window_ids must align with the request stream")
        sh_win = np.full((n_shards, cap), n_windows, np.int32)
        sh_win[row, col] = window_ids[order]
        out.append(sh_win)
    elif n_windows is not None:
        out.append(_scatter_window_ids(owner, n_shards, n_windows, cap,
                                       order, row, col))
    if times is not None:
        times = np.asarray(times, np.float32)
        if times.shape != owner.shape:
            raise ValueError("times must align with the request stream")
        sh_times = np.full((n_shards, cap), -1.0, np.float32)
        sh_times[row, col] = times[order]
        out.append(sh_times)
    return tuple(out)


def _shard_positions(owner: np.ndarray, counts: np.ndarray):
    """(order, row, col) scatter coordinates: the stable shard-sort of the
    request indices (original order preserved within each shard), and for
    each sorted request its owning shard and rank within that shard."""
    order = np.argsort(owner, kind="stable")
    starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
    row = owner[order]
    col = np.arange(owner.shape[0]) - starts[row]
    return order, row, col


def _scatter_window_ids(
    owner, n_shards: int, n_windows: int, cap: int, order, row, col
) -> np.ndarray:
    """Scatter global window ids to per-shard positions (pads keep the
    dropped id ``n_windows``) using precomputed shard-sort coordinates."""
    gwin = stream_window_ids(owner.shape[0], n_windows)
    sh_win = np.full((n_shards, cap), n_windows, np.int32)
    sh_win[row, col] = gwin[order]
    return sh_win


def partition_window_ids(
    owner: np.ndarray,
    counts: np.ndarray,
    cap: int,
    n_windows: int,
) -> np.ndarray:
    """Per-shard window-id arrays aligned with :func:`partition_streams`.

    Returns int32 ``[n_shards, cap]``: real positions carry their request's
    *global* time window (``g * n_windows // n`` for global position ``g``),
    padding positions carry the out-of-range id ``n_windows`` so the
    engine's windowed scatter drops them. Windowed counters are therefore
    independent of padding/bucketing choices. (The internal partitioning
    paths use ``partition_streams(..., n_windows=)`` instead, which reuses
    one shard sort for streams and window ids.)
    """
    owner = np.asarray(owner)
    counts = np.asarray(counts)
    order, row, col = _shard_positions(owner, counts)
    return _scatter_window_ids(owner, counts.shape[0], n_windows, cap,
                               order, row, col)


def correct_padded_stats(stats: StreamStats, counts, cap: int) -> StreamStats:
    """Undo padding artifacts in per-shard stats from padded substreams
    (see :func:`partition_streams`): padded requests are pure hits on each
    shard's last page (subtracted from ``hits``), and a shard with no real
    requests ran a pure-padding stream whose first access is a phantom
    miss (all its counters are zeroed).

    The windowed counters need no correction at all: real requests carry
    their own window ids, pads (including the whole stream of an empty
    shard, phantom miss included) scatter to the dropped out-of-range id,
    so per-window counters already count exactly the real requests."""
    pad = jnp.asarray(cap - np.asarray(counts), jnp.int32)
    nonempty = jnp.asarray(np.asarray(counts) > 0)
    zero = jnp.zeros((), jnp.int32)
    return stats._replace(
        requests=jnp.asarray(counts, jnp.int32),
        hits=jnp.maximum(stats.hits - pad, 0),
        misses=jnp.where(nonempty, stats.misses, zero),
        prefetch_hits=jnp.where(nonempty, stats.prefetch_hits, zero),
        tier2_reads=jnp.where(nonempty, stats.tier2_reads, zero),
        tier2_writes=jnp.where(nonempty, stats.tier2_writes, zero),
        evictions=jnp.where(nonempty, stats.evictions, zero),
    )


def run_distributed(
    cfg: StoreConfig,
    pages: np.ndarray,
    is_write: np.ndarray,
    *,
    n_shards: int,
    mapping: str = "block",
    n_pages: Optional[int] = None,
    seed: int = 0,
    n_windows: int = 1,
    timestamps: Optional[np.ndarray] = None,
    window_dt: Optional[float] = None,
    owner: Optional[np.ndarray] = None,
):
    """Distributed tier-1 cache: requests partitioned to per-shard caches by
    the §III mapping policy, shards processed by ``vmap`` (the paper's
    per-process caches are independent — no replication, no migration).

    Returns ``(per_shard_stats, shard_request_counts)``; per-shard stats are
    padded streams, so counters are exact but ``requests`` reflects real
    (unpadded) request counts. ``n_windows`` resolves every counter over
    time windows of the *global* request stream (``win_*`` fields, shape
    ``[n_shards, n_windows]``): wall-clock bins of ``window_dt`` seconds
    when ``timestamps`` (arrival seconds, float[n]) are supplied, equal
    request-count slices otherwise. ``owner`` optionally overrides the
    mapping policy with precomputed (e.g. failover-remapped) owners.
    """
    if timestamps is not None:
        if window_dt is None:
            raise ValueError("timestamps need a window_dt (seconds per bin)")
        # Bin host-side in float64 (timestamp_window_ids) and hand the
        # engine int32 ids: f32 arrival times lose whole-second resolution
        # past ~2^24, so long-horizon traces would drift across bin edges.
        gwin = timestamp_window_ids(timestamps, n_windows, window_dt)
        sh_pages, sh_writes, counts, owner, sh_win = partition_streams(
            pages, is_write, n_shards=n_shards, mapping=mapping,
            n_pages=n_pages, n_windows=n_windows, window_ids=gwin,
            owner=owner,
        )
    else:
        sh_pages, sh_writes, counts, owner, sh_win = partition_streams(
            pages, is_write, n_shards=n_shards, mapping=mapping,
            n_pages=n_pages, n_windows=n_windows, owner=owner,
        )
    stats = jax.vmap(
        lambda p, w, wi: run_stream(
            cfg, p, w, seed=seed, n_windows=n_windows, window_ids=wi)
    )(jnp.asarray(sh_pages), jnp.asarray(sh_writes), jnp.asarray(sh_win))
    return correct_padded_stats(stats, counts, sh_pages.shape[1]), counts


# ---------------------------------------------------------------------------
# Chunked streaming replay: resumable masked scan with donated chunk buffers.
#
# The one-shot paths above hold the whole trace in one [shard, len] device
# array. The streaming path instead carries the full engine state — the
# [S]-stacked (StoreState, _Accum) pytree — across fixed-size chunks, so a
# trace of any length replays in O(S * chunk) device memory. Bit-exactness
# with the one-shot scan comes from *masking*: a chunk row's padding
# positions (window id == the dropped ``n_windows``) leave the carried state
# completely untouched (``t`` not advanced, PRNG key not split) and
# contribute zero to every counter, so the state seen by real request ``j``
# of a shard is identical whatever the chunking. (The one-shot path instead
# lets trailing pads run as pure hits and corrects the totals afterwards —
# equivalent for trailing pads, wrong for mid-stream pads, which is exactly
# why the chunk engine masks.)
# ---------------------------------------------------------------------------

# Chunk engines are cached per (static store, unroll, n_windows, donate);
# the counter increments at trace time, i.e. once per XLA compile (jit's
# shape cache adds one compile per distinct (n_shards, cap) chunk shape).
_STREAM_CACHE: dict = {}
_STREAM_COMPILES = [0]


def stream_compile_count() -> int:
    """Number of XLA compiles of the chunked stream engine so far."""
    return _STREAM_COMPILES[0]


def reset_stream_compile_count() -> None:
    _STREAM_COMPILES[0] = 0


def init_stream_carry(cfg: StoreConfig, n_shards: int, *, seed: int = 0,
                      n_windows: int = 1):
    """Fresh [n_shards]-stacked ``(StoreState, _Accum)`` chunk-engine carry
    — every shard starts from the cold :func:`init_store` state (same seed,
    matching :func:`run_distributed`'s per-shard init) with zeroed
    accumulators."""
    one = (init_store(cfg, seed), _init_accum(n_windows))
    return jax.tree.map(
        lambda x: jnp.repeat(x[None], n_shards, axis=0), one)


def stream_chunk_engine(cfg: StoreConfig, *, unroll: int = 1,
                        n_windows: int = 1, donate: bool = True):
    """The compiled chunk engine for a structural store config:
    ``(hyper, carry, pages [S, L], writes [S, L], win [S, L]) -> carry``.

    The carry and all three chunk buffers are donated
    (``jit(..., donate_argnums=(1, 2, 3, 4))``) so every chunk reuses the
    previous chunk's device allocations — peak device memory is O(S * L)
    regardless of how many chunks stream through. ``hyper`` is a traced
    operand (one compile serves a grid of learning knobs); padding rows
    carry window id ``n_windows`` and are masked no-ops (see the section
    comment). Callers must treat donated arguments as consumed: thread the
    returned carry, never reuse a chunk buffer after passing it in.
    ``donate=False`` exists for the naive per-chunk baseline benchmarks
    compare against. Each shard row runs
    :func:`~repro.kernels.ref.cache_scan_ref` in its masked mode, looked up
    through this module's name when the engine is traced."""
    static = cfg.static_config()
    key = (static, unroll, n_windows, donate)
    fn = _STREAM_CACHE.get(key)
    if fn is not None:
        return fn

    # Named for the profiler: the trace shows jit_chunk_engine.
    def chunk_engine(hyper, carry, pages, writes, win):
        _STREAM_COMPILES[0] += 1  # trace-time: once per XLA compile

        def shard(state, acc, p, w, wi):
            # Resumable masked mode: pads leave the carried state (PRNG key
            # included) untouched; the PRNG stays in-loop because the
            # carried key must advance per real request.
            return cache_scan_ref(
                state, acc, p, w, wi, hyper, None,
                epoch_width=static.epoch_width,
                pred_cap=static.pred_cap, prefetch=static.prefetch,
                prefetch_width=static.prefetch_width,
                n_windows=n_windows, unroll=unroll, masked=True)

        state, acc = carry
        return tuple(jax.vmap(shard)(state, acc,
                                     pages.astype(jnp.int32),
                                     writes.astype(bool),
                                     win.astype(jnp.int32)))

    jfn = jax.jit(chunk_engine,
                  donate_argnums=(1, 2, 3, 4) if donate else ())

    if donate:
        # The chunk buffers (int32/bool operands) have no same-shape output
        # to alias, so XLA warns it can only *free* them early, not reuse
        # them. That is the intended behavior — silence just that warning
        # (the carry donation, the one that bounds peak memory, is silent).
        def fn(*args):
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore",
                    message="Some donated buffers were not usable")
                return jfn(*args)
    else:
        fn = jfn
    _STREAM_CACHE[key] = fn
    return fn


def stream_stats_from_carry(carry, counts) -> StreamStats:
    """Materialize :class:`StreamStats` from a chunk-engine carry. ``counts``
    is the per-shard count of *real* requests streamed so far. No padding
    correction applies — masked pads never touched the accumulators — so
    the result is directly comparable to :func:`run_distributed`'s
    padding-corrected per-shard stats."""
    state, acc = carry
    return _stream_stats(counts, acc, state.ols.weights)


def run_stream_chunked(
    cfg: StoreConfig,
    pages: np.ndarray,
    is_write: np.ndarray,
    *,
    chunk: int,
    seed: int = 0,
    hyper: Optional[StoreHyper] = None,
    unroll: int = 1,
    n_windows: int = 1,
    window_ids: Optional[np.ndarray] = None,
) -> StreamStats:
    """Single-shard chunked replay: :func:`run_stream` semantics, consumed
    ``chunk`` requests at a time through the resumable chunk engine.
    Bit-identical to ``run_stream(cfg, pages, is_write, ...)`` for every
    counter and ``final_weights``. The multi-shard,
    generator-fed production path is :func:`repro.sim.stream.simulate_stream`."""
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    pages = np.asarray(pages, np.int32)
    is_write = np.asarray(is_write, bool)
    n = pages.shape[0]
    if window_ids is None:
        window_ids = stream_window_ids(n, n_windows)
    window_ids = np.asarray(window_ids, np.int32)
    if hyper is None:
        hyper = cfg.hyper()
    eng = stream_chunk_engine(cfg, unroll=unroll, n_windows=n_windows)
    carry = init_stream_carry(cfg, 1, seed=seed, n_windows=n_windows)
    for start in range(0, n, chunk):
        stop = min(start + chunk, n)
        p = np.zeros(chunk, np.int32)
        w = np.zeros(chunk, bool)
        wi = np.full(chunk, n_windows, np.int32)  # tail padding: masked
        p[: stop - start] = pages[start:stop]
        w[: stop - start] = is_write[start:stop]
        wi[: stop - start] = window_ids[start:stop]
        carry = eng(hyper, carry, p[None], w[None], wi[None])
    stats = stream_stats_from_carry(carry, np.array([n], np.int32))
    return jax.tree.map(lambda a: a[0], stats)
