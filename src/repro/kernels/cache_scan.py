"""Fused tier-1 cache-scan engine: VMEM-resident state for the request loop.

The reference engine (``repro.storage.tiered_store``) carries the full
``StoreState`` pytree through a ``lax.scan``, so every request round-trips
cache tags, recency metadata, prediction rings and expert weights through
HBM. This module fuses the whole request loop — lookup → policy decision →
eviction → windowed scatter-add — per ``(shard, point)`` stream row:

- **Pallas kernel** (:func:`cache_scan_kernel`): one grid row per stream
  row keeps the cache tag/metadata arrays, prediction rings and expert
  weights in VMEM scratch, the scalar learner/prefetcher state and every
  counter in SMEM, and loops over the requests with elementwise one-hot
  updates. Requests arrive in SMEM chunks along a second grid axis, next to
  the matching rows of the Random-expert noise table in VMEM.
- **XLA engine** (:func:`repro.kernels.ref.cache_scan_ref`): the same
  one-hot step as a ``lax.scan``, vectorised over the rows of a megabatch —
  the default engine on every platform, and the golden oracle for the
  kernel.
- **Hoisted PRNG** (:func:`repro.kernels.ref.cache_scan_noise`): the
  Random expert's per-step uniforms become a precomputed ``[len,
  n_lines]`` table — bit-identical draws (same threefry chain), shared by
  every row of a megabatch.

**Engine-path rule.** :func:`fused_cache_scan` (one-shot, cold-start rows)
runs the Pallas kernel iff (1) the caller asked for it (``pallas=True``,
``engine="pallas"`` at the public entry points), (2) the computation is
lowered for a TPU (:func:`repro.kernels.backend.kernel_or_xla`), (3) the
row's noise table fits :data:`NOISE_TABLE_MAX` elements (the table both
engines hoist), and (4) the kernel's VMEM working set at the row's
``n_lines`` fits :data:`VMEM_BUDGET` (:func:`kernel_fits`). Otherwise the
XLA engine runs. The kernel is not the default because it is the slower
engine on a TPU v5e: one 288-point sweep megabatch (1,152 rows of 8,192
requests, 256 lines) takes 14.5 s on the kernel and 1.15 s on the XLA
engine — the kernel walks the rows one grid cell after another, while XLA
steps all of them at once. The resumable chunk mode
(``tiered_store.stream_chunk_engine``) always runs the XLA engine. The
outputs carry the path id that ran, which the callers count
(:func:`repro.kernels.backend.engine_path_counts`).
:func:`cache_scan_compile_count` counts traces of the one-shot engine
(once per XLA compile under jit).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.online_learning import N_EXPERTS
from repro.kernels.backend import XLA, kernel_or_xla
from repro.kernels.ref import cache_scan_noise, cache_scan_ref

__all__ = [
    "KERNEL_WEIGHT_ULP",
    "NOISE_TABLE_MAX",
    "VMEM_BUDGET",
    "cache_scan_kernel",
    "fused_cache_scan",
    "kernel_fits",
    "cache_scan_compile_count",
    "reset_cache_scan_compile_count",
]

# Noise-table cap, elements. One-shot rows whose [len, n_lines] f32
# Random-expert table would exceed it draw in the loop instead (same draws),
# which only the XLA engine can do. The table is one array per compiled
# program (a constant under the megabatch's vmaps), so HBM does not bind
# here: 2^22 elements are 16 MiB of a v5e's 16 GiB. The cap is kept at
# 2^22 because every engine that hoists the table materialises it whole
# (on a CPU host, in host memory) and because it only decides how long a
# row the kernel may take, which matters once the kernel is worth taking
# (ROADMAP S4).
NOISE_TABLE_MAX = 1 << 22

# VMEM the kernel may plan for, bytes: the noise-table chunk (double
# buffered) plus the cache-state scratch. Mosaic's default scoped VMEM limit
# on a v5e is 16 MiB; the compile rehearsal in tests/test_tpu_compile.py
# compiles the kernel at the largest n_lines this budget admits.
VMEM_BUDGET = 14 << 20
# Noise rows streamed per grid step (upper bound; fewer when n_lines is
# wide, see _chunk_rows).
MAX_CHUNK = 2048

# Largest f32 expert-weight difference between the compiled kernel and the
# XLA engines measured on a TPU v5e, in ulp. The counters are bit-exact;
# the weights read 0 ulp on the compiled goldens and on a 288-point sweep,
# and 1 ulp on 4 of 24 weights of a 4-point sweep. Each op of the weight
# update (pow, mean, sum, divide) reads 0 ulp alone, so the op that rounds
# differently inside the compiled programs is not identified.
KERNEL_WEIGHT_ULP = 1

# Trace-time compile counter for the one-shot engine: increments once per
# trace, i.e. once per XLA compile under jit.
_CACHE_SCAN_COMPILES = [0]

# SMEM scalar slots of the kernel (learner + stream-identifier state).
_SM_EPOCH_MISSES, _SM_NVALID, _SM_LAST_MISS = 0, 1, 2
_SM_STRIDE, _SM_CONF, _SM_ISSUED = 3, 4, 5
_N_SM = 8
# Whole-row totals in the SMEM counter slab, in this order.
_TOTALS = ("hits", "misses", "prefetch_hits", "tier2_reads", "tier2_writes",
           "evictions")
# Windowed counters, one W-wide row each, after the totals and expert_use.
_WIN = ("win_requests", "win_hits", "win_misses", "win_prefetch_hits",
        "win_tier2_reads", "win_tier2_writes", "win_evictions")

_BIG = jnp.iinfo(jnp.int32).max


def cache_scan_compile_count() -> int:
    """Number of traces (== XLA compiles under jit) of the one-shot engine."""
    return _CACHE_SCAN_COMPILES[0]


def reset_cache_scan_compile_count() -> None:
    _CACHE_SCAN_COMPILES[0] = 0


def _pad(n: int, m: int) -> int:
    return -(-n // m) * m


def _state_bytes(n_lines: int) -> int:
    # Four [1, n_lines] int32 scratch arrays, each tiled to 8 sublanes.
    return 4 * 8 * _pad(n_lines, 128) * 4


def _chunk_rows(length: int, n_lines: int) -> int:
    """Noise rows per grid step: the largest power of two (at most
    MAX_CHUNK, at least 128) whose double-buffered block fits the budget
    left after the state scratch. The whole row when it is shorter."""
    room = VMEM_BUDGET - _state_bytes(n_lines)
    rows = MAX_CHUNK
    while rows > 128 and 2 * rows * _pad(n_lines, 128) * 4 > room:
        rows //= 2
    return length if length <= rows else rows


def kernel_fits(length: int, n_lines: int) -> bool:
    """Conditions (3) and (4) of the engine-path rule (module docstring)."""
    if length * n_lines > NOISE_TABLE_MAX:
        return False
    rows = _pad(_chunk_rows(length, n_lines), 8)
    return (_state_bytes(n_lines) + 2 * rows * _pad(n_lines, 128) * 4
            <= VMEM_BUDGET)


def fused_cache_scan(cfg, hyper, state0, acc0, pages, writes, win, *,
                     n_windows: int, unroll: int = 1, pallas: bool = False):
    """One-shot fused engine for one cold-start stream row: ``(state0,
    acc0, pages [L], writes [L], win [L]) -> (final_weights, acc, path)``.

    Plain traceable function (inlines into the caller's jit; the compile
    counter increments once per outer XLA compile). ``cfg`` supplies the
    structural knobs (``epoch_width``, ``pred_cap``, ``prefetch``,
    ``prefetch_width``), ``hyper`` the traced scalar knobs; ``state0`` and
    ``acc0`` are the cold :func:`~repro.storage.tiered_store.init_store`
    state and zeroed accumulators every one-shot caller passes.
    ``pallas=True`` asks for the Pallas kernel. The engine follows the rule
    in the module docstring; ``path`` is the int32
    :data:`~repro.kernels.backend.PALLAS` / ``XLA`` id of the engine that
    ran."""
    _CACHE_SCAN_COMPILES[0] += 1  # trace-time: once per XLA compile
    n_lines = state0.cache.tags.shape[-1]
    length = pages.shape[0]
    noise = (cache_scan_noise(state0.key, length, n_lines)
             if length * n_lines <= NOISE_TABLE_MAX else None)

    def xla(p, w, wi):
        final, acc = cache_scan_ref(
            state0, acc0, p, w, wi, hyper, noise,
            epoch_width=cfg.epoch_width, pred_cap=cfg.pred_cap,
            prefetch=cfg.prefetch, prefetch_width=cfg.prefetch_width,
            n_windows=n_windows, unroll=unroll,
        )
        return final.ols.weights, acc

    def kernel(p, w, wi):
        out = cache_scan_kernel(
            p[None], w[None], wi[None], noise,
            hyper.alpha, hyper.beta, hyper.threshold, hyper.policy_idx,
            n_lines=n_lines, epoch_width=cfg.epoch_width,
            pred_cap=cfg.pred_cap, prefetch=cfg.prefetch,
            prefetch_width=cfg.prefetch_width,
            prefetch_buf=state0.pf.ptags.shape[-1], n_windows=n_windows,
        )
        acc = type(acc0)(**{
            f: out[f][0].reshape(jnp.shape(a0)).astype(a0.dtype)
            for f, a0 in zip(acc0._fields, acc0)})
        return out["final_weights"][0], acc

    if not pallas or noise is None or not kernel_fits(length, n_lines):
        weights, acc = xla(pages, writes, win)
        return weights, acc, jnp.int32(XLA)
    (weights, acc), path = kernel_or_xla(kernel, xla, pages, writes, win)
    return weights, acc, path


def _cache_scan_body(knob_ref, pol_ref, pages_ref, flags_ref, noise_ref,
                     cnt_ref, ww_ref, fw_ref,
                     tags_s, dirty_s, freq_s, ts_s, pred_s, wts_s, predn_s,
                     mispred_s, ptags_s, pvalid_s, sm, cnt_s, ww_s, *,
                     length, chunk, n_chunks, n_lines, epoch_width, pred_cap,
                     prefetch, prefetch_width, prefetch_buf, n_windows):
    """One grid step = one chunk of one stream row; the row's state stays
    resident in VMEM/SMEM scratch across its chunks.

    Mirrors :func:`repro.kernels.ref.fused_cache_step` op for op: lines fill
    strictly in order (a scalar fill count replaces the ``valid`` array),
    the victim arg-reductions are unmasked first-index min-selects
    (``min(where(mask, iota, BIG))`` equals ``argmin``/``argmax``), and the
    prediction rings are stored transposed (``[C, E]``) with the cursor kept
    modulo ``C``. Per-request operands and every counter are SMEM scalars;
    the weight update is gated on real requests exactly as the XLA engine.
    Counters and window weights accumulate in scratch and reach the output
    blocks once, at the row's last chunk, so the result never depends on an
    output block staying resident across grid steps.
    """
    i32, f32 = jnp.int32, jnp.float32
    E, W, C = N_EXPERTS, n_windows, pred_cap
    c = pl.program_id(1)
    line = jax.lax.broadcasted_iota(i32, (1, n_lines), 1)
    eline = jax.lax.broadcasted_iota(i32, (1, E), 1)
    wrow = jax.lax.broadcasted_iota(i32, (W, E), 0)
    base_eu = len(_TOTALS)
    base_w = base_eu + E
    base_weu = base_w + len(_WIN) * W

    @pl.when(c == 0)
    def _cold_start():
        tags_s[...] = jnp.full((1, n_lines), -1, i32)
        dirty_s[...] = jnp.zeros((1, n_lines), i32)
        freq_s[...] = jnp.zeros((1, n_lines), i32)
        ts_s[...] = jnp.zeros((1, n_lines), i32)
        pred_s[...] = jnp.full((C, E), -1, i32)
        wts_s[...] = jnp.full((1, E), 1.0 / E, f32)
        predn_s[...] = jnp.zeros((1, E), i32)
        mispred_s[...] = jnp.zeros((1, E), i32)
        ptags_s[...] = jnp.full((1, prefetch_buf), -1, i32)
        pvalid_s[...] = jnp.zeros((1, prefetch_buf), i32)
        for k in range(_N_SM):
            sm[k] = jnp.int32(-1 if k == _SM_LAST_MISS else 0)

        def zero(k, carry):
            cnt_s[k] = jnp.int32(0)
            return carry

        jax.lax.fori_loop(0, cnt_s.shape[0], zero, 0)
        ww_s[...] = jnp.zeros((W, E), f32)

    alpha, beta, thr = knob_ref[0, 0], knob_ref[0, 1], knob_ref[0, 2]
    pol = pol_ref[0, 0]

    def first_idx(mask, iota):
        return jnp.min(jnp.where(mask, iota, _BIG))

    def any_(mask):
        return jnp.max(mask.astype(i32)) > 0

    def add(k, v):
        cnt_s[k] = cnt_s[k] + v.astype(i32)

    def step(j, carry):
        t = c * chunk + j
        page = pages_ref[0, j]
        fl = flags_ref[0, j]
        is_w = (fl & 1) == 1
        win_i = fl >> 1
        nrow = noise_ref[pl.ds(j, 1), :]                  # (1, n_lines)
        tags, dirty = tags_s[...], dirty_s[...]
        freq, ts = freq_s[...], ts_s[...]

        # --- lookup: free lines hold -1, never a page id ---
        match = tags == page
        hit = any_(match)
        miss = jnp.logical_not(hit)

        # --- miss bookkeeping ---
        hit_pred = jnp.max((pred_s[...] == page).astype(i32), axis=0,
                           keepdims=True)                 # (1, E)
        mis = mispred_s[...] + jnp.where(miss, hit_pred, 0)
        em = sm[_SM_EPOCH_MISSES] + miss.astype(i32)
        if prefetch:
            ptags, pvalid = ptags_s[...], pvalid_s[...]
            pmatch = (pvalid != 0) & (ptags == page)
            promoted = miss & any_(pmatch)
            pvalid = jnp.where(miss, jnp.where(pmatch, 0, pvalid), pvalid)
        else:
            promoted = jnp.zeros((), bool)

        n_valid = sm[_SM_NVALID]
        has_free = n_valid < n_lines

        # --- GetVictim (unmasked: only observable when the cache is full) ---
        lru = first_idx(ts == jnp.min(ts), line)
        lfu = first_idx(freq == jnp.min(freq), line)
        rnd = first_idx(nrow == jnp.max(nrow), line)
        w = wts_s[...]
        s = jnp.sum(w)
        probs = jnp.where(s > 0, w / s, 1.0 / E)
        learned = first_idx(probs == jnp.max(probs), eline)
        chosen = jnp.where(pol >= 0, jnp.clip(pol, 0, E - 1), learned)
        victim_idx = jnp.where(chosen == 0, lru,
                               jnp.where(chosen == 1, lfu, rnd))
        vp_lru = jnp.sum(jnp.where(line == lru, tags, 0))
        vp_lfu = jnp.sum(jnp.where(line == lfu, tags, 0))
        vp_rnd = jnp.sum(jnp.where(line == rnd, tags, 0))
        victim_pages = jnp.where(eline == 0, vp_lru,
                                 jnp.where(eline == 1, vp_lfu, vp_rnd))

        evict = miss & jnp.logical_not(has_free)
        slot = jnp.where(has_free, n_valid, victim_idx)
        slot_oh = line == slot
        writeback = evict & any_(slot_oh & (dirty != 0))

        # --- prediction rings (transposed [C, E], cursor modulo C) ---
        predn = predn_s[...]
        riota = jax.lax.broadcasted_iota(i32, (C, E), 0)
        pred = pred_s[...]
        pred = jnp.where(evict & (riota == predn), victim_pages, pred)
        predn = jnp.where(evict, jnp.where(predn + 1 == C, 0, predn + 1),
                          predn)

        # --- insert / touch (one select per array) ---
        touch = jnp.where(miss, slot_oh.astype(i32), match.astype(i32)) != 0
        tags = jnp.where(touch, page, tags)
        tags_s[...] = tags
        dirty_s[...] = jnp.where(
            touch, jnp.where(hit, dirty, 0) | is_w.astype(i32), dirty)
        freq_s[...] = jnp.where(touch, jnp.where(miss, 0, freq) + 1, freq)
        ts_s[...] = jnp.where(touch, t, ts)
        sm[_SM_NVALID] = n_valid + (miss & has_free).astype(i32)

        # --- stream identifier + prefetch issue ---
        if prefetch:
            last_miss, stride = sm[_SM_LAST_MISS], sm[_SM_STRIDE]
            conf = sm[_SM_CONF]
            delta = page - last_miss
            same = (delta == stride) & (last_miss >= 0) & (delta != 0)
            conf_n = jnp.where(miss, jnp.where(
                same, conf + 1, jnp.where(delta != 0, 1, conf)), conf)
            stride_n = jnp.where(miss & ~same & (delta != 0), delta, stride)
            sm[_SM_LAST_MISS] = jnp.where(miss, page, last_miss)
            sm[_SM_STRIDE] = stride_n
            sm[_SM_CONF] = conf_n
            n_before = sm[_SM_ISSUED]
            active = conf_n >= 2
            bline = jax.lax.broadcasted_iota(i32, (1, prefetch_buf), 1)
            ptg, pvl, issued = ptags, pvalid, n_before
            for k in range(prefetch_width):
                cand = page + (k + 1) * stride_n
                in_cache = any_(tags == cand)
                in_buf2 = any_((pvl != 0) & (ptg == cand))
                bfree = pvl == 0
                do = (active & any_(bfree) & ~in_cache & ~in_buf2
                      & (cand >= 0))
                boh = (bline == first_idx(bfree, bline)) & do
                ptg = jnp.where(boh, cand, ptg)
                pvl = jnp.where(boh, 1, pvl)
                issued = issued + do.astype(i32)
            ptags_s[...] = jnp.where(miss, ptg, ptags)
            pvalid_s[...] = jnp.where(miss, pvl, pvalid)
            issued_n = jnp.where(miss, issued, n_before)
            sm[_SM_ISSUED] = issued_n
            prefetch_fetches = issued_n - n_before
        else:
            prefetch_fetches = jnp.int32(0)

        # --- epoch boundary (WeightAdjust, ws policy, real requests) ---
        do_adj = (((t + 1) % epoch_width == 0) & (pol < 0)
                  & (win_i < W))
        losses = jnp.where(mis.astype(f32) >= thr * em.astype(f32),
                           mis, 0).astype(f32)
        wadj = w * jnp.power(beta, losses)
        wadj = wadj + alpha * jnp.mean(w - wadj)
        wadj = jnp.maximum(wadj, 1e-8)
        wadj = wadj / jnp.sum(wadj)
        w = jnp.where(do_adj, wadj, w)
        wts_s[...] = w
        pred_s[...] = jnp.where(do_adj, -1, pred)
        predn_s[...] = jnp.where(do_adj, 0, predn)
        mispred_s[...] = jnp.where(do_adj, 0, mis)
        sm[_SM_EPOCH_MISSES] = jnp.where(do_adj, 0, em)

        # --- fold: totals count every position, windows drop pads ---
        t2r = (miss & ~promoted).astype(i32) + prefetch_fetches
        vals = (hit, miss, promoted, t2r, writeback, evict)
        for k, v in enumerate(vals):
            add(k, v)
        expert = jnp.where(evict, chosen, 0)
        add(base_eu + expert, evict)
        real = (win_i < W).astype(i32)
        wi = jnp.minimum(win_i, W - 1)
        for r, v in enumerate((jnp.int32(1),) + vals):
            add(base_w + r * W + wi, v.astype(i32) * real)
        add(base_weu + wi * E + expert, evict.astype(i32) * real)
        ww_s[...] = jnp.where(wrow == win_i, w, ww_s[...])
        return carry

    jax.lax.fori_loop(0, jnp.minimum(chunk, length - c * chunk), step, 0)

    @pl.when(c == n_chunks - 1)
    def _emit():
        def copy(k, carry):
            cnt_ref[0, k] = cnt_s[k]
            return carry

        jax.lax.fori_loop(0, cnt_s.shape[0], copy, 0)
        ww_ref[...] = ww_s[...]
        fw_ref[...] = wts_s[...]


@functools.partial(jax.jit, static_argnames=(
    "n_lines", "epoch_width", "pred_cap", "prefetch", "prefetch_width",
    "prefetch_buf", "n_windows", "interpret"))
def cache_scan_kernel(
    pages: jnp.ndarray,   # int32[B, L] per-row request streams
    writes: jnp.ndarray,  # bool/int32[B, L]
    win: jnp.ndarray,     # int32[B, L] window ids (n_windows = pad/drop)
    noise: jnp.ndarray,   # f32[L, n_lines] shared Random-expert table
    alpha, beta, threshold, policy_idx,  # scalar or [B] hyper knobs
    *,
    n_lines: int,
    epoch_width: int = 4,
    pred_cap: int = 64,
    prefetch: bool = False,
    prefetch_width: int = 4,
    prefetch_buf: int = 16,
    n_windows: int = 1,
    interpret: bool = False,
) -> dict:
    """Batched Pallas cache scan: each of the ``B`` rows runs the whole
    request loop from the cold :func:`~repro.storage.tiered_store.init_store`
    state, tier-1 state resident in VMEM scratch, over a ``(B, chunks)``
    grid.

    Returns the accumulator dict (keys = the reference ``_Accum`` fields
    plus ``final_weights``): scalar counters ``[B]``, windowed counters
    ``[B, n_windows]``, ``win_expert_use``/``win_weights``
    ``[B, n_windows, E]``. Bit-identical to
    :func:`repro.kernels.ref.cache_scan_ref` over each row with the same
    ``noise`` table in interpret mode; compiled for a TPU, the counters are
    bit-identical and the weights within :data:`KERNEL_WEIGHT_ULP`."""
    B, L = pages.shape
    E, W = N_EXPERTS, n_windows
    i32, f32 = jnp.int32, jnp.float32
    # The ring only ever holds min(pred_cap, epoch_width) live entries:
    # under ws it is cleared every epoch (<= epoch_width evictions between
    # resets), and under fixed policies it is unobservable (weights never
    # adjust) — same truncation as cache_scan_ref, bit-exact.
    pred_cap = min(pred_cap, epoch_width)
    chunk = _chunk_rows(L, n_lines)
    n_chunks = -(-L // chunk)
    lp = n_chunks * chunk

    def rows(x, dtype):
        x = jnp.asarray(x).astype(dtype)
        return jnp.pad(x, ((0, 0), (0, lp - L))) if lp != L else x

    pages = rows(pages, i32)
    # Per-request flags: window id * 2 + is_write, one SMEM word.
    flags = rows(jnp.asarray(win, i32) * 2 + jnp.asarray(writes).astype(i32),
                 i32)
    noise = jnp.asarray(noise, f32)
    if lp != L:
        noise = jnp.pad(noise, ((0, lp - L), (0, 0)))
    # Rows carry a unit middle axis so every block's last two dimensions
    # equal the array's (Mosaic's tiling rule for squeezed row blocks).
    pages, flags = pages[:, None], flags[:, None]
    knobs = jnp.stack([jnp.broadcast_to(jnp.asarray(x, f32), (B,))
                       for x in (alpha, beta, threshold)], axis=1)[:, None]
    pol = jnp.broadcast_to(jnp.asarray(policy_idx, i32), (B,)).reshape(
        B, 1, 1)
    n_cnt = len(_TOTALS) + E + len(_WIN) * W + W * E
    vma = frozenset().union(*(jax.typeof(x).vma for x in
                              (pages, flags, noise, knobs, pol)))

    def smem_row(width):
        return pl.BlockSpec((None, 1, width), lambda b, c: (b, 0, 0),
                            memory_space=pltpu.SMEM)

    stream = pl.BlockSpec((None, 1, chunk), lambda b, c: (b, 0, c),
                          memory_space=pltpu.SMEM)
    cnt, ww, fw = pl.pallas_call(
        functools.partial(
            _cache_scan_body, length=L, chunk=chunk, n_chunks=n_chunks,
            n_lines=n_lines,
            epoch_width=epoch_width, pred_cap=pred_cap, prefetch=prefetch,
            prefetch_width=prefetch_width, prefetch_buf=prefetch_buf,
            n_windows=W),
        grid=(B, n_chunks),
        in_specs=[
            smem_row(3), smem_row(1), stream, stream,
            pl.BlockSpec((chunk, n_lines), lambda b, c: (c, 0)),
        ],
        out_specs=[
            smem_row(n_cnt),
            pl.BlockSpec((None, W, E), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((None, 1, E), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, 1, n_cnt), i32, vma=vma),
            jax.ShapeDtypeStruct((B, W, E), f32, vma=vma),
            jax.ShapeDtypeStruct((B, 1, E), f32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, n_lines), i32),   # tags
            pltpu.VMEM((1, n_lines), i32),   # dirty
            pltpu.VMEM((1, n_lines), i32),   # freq
            pltpu.VMEM((1, n_lines), i32),   # ts
            pltpu.VMEM((pred_cap, E), i32),  # prediction rings (transposed)
            pltpu.VMEM((1, E), f32),         # expert weights
            pltpu.VMEM((1, E), i32),         # ring cursor
            pltpu.VMEM((1, E), i32),         # mispred
            pltpu.VMEM((1, prefetch_buf), i32),  # prefetch tags
            pltpu.VMEM((1, prefetch_buf), i32),  # prefetch valid
            pltpu.SMEM((_N_SM,), i32),       # scalar learner/prefetch state
            pltpu.SMEM((n_cnt,), i32),       # counters
            pltpu.VMEM((W, E), f32),         # window weights
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="cache_scan",
    )(knobs, pol, pages, flags, noise)
    cnt = cnt[:, 0]
    out = {f: cnt[:, k] for k, f in enumerate(_TOTALS)}
    base = len(_TOTALS)
    out["expert_use"] = cnt[:, base:base + E]
    base += E
    for r, f in enumerate(_WIN):
        out[f] = cnt[:, base + r * W: base + (r + 1) * W]
    base += len(_WIN) * W
    out["win_expert_use"] = cnt[:, base:].reshape(B, W, E)
    out["win_weights"] = ww
    out["final_weights"] = fw[:, 0]
    return out
