"""Pure-jnp oracles for every Pallas kernel (shape-for-shape references).

These are deliberately naive (full softmax, materialized scores, sequential
scans) — correctness baselines for the interpret-mode kernel tests, NOT the
production XLA path (that is ``models/attention.py`` etc.).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import online_learning as _ol

__all__ = [
    "attention_ref",
    "paged_attention_ref",
    "page_copy_ref",
    "reuse_distance_ref",
    "cache_scan_noise",
    "cache_scan_ref",
    "fused_cache_step",
    "fused_fold",
    "vary_like",
    "rglru_ref",
    "ssd_ref",
]

# Reuse distance of a first-ever access (compulsory miss): larger than any
# possible cache size, so `d < C` is False for every C. Shared sentinel with
# kernels/reuse_distance.py.
DIST_INF = 2**31 - 1


def attention_ref(
    q: jnp.ndarray,  # [B, H, Sq, hd]
    k: jnp.ndarray,  # [B, KV, Skv, hd]
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
) -> jnp.ndarray:
    B, H, Sq, hd = q.shape
    KV = k.shape[1]
    G = H // KV
    qf = q.astype(jnp.float32).reshape(B, KV, G, Sq, hd)
    s = jnp.einsum("bkgqh,bksh->bkgqs", qf, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    qpos = jnp.arange(Sq)[:, None]
    kpos = jnp.arange(k.shape[2])[None, :]
    if causal:
        m = qpos >= kpos
        if window is not None:
            m &= kpos > qpos - window
        s = jnp.where(m[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bksh->bkgqh", p, v.astype(jnp.float32))
    return o.reshape(B, H, Sq, hd).astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,        # [B, H, hd] single-token queries
    pool: jnp.ndarray,     # [slots, page, 2, KV, hd]
    page_slot: jnp.ndarray,  # [B, n_pages] int32 slot ids (-1 invalid)
    lengths: jnp.ndarray,  # [B]
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Partial attention over resident pages. Returns (acc, m, l) so results
    can be combined across shards exactly like the kernel."""
    B, H, hd = q.shape
    n_pages = page_slot.shape[1]
    page = pool.shape[1]
    KV = pool.shape[3]
    G = H // KV
    slot = jnp.clip(page_slot, 0)
    data = pool[slot]                          # [B, n_pages, page, 2, KV, hd]
    k = data[..., 0, :, :].reshape(B, n_pages * page, KV, hd)
    v = data[..., 1, :, :].reshape(B, n_pages * page, KV, hd)
    tok = (jnp.arange(n_pages)[:, None] * page
           + jnp.arange(page)[None, :]).reshape(-1)
    valid = (page_slot >= 0)[:, :, None].repeat(page, 2).reshape(B, -1)
    valid &= tok[None, :] < lengths[:, None]
    qf = q.astype(jnp.float32).reshape(B, KV, G, hd)
    s = jnp.einsum("bkgh,btkh->bkgt", qf, k.astype(jnp.float32))
    s = s / math.sqrt(hd)
    s = jnp.where(valid[:, None, None], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    p = jnp.where(valid[:, None, None], p, 0.0)  # all-masked rows -> l = 0
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bkgt,btkh->bkgh", p, v.astype(jnp.float32))
    return acc, m, l


def page_copy_ref(
    dst: jnp.ndarray,       # [Sd, ...page payload...]
    src: jnp.ndarray,       # [Ss, ...]
    dst_idx: jnp.ndarray,   # [N] int32 (-1 = skip)
    src_idx: jnp.ndarray,   # [N] int32
) -> jnp.ndarray:
    """Tier movement: dst[dst_idx[i]] = src[src_idx[i]] for each live pair."""
    def body(i, d):
        ok = (dst_idx[i] >= 0) & (src_idx[i] >= 0)
        row = src[jnp.clip(src_idx[i], 0)]
        di = jnp.clip(dst_idx[i], 0)
        return jnp.where(ok, d.at[di].set(row), d)

    return jax.lax.fori_loop(0, dst_idx.shape[0], body, dst)


def reuse_distance_ref(
    prev: jnp.ndarray,   # int32[S, L] previous-occurrence index (-1 = first)
    valid: jnp.ndarray,  # bool[S, L]  real positions (False = padding)
    *,
    block: int = 128,
) -> jnp.ndarray:
    """LRU stack (Mattson reuse) distance per request, pure jnp.

    For request ``j`` of shard row ``s`` with previous same-page occurrence
    ``i = prev[s, j]``, the reuse distance is the number of *distinct* pages
    touched strictly between the two accesses — counted as the positions
    ``k`` in ``(i, j)`` whose own previous occurrence lies at or before
    ``i`` (``prev[s, k] <= i``), i.e. the first in-gap occurrence of each
    distinct page. First-ever accesses return :data:`DIST_INF` (compulsory
    miss at every cache size); padding returns ``-1``. Distances never
    cross shard rows.

    This is both the oracle for the Pallas kernel golden tests and the
    production CPU fallback: the O(L^2) dominance count is blocked over
    ``block`` queries at a time (O(block*L) memory, vectorized compares),
    not materialized as a full [L, L] matrix.
    """
    prev = jnp.asarray(prev, jnp.int32)
    valid = jnp.asarray(valid, bool)
    S, L = prev.shape
    pad = (-L) % block
    P = jnp.pad(prev, ((0, 0), (0, pad)), constant_values=-1)
    V = jnp.pad(valid, ((0, 0), (0, pad)), constant_values=False)
    Lp = L + pad
    kidx = jnp.arange(Lp, dtype=jnp.int32)

    def per_shard(Ps, Vs):
        def jblock(jb):
            j0 = jb * block
            pj = jax.lax.dynamic_slice(Ps, (j0,), (block,))
            vj = jax.lax.dynamic_slice(Vs, (j0,), (block,))
            jidx = j0 + jnp.arange(block, dtype=jnp.int32)
            m = (
                (kidx[None, :] > pj[:, None])
                & (kidx[None, :] < jidx[:, None])
                & (Ps[None, :] <= pj[:, None])
                & Vs[None, :]
            )
            d = jnp.sum(m, axis=1, dtype=jnp.int32)
            d = jnp.where(pj >= 0, d, DIST_INF)
            return jnp.where(vj, d, -1)

        return jax.lax.map(jblock, jnp.arange(Lp // block)).reshape(Lp)

    return jax.vmap(per_shard)(P, V)[:, :L]


# ---------------------------------------------------------------------------
# Fused tier-1 cache scan (oracle + production CPU fallback).
#
# One request step of the storage engine with every scatter/gather replaced
# by one-hot iota-compare updates — elementwise selects and adds on [N]
# arrays, the exact op mix the Pallas kernel runs on its VMEM-resident
# state. Bit-identical to repro.storage.tiered_store._step: integer/bool
# updates are exact by construction (a one-hot where() IS a single-index
# scatter), and the float expert-weight arithmetic calls the same
# online_learning routines (same op order, same f32 rounding).
# ---------------------------------------------------------------------------


def cache_scan_noise(key: jax.Array, length: int, n_lines: int) -> jnp.ndarray:
    """Random-expert noise table: row ``t`` holds the uniforms the in-loop
    PRNG would draw at step ``t`` of a stream starting from ``key``.

    The reference scan splits per step (``key, vkey = split(key)``) and
    draws ``uniform(vkey, [n_lines])`` inside the sequential loop; each
    draw is a pure function of its ``vkey``, so precomputing the split
    chain (a cheap scan over single keys) and batching the draws
    (``vmap``'d threefry, fully parallel over ``length``) yields
    bit-identical values while removing the PRNG from the request loop.
    Under vmap over sweep points/shards the table is a *constant* (the
    seed is static), so one table serves the whole megabatch."""

    def split_step(k, _):
        k2, vk = jax.random.split(k)
        return k2, vk

    _, vkeys = jax.lax.scan(split_step, key, None, length=length)
    return jax.vmap(lambda vk: jax.random.uniform(vk, (n_lines,)))(vkeys)


class _ScanCache(NamedTuple):
    """Slim cache carry for :func:`cache_scan_ref` — the ``CacheState``
    fields the scan actually needs, with the ``valid`` array replaced by a
    scalar fill count. Lines fill strictly in order (inserts always take
    the lowest free index, nothing ever invalidates), so ``valid`` is
    exactly ``tags >= 0`` (init ``-1``; pages are non-negative) and the
    next free index is the fill count itself — dropping one ``[n_lines]``
    array from the sequential carry and three mask ops from the victim
    argreductions (see the step body)."""

    tags: jnp.ndarray    # int32[n_lines]
    dirty: jnp.ndarray   # bool[n_lines]
    freq: jnp.ndarray    # int32[n_lines]
    ts: jnp.ndarray      # int32[n_lines]
    n_valid: jnp.ndarray  # int32 scalar fill count


def fused_cache_step(state, page, is_write, noise, hyper, *,
                     epoch_width: int, pred_cap: int, prefetch: bool,
                     prefetch_width: int, real=True):
    """One fused request step on duck-typed store state (any pytree with
    the ``StoreState``/``OLState``/``PrefetchState`` fields, ``cache``
    being a :class:`_ScanCache`).

    ``noise`` is this step's Random-expert draw (f32[n_lines]) — a row of
    :func:`cache_scan_noise` or an in-loop ``uniform(vkey, ...)``; the PRNG
    key itself is managed by the caller (left untouched here). ``real`` is
    False at padding positions, which must not run WeightAdjust (see
    ``tiered_store._step``). Returns ``(state, out)`` with ``out``
    matching the reference step's dict."""
    cache, ols, pf = state.cache, state.ols, state.pf
    t = state.t
    page = page.astype(jnp.int32)
    n_lines = cache.tags.shape[-1]
    line = jnp.arange(n_lines, dtype=jnp.int32)
    E = _ol.N_EXPERTS

    # --- 1. lookup -------------------------------------------------------
    # A page occupies at most one line and free lines hold ``-1`` (never a
    # page id), so ``match`` is already the hit one-hot — no validity mask
    # or argmax needed, and the hit-path updates merge with the miss-path
    # insert below through a single ``touch`` mask.
    match = cache.tags == page
    hit = jnp.any(match)

    # --- 2/3. miss path ---------------------------------------------------
    miss = ~hit
    hit_pred = jnp.any(ols.pred == page, axis=1)  # bool[E]
    ols = ols._replace(
        mispred=ols.mispred + jnp.where(miss, hit_pred.astype(jnp.int32), 0),
        epoch_misses=ols.epoch_misses + jnp.where(miss, 1, 0),
    )
    # Prefetch buffer probe. With prefetch off the buffer is never
    # populated, so the probe is a state-invariant no-op — skipping it
    # entirely (promoted = False) is exact, and the [B]-wide compares drop
    # out of the hot loop.
    if prefetch:
        pmatch = pf.pvalid & (pf.ptags == page)
        in_buf = jnp.any(pmatch)
        pf = pf._replace(
            pvalid=jnp.where(miss & pmatch, False, pf.pvalid),
            useful=pf.useful + jnp.where(miss, in_buf.astype(jnp.int32), 0),
        )
        promoted = miss & in_buf
    else:
        promoted = jnp.zeros((), bool)

    # Sequential fill: the free lines are exactly the suffix [n_valid, N),
    # so the free-slot search is a scalar compare, not an argreduction.
    has_free = cache.n_valid < n_lines
    free_idx = cache.n_valid

    # GetVictim (ol.propose_victims with the provided noise): compares and
    # first-index argreductions only — exact. The reference masks invalid
    # lines out of each argreduction, but the victims are only *observable*
    # on an eviction (slot, pred ring, writeback — all gated by ``evict``,
    # which implies a full cache where the masks are identity), so the
    # unmasked reductions are bit-exact where it matters.
    lru = jnp.argmin(cache.ts).astype(jnp.int32)
    lfu = jnp.argmin(cache.freq).astype(jnp.int32)
    rnd = jnp.argmax(noise).astype(jnp.int32)
    proposals = jnp.stack([lru, lfu, rnd])
    victim_pages = cache.tags[proposals]                  # int32[E] gather
    chosen = _ol.choose_expert(ols, hyper.policy_idx)
    victim_idx = jnp.sum(
        jnp.where(jnp.arange(E, dtype=jnp.int32) == chosen, proposals, 0)
    ).astype(jnp.int32)

    evict = miss & ~has_free
    slot = jnp.where(has_free, free_idx, victim_idx)
    slot_oh = line == slot
    writeback = evict & cache.dirty[slot]

    # Prediction rings (one-hot column write), gated by evict. The ring
    # width is whatever the carried state holds — cache_scan_ref may have
    # truncated it to min(pred_cap, epoch_width) (see there); the modulo
    # follows the actual width so the truncated ring wraps consistently.
    ring = ols.pred.shape[-1]
    col_oh = (jnp.arange(ring, dtype=jnp.int32)[None, :]
              == (ols.pred_n % ring)[:, None])            # bool[E, C]
    pred_new = jnp.where(col_oh, victim_pages[:, None], ols.pred)
    ols = ols._replace(
        pred=jnp.where(evict, pred_new, ols.pred),
        pred_n=jnp.where(evict, ols.pred_n + 1, ols.pred_n),
        chosen=jnp.where(evict, chosen, ols.chosen[0])[None],
    )

    # Touched line: the hit line on a hit, the insert slot on a miss. On a
    # hit ``tags[match] == page`` already, so the unified writes below are
    # no-ops there — one select per array instead of the nested
    # hit/miss/unchanged merge (bit-identical: same values land).
    touch = jnp.where(miss, slot_oh, match)
    cache = cache._replace(
        tags=jnp.where(touch, page, cache.tags),
        dirty=jnp.where(touch, (cache.dirty & hit) | is_write, cache.dirty),
        freq=jnp.where(touch, jnp.where(miss, 0, cache.freq) + 1, cache.freq),
        ts=jnp.where(touch, t, cache.ts),
        n_valid=cache.n_valid + (miss & has_free).astype(jnp.int32),
    )

    # --- 4. stream identifier + prefetch issue ----------------------------
    if prefetch:
        delta = page - pf.last_miss
        same = (delta == pf.stride) & (pf.last_miss >= 0) & (delta != 0)
        conf_o = jnp.where(same, pf.conf + 1,
                           jnp.where(delta != 0, 1, pf.conf))
        stride_o = jnp.where(same, pf.stride,
                             jnp.where(delta != 0, delta, pf.stride))
        pf = pf._replace(
            last_miss=jnp.where(miss, page, pf.last_miss),
            stride=jnp.where(miss, stride_o, pf.stride),
            conf=jnp.where(miss, conf_o, pf.conf),
        )
        n_before = pf.issued
        active = pf.conf >= 2
        buf = jnp.arange(pf.ptags.shape[-1], dtype=jnp.int32)

        def body(k, pf_):
            cand = page + (k + 1) * pf_.stride
            # Free lines hold -1; a negative ``cand`` is discarded by the
            # ``cand >= 0`` gate below, so the tags compare alone is exact.
            in_cache = jnp.any(cache.tags == cand)
            in_buf2 = jnp.any(pf_.pvalid & (pf_.ptags == cand))
            bfree = ~pf_.pvalid
            do = (active & jnp.any(bfree) & ~in_cache & ~in_buf2
                  & (cand >= 0))
            boh = (buf == jnp.argmax(bfree).astype(jnp.int32)) & do
            return pf_._replace(
                ptags=jnp.where(boh, cand, pf_.ptags),
                pvalid=pf_.pvalid | boh,
                issued=pf_.issued + do.astype(jnp.int32),
            )

        pf_issued = jax.lax.fori_loop(0, prefetch_width, body, pf)
        pf = jax.tree.map(lambda n, o: jnp.where(miss, n, o), pf_issued, pf)
        prefetch_fetches = jnp.where(miss, pf.issued - n_before, 0)
    else:
        prefetch_fetches = jnp.zeros((), jnp.int32)

    # --- 5. epoch boundary -------------------------------------------------
    epoch_end = (t + 1) % epoch_width == 0
    is_ws = hyper.policy_idx < 0
    ol_cfg = _ol.OLConfig(epoch_width=epoch_width, alpha=hyper.alpha,
                          beta=hyper.beta, threshold=hyper.threshold,
                          pred_cap=pred_cap)
    ols = jax.tree.map(
        lambda new, old: jnp.where(epoch_end & is_ws & real, new, old),
        _ol.weight_adjust(ols, ol_cfg), ols,
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
    return state._replace(cache=cache, ols=ols, pf=pf, t=t + 1), out


def fused_fold(acc, outs, win, weights, n_windows: int):
    """Dense post-pass counterpart of the reference per-step ``_fold``:
    consumes the *stacked* ``[L]`` per-request outcomes of a whole scan
    and reduces them into the accumulators in one shot — the windowed
    scatter-adds become one-hot mask reductions over the request axis
    (commutative integer adds: exact), hoisted out of the sequential loop
    entirely so the scan carries only the engine state.

    ``win == n_windows`` (padding) matches no window slot and drops,
    exactly the ``mode="drop"`` semantics; the scalar totals sum over all
    positions (pads included — historic semantics). ``weights`` is the
    ``[L, E]`` stack of post-step expert weights: each window row takes
    the weights at its *last* matching request (identical to the
    reference's overwrite-every-step fold), keeping ``acc``'s existing
    row where the window saw no request."""
    i32 = jnp.int32
    hit = outs["hit"].astype(i32)
    miss = outs["miss"].astype(i32)
    pfh = outs["prefetch_hit"].astype(i32)
    t2r = outs["tier2_read"].astype(i32)
    t2w = outs["tier2_write"].astype(i32)
    ev = outs["evict"].astype(i32)
    expert = jnp.where(outs["evict"], outs["chosen"], 0)
    length = hit.shape[0]
    woh = win[:, None] == jnp.arange(n_windows, dtype=i32)[None, :]  # [L, W]
    wohi = woh.astype(i32)
    eoh = (expert[:, None] == jnp.arange(_ol.N_EXPERTS, dtype=i32)[None, :]
           ).astype(i32) * ev[:, None]                               # [L, E]
    # [L, 7] stacked counters -> [W, 7] via one integer contraction.
    vals = jnp.stack([jnp.ones_like(hit), hit, miss, pfh, t2r, t2w, ev],
                     axis=1)
    winc = wohi.T @ vals                                             # [W, 7]
    # Last matching request per window (-1 = window untouched this scan).
    pos = jnp.max(jnp.where(woh, jnp.arange(length, dtype=i32)[:, None], -1),
                  axis=0)
    wsel = jnp.take(weights, jnp.maximum(pos, 0), axis=0)            # [W, E]
    return acc._replace(
        hits=acc.hits + jnp.sum(hit),
        misses=acc.misses + jnp.sum(miss),
        prefetch_hits=acc.prefetch_hits + jnp.sum(pfh),
        tier2_reads=acc.tier2_reads + jnp.sum(t2r),
        tier2_writes=acc.tier2_writes + jnp.sum(t2w),
        evictions=acc.evictions + jnp.sum(ev),
        expert_use=acc.expert_use + jnp.sum(eoh, axis=0),
        win_requests=acc.win_requests + winc[:, 0],
        win_hits=acc.win_hits + winc[:, 1],
        win_misses=acc.win_misses + winc[:, 2],
        win_prefetch_hits=acc.win_prefetch_hits + winc[:, 3],
        win_tier2_reads=acc.win_tier2_reads + winc[:, 4],
        win_tier2_writes=acc.win_tier2_writes + winc[:, 5],
        win_evictions=acc.win_evictions + winc[:, 6],
        win_expert_use=acc.win_expert_use + wohi.T @ eoh,
        win_weights=jnp.where((pos >= 0)[:, None], wsel, acc.win_weights),
    )


def vary_like(tree, like):
    """``tree`` marked varying over every manual mesh axis ``like`` varies
    over. Under ``shard_map(check_vma=True)`` a scan whose carry starts from
    a constant (the cold store state) and absorbs sharded requests must
    start varying; elsewhere this is the identity."""
    want = jax.typeof(like).vma

    def cast(x):
        missing = tuple(sorted(want - jax.typeof(x).vma))
        return jax.lax.pcast(x, missing, to="varying") if missing else x

    return jax.tree.map(cast, tree) if want else tree


def cache_scan_ref(state0, acc0, pages, writes, win, hyper, noise, *,
                   epoch_width: int, pred_cap: int, prefetch: bool,
                   prefetch_width: int, n_windows: int, unroll: int = 1,
                   masked: bool = False):
    """One stream row of the fused cache engine, pure jnp — the oracle for
    the Pallas ``cache_scan`` kernel's golden tests AND the production CPU
    fallback (same pattern as :func:`reuse_distance_ref`; the sequential
    dependence means the scan stays a scan — ``unroll`` is the blocking
    knob here, chunking the loop body like the reference engine's).

    ``noise`` is the precomputed ``[len, n_lines]`` Random-expert table
    (:func:`cache_scan_noise` — the one-shot megabatch mode; ``state0.key``
    is carried through untouched) or ``None`` for in-loop PRNG splits (the
    resumable chunk-engine mode, where the carried key must advance exactly
    as the reference engine's). ``masked=True`` reproduces the chunk
    engine's pad semantics: positions with ``win >= n_windows`` leave the
    state (including ``t`` and the key) untouched and contribute zero to
    every counter. Returns ``(final_state, acc)``.

    The sequential scan carries *only* the engine state and emits the tiny
    per-request outcome scalars; the counter fold over those outcomes is
    commutative, so it runs as one dense post-pass (:func:`fused_fold`)
    instead of riding the loop carry.

    The prediction ring is carried truncated to ``min(pred_cap,
    epoch_width)`` columns: under online learning (ws) the ring is cleared
    every epoch boundary and sees at most one eviction per step, so slots
    ``>= epoch_width`` are never written between resets — they stay at
    their incoming value (``-1``), and truncating them is bit-exact. Under
    a fixed-expert policy the full ring *would* wrap through all
    ``pred_cap`` slots, but then ``weight_adjust`` never fires, so neither
    the ring nor ``mispred`` is observable in any output. The untouched
    tail columns are spliced back onto the final state unchanged."""

    c_eff = min(pred_cap, epoch_width)
    ols0 = state0.ols
    cache0 = state0.cache
    state0 = state0._replace(
        ols=ols0._replace(pred=ols0.pred[:, :c_eff]),
        # Slim cache carry: ``valid`` becomes a scalar fill count (lines
        # fill strictly in order — see _ScanCache), reconstructed exactly
        # as ``tags >= 0`` on exit.
        cache=_ScanCache(
            tags=cache0.tags, dirty=cache0.dirty, freq=cache0.freq,
            ts=cache0.ts,
            n_valid=jnp.sum(cache0.valid).astype(jnp.int32)),
    )

    def scan_fn(state, xs):
        if noise is None:
            page, write, win_i = xs
            key, vkey = jax.random.split(state.key)
            nrow = jax.random.uniform(vkey, state.cache.tags.shape)
            st_in = state._replace(key=key)
        else:
            page, write, win_i, nrow = xs
            st_in = state
        new_state, out = fused_cache_step(
            st_in, page, write.astype(bool), nrow, hyper,
            epoch_width=epoch_width, pred_cap=pred_cap, prefetch=prefetch,
            prefetch_width=prefetch_width, real=win_i < n_windows,
        )
        if masked:
            valid = win_i < n_windows
            new_state = jax.tree.map(
                lambda n, o: jnp.where(valid, n, o), new_state, state)
            out = dict(
                hit=out["hit"] & valid,
                miss=out["miss"] & valid,
                prefetch_hit=out["prefetch_hit"] & valid,
                tier2_read=jnp.where(valid, out["tier2_read"], 0),
                tier2_write=jnp.where(valid, out["tier2_write"], 0),
                evict=out["evict"] & valid,
                chosen=out["chosen"],
            )
        return new_state, (out, new_state.ols.weights)

    xs = (pages, writes, win) if noise is None else (pages, writes, win, noise)
    final, (outs, wts) = jax.lax.scan(scan_fn, vary_like(state0, pages), xs,
                                      unroll=unroll)
    fc = final.cache
    final = final._replace(
        ols=final.ols._replace(pred=jnp.concatenate(
            [final.ols.pred, ols0.pred[:, c_eff:]], axis=1)),
        # Rebuild the full CacheState (duck-typed via the caller's class):
        # a line is valid iff it ever took an insert, i.e. tags >= 0.
        cache=type(cache0)(tags=fc.tags, valid=fc.tags >= 0, dirty=fc.dirty,
                           freq=fc.freq, ts=fc.ts),
    )
    return final, fused_fold(vary_like(acc0, pages), outs, win, wts,
                             n_windows)


def rglru_ref(u, w_a, b_a, w_x, b_x, lam):
    """Sequential RG-LRU recurrence. u: [B, S, W] -> h [B, S, W] (f32)."""
    uf = u.astype(jnp.float32)
    r = jax.nn.sigmoid(uf * w_a + b_a)
    i = jax.nn.sigmoid(uf * w_x + b_x)
    log_a = -8.0 * jax.nn.softplus(lam) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * uf)

    def step(h, ab):
        a_t, b_t = ab
        h = a_t * h + b_t
        return h, h

    _, hs = jax.lax.scan(step, jnp.zeros_like(uf[:, 0]),
                         (jnp.moveaxis(a, 1, 0), jnp.moveaxis(b, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)


def ssd_ref(x, dt, A, Bm, Cm):
    """Sequential SSD scan. x: [B,S,H,P]; returns y [B,S,H,P] f32."""
    Bsz, S, H, P = x.shape

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * A)                        # [B,H]
        h = h * decay[..., None, None] + jnp.einsum(
            "bn,bhp->bhnp", b_t, dt_t[..., None] * x_t)
        y = jnp.einsum("bn,bhnp->bhp", c_t, h)
        return h, y

    h0 = jnp.zeros((Bsz, H, Bm.shape[-1], P), jnp.float32)
    _, ys = jax.lax.scan(
        step, h0,
        (jnp.moveaxis(x.astype(jnp.float32), 1, 0),
         jnp.moveaxis(dt.astype(jnp.float32), 1, 0),
         jnp.moveaxis(Bm.astype(jnp.float32), 1, 0),
         jnp.moveaxis(Cm.astype(jnp.float32), 1, 0)),
    )
    return jnp.moveaxis(ys, 0, 1)
