"""Chunked streaming trace replay: bounded memory, one compile, bit-exact.

The one-shot path (:func:`repro.sim.engine.tier1_counters`) materializes
the whole request stream, partitions it, and pushes ``[n_shards, n]``
device buffers through one scan — peak device memory grows linearly with
trace length, and a multi-million-request replay either thrashes or OOMs.
This module replays the same workload in fixed-size *chunks* through the
resumable chunk engine
(:func:`repro.storage.tiered_store.stream_chunk_engine`):

- **Bounded memory.** Only one chunk's ``[n_shards, cap]`` buffers plus
  the carried ``(StoreState, accumulators)`` live on device at a time;
  the carry and chunk buffers are *donated* (``jit(...,
  donate_argnums=...)``) so every chunk reuses the previous chunk's
  allocations. Peak device memory is independent of trace length.
- **Few compiles (a power-of-two ladder).** A chunk's per-shard length
  bucket is the power of two that fits its hottest shard, clamped between
  a primary bucket sized for balanced shard loads and a fallback that fits
  one shard owning the whole chunk (:func:`_chunk_cap`). The scan's cost is
  proportional to the bucket, so a skewed chunk pays for its hot shard and
  no more, and an arbitrarily long replay compiles the engine at most once
  per rung, ``log2(fallback / primary) + 1`` shapes
  (:func:`repro.storage.tiered_store.stream_compile_count` observes this).
- **Overlap.** The engine call dispatches asynchronously: host-side
  generation, window binning and partitioning of chunk ``k+1`` overlap
  device compute of chunk ``k`` (double buffering — the ``device_put``
  of the next chunk happens while the previous one is still running).
- **Bit-exact.** Chunk-boundary requests straddle window edges, bucket
  pads and fault events freely: pads carry the dropped window id and are
  *masked no-ops* in the chunk engine (state untouched, zero counter
  contribution), so every counter — whole-stream, windowed, faulted —
  equals the one-shot engine's exactly, for every chunk size.
- **Resume.** :class:`StreamCheckpoint` snapshots everything the replay
  carries (cache state, windowed accumulators, expert weights, traffic
  generator state, fluid backlog) as host data; a later process resumes
  bit-exactly mid-stream.

**Multi-tenant attribution.** ``tenant_mix`` traffic
(:func:`repro.core.traffic.tenant_mix`) is generated chunk-by-chunk on the
host (:class:`repro.core.traffic.TenantStream` — never materialized
whole), and per-tenant windowed counters cost no extra engine pass: the
windowed scatter runs over composite ``window * n_tenants + tenant`` ids,
and the host collapses the composite axis back into per-window totals
(sum over tenants) plus per-tenant series (sum over shards).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional

import numpy as np

import jax

from repro.core.online_learning import EXPERTS
from repro.core.queuing import transient_two_tier
from repro.core.traffic import TenantStream
from repro.kernels.backend import XLA, record_paths
from repro.sim.engine import (
    SimReport,
    TenantCounters,
    Tier1Counters,
    _assemble_counters,
    fault_owner,
    report_from_counters,
    stream_for_spec,
)
from repro.sim.spans import count, span
from repro.sim.spec import SimSpec
from repro.storage.tiered_store import (
    init_stream_carry,
    partition_streams,
    stream_chunk_engine,
    stream_stats_from_carry,
    stream_window_ids,
    timestamp_window_ids,
)

__all__ = [
    "DEFAULT_CHUNK",
    "StreamCheckpoint",
    "stream_tier1_counters",
    "simulate_stream",
]

# Default requests per chunk. Large enough that per-chunk dispatch overhead
# amortizes, small enough that one chunk's device buffers stay modest.
DEFAULT_CHUNK = 1 << 18

# Floor of the primary per-shard length bucket (balanced-load sizing).
MIN_CAP = 512


def _next_pow2(n: int) -> int:
    cap = 1
    while cap < n:
        cap <<= 1
    return cap


def _chunk_cap(hot: int, chunk: int, n_shards: int) -> int:
    """The per-shard length bucket of a chunk whose hottest shard holds
    ``hot`` requests: ``next_pow2(hot)``, clamped below at the primary
    bucket and above at the fallback.

    The primary bucket assumes roughly balanced shard loads (2x headroom
    over ``chunk / n_shards``, at least :data:`MIN_CAP`); the fallback fits
    any chunk (one shard owning everything). Between them lies a ladder of
    powers of two, so a skewed chunk scans the rung its hot shard needs,
    never more than the fallback, and a replay compiles the engine at most
    ``log2(fallback / primary) + 1`` times, no matter how many chunks
    stream through."""
    fallback = _next_pow2(max(chunk, 1))
    primary = min(_next_pow2(max(MIN_CAP, -(-2 * chunk // n_shards))),
                  fallback)
    return min(max(_next_pow2(hot), primary), fallback)


@dataclasses.dataclass
class StreamCheckpoint:
    """Everything a chunked replay carries between chunks, as host data.

    Snapshot of a replay frontier: the per-shard cache/learner state and
    windowed accumulators (``carry`` — numpy copies of the chunk-engine
    carry, safe to pickle), the consumed-request offset and per-shard
    tallies, the traffic generator's mid-stream state (``tenant_state``,
    tenant workloads only), the host-tracked last-tenant table behind
    windowed expert-weight attribution, and the pooled fluid backlog
    ``fluid_q0 = (q1, q2)`` at the frontier — the ``q0`` a continuation
    transient solve resumes from. Resuming validates ``signature`` (the
    spec's :meth:`~repro.sim.spec.SimSpec.cache_signature`) plus the
    stream's length and page space, so a checkpoint cannot silently
    continue a different workload."""

    signature: tuple
    offset: int                  # requests consumed so far
    total: int                   # total requests of the stream
    counts: np.ndarray           # [n_shards] real requests per shard
    shard_writes: np.ndarray     # [n_shards] writes per shard
    carry: object                # host-numpy (StoreState, _Accum) pytree
    n_pages: int
    n_windows: int               # plain window count W (not composite)
    n_tenants: int               # 0 = single-tenant replay
    tenant_state: Optional[dict] = None
    last_tenant: Optional[np.ndarray] = None   # [n_shards, W], -1 = empty
    fluid_q0: Optional[tuple] = None           # (q1, q2) at the frontier

    @property
    def done(self) -> bool:
        return self.offset >= self.total


def _validate_resume(ck: StreamCheckpoint, signature: tuple, total: int,
                     n_pages: int, n_windows: int, n_tenants: int) -> None:
    if ck.signature != signature:
        raise ValueError(
            "StreamCheckpoint does not match this spec (cache_signature "
            "differs) — a checkpoint resumes only the workload it snapshot")
    if (ck.total, ck.n_pages, ck.n_windows, ck.n_tenants) != (
            total, n_pages, n_windows, n_tenants):
        raise ValueError(
            "StreamCheckpoint stream layout mismatch: checkpoint has "
            f"(total={ck.total}, n_pages={ck.n_pages}, "
            f"n_windows={ck.n_windows}, n_tenants={ck.n_tenants}), replay "
            f"has ({total}, {n_pages}, {n_windows}, {n_tenants})")


def stream_tier1_counters(
    spec: SimSpec,
    trace=None,
    *,
    chunk: int = DEFAULT_CHUNK,
    unroll: int = 1,
    checkpoint: Optional[StreamCheckpoint] = None,
    max_requests: Optional[int] = None,
    donate: bool = True,
    engine: str = "fused",
    profile: Optional[dict] = None,
):
    """Chunked-replay counterpart of :func:`repro.sim.engine.tier1_counters`.

    Returns ``(counters, tenant_counters, checkpoint)``:
    :class:`Tier1Counters` bit-identical to the one-shot engine's for the
    consumed prefix, :class:`TenantCounters` for ``tenant_mix`` workloads
    (``None`` otherwise), and the :class:`StreamCheckpoint` at the final
    frontier (``checkpoint.done`` when the stream is exhausted).

    ``tenant_mix`` specs are generated chunk-by-chunk on the host; any
    other spec (or an explicit ``trace``) is materialized host-side once
    (exactly the one-shot stream) and *fed* in chunks — device memory
    stays bounded either way. ``checkpoint`` resumes a prior partial run;
    ``max_requests`` bounds how many further requests this call consumes
    (``None`` = run to the end). ``donate=False`` disables buffer donation
    and async overlap — the naive baseline the benchmarks compare
    against. ``engine`` selects the fused cache-scan request loop
    (default) or the original ``"scan"`` reference (bit-exact either way).

    ``profile`` (a mutable dict) accumulates the replay's stage spans
    (:func:`repro.sim.spans.span`, seconds): ``stream_resume_prep`` (the
    whole-trace stream, window binning and owner map of a given trace),
    ``stream_chunk_host`` (generation + binning + partitioning of a
    chunk), ``stream_chunk_dispatch`` (device_put + async engine
    submission), ``stream_chunk_wait`` (blocking materialization of the
    final carry; per-chunk blocking too when ``donate=False``) and
    ``stream_engine`` (from the first chunk's submission to the carry on
    the host), and the counters ``stream_chunks`` (chunks),
    ``stream_scan_steps`` (engine steps: ``cap * n_shards`` a chunk, pads
    included) and ``stream_requests`` (the chunks' real requests). The
    learner's work is counted on the host from the carry: over the call,
    ``stream_evictions`` (evictions, summed over shards) and
    ``stream_evictions_lru``, ``stream_evictions_lfu`` and
    ``stream_evictions_random`` (those each expert named)."""
    if chunk < 1:
        raise ValueError("chunk must be >= 1")
    prof = profile
    n_shards = spec.n_shards
    signature = spec.cache_signature()
    tenant = spec.traffic.kind == "tenant_mix" and trace is None
    if tenant:
        gen = TenantStream(spec.traffic)
        n_tenants = gen.n_tenants
        total = spec.traffic.n_requests
        n_pages = spec.traffic.n_pages   # tenant key ranges are bounded
        n_windows, window_dt = spec.window_grid()
        pages = is_write = times = owner_all = gwin_all = None
    else:
        gen = None
        n_tenants = 0
        with span("stream_resume_prep", prof):
            pages, is_write, times, n_pages, n_windows, window_dt = (
                stream_for_spec(spec, trace))
            total = int(pages.shape[0])
            # Whole-stream host precompute, identical to the one-shot
            # path: window binning (float64) and the fault-schedule owner
            # remap are global maps, so chunking cannot perturb them.
            if window_dt is not None:
                gwin_all = timestamp_window_ids(times, n_windows, window_dt)
            else:
                gwin_all = stream_window_ids(total, n_windows)
            owner_all = fault_owner(spec, pages, times, n_pages)
    # Composite window ids interleave the tenant axis into the engine's
    # windowed scatter: id = window * n_tenants + tenant. The engine runs
    # at W * n_tenants windows; the host collapses the axis afterwards.
    eng_windows = n_windows * max(n_tenants, 1)

    if checkpoint is not None:
        _validate_resume(checkpoint, signature, total, n_pages, n_windows,
                         n_tenants)
        offset = int(checkpoint.offset)
        counts = np.asarray(checkpoint.counts, np.int64).copy()
        shard_writes = np.asarray(checkpoint.shard_writes, np.int64).copy()
        carry = jax.device_put(checkpoint.carry)
        last_tenant = (np.asarray(checkpoint.last_tenant, np.int32).copy()
                       if tenant else None)
        if tenant:
            gen.restore(checkpoint.tenant_state)
    else:
        offset = 0
        counts = np.zeros(n_shards, np.int64)
        shard_writes = np.zeros(n_shards, np.int64)
        carry = init_stream_carry(spec.store, n_shards,
                                  n_windows=eng_windows)
        last_tenant = (np.full((n_shards, n_windows), -1, np.int32)
                       if tenant else None)

    start = offset
    stop = total if max_requests is None else min(total,
                                                  offset + int(max_requests))
    eng = stream_chunk_engine(spec.store, unroll=unroll,
                              n_windows=eng_windows, donate=donate,
                              engine=engine)
    hyper = spec.store.hyper()

    # stream_engine opens at the first chunk's submission and closes with
    # the carry on the host: the span the chunk pipeline runs under.
    with contextlib.ExitStack() as engine_span:
        while offset < stop:
            m = min(chunk, stop - offset)
            with span("stream_chunk_host", prof):
                if tenant:
                    p, w, t, tids = gen.take(m)
                    own = fault_owner(spec, p, t, n_pages)
                    if window_dt is not None:
                        win = timestamp_window_ids(t, n_windows, window_dt)
                    else:
                        g = offset + np.arange(m, dtype=np.int64)
                        win = ((g * n_windows) // total).astype(np.int32)
                    # Last tenant per (shard, window): duplicate fancy-
                    # index assignment keeps the final occurrence —
                    # exactly "the tenant of this shard's last request in
                    # this window so far".
                    last_tenant[own, win] = tids
                    cwin = win * n_tenants + tids
                else:
                    sl = slice(offset, offset + m)
                    p, w = pages[sl], is_write[sl]
                    own, cwin = owner_all[sl], gwin_all[sl]
                cnt = np.bincount(own, minlength=n_shards)
                cap = _chunk_cap(int(cnt.max()), chunk, n_shards)
                sh_p, sh_w, cnt, _, sh_win = partition_streams(
                    p, w, n_shards=n_shards, mapping=spec.mapping,
                    n_pages=n_pages, cap=cap, n_windows=eng_windows,
                    window_ids=cwin, owner=own)
                counts += cnt
                shard_writes += np.bincount(own[w], minlength=n_shards)
            if offset == start:
                engine_span.enter_context(span("stream_engine", prof))
            # Async pipeline: device_put + dispatch return before the chunk
            # finishes computing, so the next iteration's host work
            # (generate, bin, partition) overlaps device compute.
            # donate=False is the deliberately-synchronous naive baseline.
            with span("stream_chunk_dispatch", prof):
                dev = jax.device_put((sh_p, sh_w, sh_win))
                carry = eng(hyper, carry, *dev)
                # The resumable chunk mode has one implementation, the XLA
                # engine.
                record_paths("cache_scan", np.full(n_shards, XLA))
            with span("stream_chunk_wait", prof):
                if not donate:
                    jax.block_until_ready(carry)
            offset += m
            count("stream_chunks", prof)
            count("stream_scan_steps", prof, cap * n_shards)
            count("stream_requests", prof, m)

        # Materialize the carry on the host once: the numpy copies survive
        # the next resume's donation, feed the counter assembly below, and
        # make the checkpoint picklable.
        with span("stream_chunk_wait", prof):
            carry_host = jax.tree.map(np.asarray, carry)
    if prof is not None:
        _count_evictions(prof, carry_host[1],
                         None if checkpoint is None else checkpoint.carry[1])
    stats = stream_stats_from_carry(carry_host, counts)

    tenant_ctr = None
    if tenant:
        def collapse(a):
            a = np.asarray(a)
            return a.reshape(n_shards, n_windows, n_tenants,
                             *a.shape[2:]).sum(axis=2)

        # Windowed expert weights: the engine snapshot lives per composite
        # sub-window; the plain window's snapshot is the one at the shard's
        # last request in the window, i.e. the last-tenant sub-window.
        ww = np.asarray(stats.win_weights)
        wwr = ww.reshape(n_shards, n_windows, n_tenants, ww.shape[-1])
        sel = np.maximum(last_tenant, 0)[:, :, None, None]
        w_sel = np.take_along_axis(wwr, sel, axis=2)[:, :, 0, :]
        w_sel = np.where((last_tenant >= 0)[:, :, None], w_sel, 0.0)
        per_tw = np.asarray(stats.win_requests).reshape(
            n_shards, n_windows, n_tenants)
        tenant_ctr = TenantCounters(
            names=tuple(t.name for t in spec.traffic.tenants),
            win_requests=per_tw.sum(axis=0).T,
            win_hits=np.asarray(stats.win_hits).reshape(
                n_shards, n_windows, n_tenants).sum(axis=0).T,
            win_misses=np.asarray(stats.win_misses).reshape(
                n_shards, n_windows, n_tenants).sum(axis=0).T,
        )
        stats = stats._replace(
            win_requests=collapse(stats.win_requests),
            win_hits=collapse(stats.win_hits),
            win_misses=collapse(stats.win_misses),
            win_prefetch_hits=collapse(stats.win_prefetch_hits),
            win_tier2_reads=collapse(stats.win_tier2_reads),
            win_tier2_writes=collapse(stats.win_tier2_writes),
            win_evictions=collapse(stats.win_evictions),
            win_expert_use=collapse(stats.win_expert_use),
            win_weights=w_sel,
        )
    # Masked pads never touched the accumulators, so no padding correction
    # applies — _assemble_counters consumes the stats as-is.
    ctr = _assemble_counters(stats, counts, shard_writes)

    ck = StreamCheckpoint(
        signature=signature,
        offset=offset,
        total=total,
        counts=counts.copy(),
        shard_writes=shard_writes.copy(),
        carry=carry_host,
        n_pages=n_pages,
        n_windows=n_windows,
        n_tenants=n_tenants,
        tenant_state=gen.state() if tenant else None,
        last_tenant=last_tenant.copy() if tenant else None,
    )
    return ctr, tenant_ctr, ck


def _count_evictions(prof: dict, acc, acc0) -> None:
    """Add the evictions a call made, and each expert's share, to the
    profile's counters: the accumulators' growth from ``acc0`` (``None``
    for a fresh replay) to ``acc``, summed over shards."""
    ev = int(np.sum(acc.evictions))
    use = np.sum(acc.expert_use, axis=0, dtype=np.int64)
    if acc0 is not None:
        ev -= int(np.sum(acc0.evictions))
        use = use - np.sum(acc0.expert_use, axis=0, dtype=np.int64)
    count("stream_evictions", prof, ev)
    for name, n in zip(EXPERTS, use.tolist()):
        count("stream_evictions_" + name, prof, n)


def _frontier_fluid_q0(spec: SimSpec, rep: SimReport) -> Optional[tuple]:
    """Pooled fluid backlog ``(q1, q2)`` at the consumed frontier of a
    partial replay: the fluid solve re-run over the non-empty prefix of
    the window grid (the report's own solve includes the trailing not-yet-
    streamed windows, which drain the backlog as if the stream had gone
    idle). Healthy service rates — a continuation solve under a fault
    schedule should re-solve from the counters instead."""
    if spec.transient_mode != "fluid" or rep.window_duration_s <= 0:
        return None
    pooled = np.asarray(rep.windows.requests).sum(axis=0)
    nz = np.nonzero(pooled)[0]
    if nz.size == 0:
        return None
    hi = int(nz[-1]) + 1
    rates = spec.rates.resolve()
    tr = rep.transient
    sol = transient_two_tier(
        np.asarray(tr.lam)[:hi], np.asarray(tr.p12)[:hi],
        rates.mu1, rates.mu2, k=spec.k_servers, flow=spec.flow,
        mode="fluid", dt=rep.window_duration_s, mu_load=rates.mu_load,
    )
    return (np.asarray(sol.q1_end), np.asarray(sol.q2_end))


def simulate_stream(
    spec: SimSpec,
    trace=None,
    *,
    chunk: int = DEFAULT_CHUNK,
    unroll: int = 1,
    checkpoint: Optional[StreamCheckpoint] = None,
    max_requests: Optional[int] = None,
    donate: bool = True,
    engine: str = "fused",
    profile: Optional[dict] = None,
):
    """Streaming counterpart of :func:`repro.sim.engine.simulate`.

    Replays the workload in bounded-memory chunks
    (:func:`stream_tier1_counters`) and solves the queuing network on the
    streamed counters. The resulting :class:`SimReport` is bit-identical
    to ``simulate(spec)``'s for every counter and windowed series, at a
    peak device footprint independent of trace length; ``tenant_mix``
    workloads additionally carry per-tenant
    :class:`~repro.sim.engine.TenantReport` attribution.

    With ``max_requests`` set the call returns ``(report, checkpoint)``:
    the report covers the consumed prefix (untouched windows are idle) and
    the checkpoint — including the pooled fluid backlog at the frontier —
    resumes the replay bit-exactly via ``checkpoint=``. Without it the
    call runs to the end of the stream and returns the report alone."""
    ctr, tenant_ctr, ck = stream_tier1_counters(
        spec, trace, chunk=chunk, unroll=unroll, checkpoint=checkpoint,
        max_requests=max_requests, donate=donate, engine=engine,
        profile=profile)
    rep = report_from_counters(spec, ctr, tenants=tenant_ctr)
    if max_requests is None:
        return rep
    ck.fluid_q0 = _frontier_fluid_q0(spec, rep)
    return rep, ck
