"""Tier-1 counters from the per-step reference, for the engine parity tests.

:func:`reference_tier1_counters` follows
:func:`repro.sim.engine.tier1_counters` step for step, with
:func:`repro.storage.tiered_store.run_stream_reference` in place of the
engine; :func:`reference_tenant_counters` resolves the same run over
composite ``window x tenant`` ids, the per-tenant attribution of a
``tenant_mix`` workload.
"""
import numpy as np

import jax
import jax.numpy as jnp

from repro.core.traffic import tenant_mix_stream
from repro.sim.engine import (
    TenantCounters,
    _assemble_counters,
    fault_owner,
    stream_for_spec,
)
from repro.storage.tiered_store import (
    correct_padded_stats,
    partition_streams,
    run_stream_reference,
    stream_window_ids,
    timestamp_window_ids,
)


def _window_ids(times, n, n_windows, window_dt):
    if window_dt is not None:
        return timestamp_window_ids(times, n_windows, window_dt)
    return stream_window_ids(n, n_windows)


def _reference_stats(spec, pages, is_write, owner, window_ids, n_windows,
                     n_pages):
    """Padding-corrected per-shard StreamStats of the reference run, and
    the per-shard request counts."""
    sh_pages, sh_writes, counts, _, sh_win = partition_streams(
        pages, is_write, n_shards=spec.n_shards, mapping=spec.mapping,
        n_pages=n_pages, n_windows=n_windows, window_ids=window_ids,
        owner=owner)
    stats = jax.vmap(
        lambda p, w, wi: run_stream_reference(
            spec.store, p, w, n_windows=n_windows, window_ids=wi)
    )(jnp.asarray(sh_pages), jnp.asarray(sh_writes), jnp.asarray(sh_win))
    return correct_padded_stats(stats, counts, sh_pages.shape[1]), counts


def reference_tier1_counters(spec, trace=None):
    """:func:`repro.sim.engine.tier1_counters` on the per-step reference."""
    pages, is_write, times, n_pages, n_windows, window_dt = stream_for_spec(
        spec, trace)
    owner = fault_owner(spec, pages, times, n_pages)
    stats, counts = _reference_stats(
        spec, pages, is_write, owner,
        _window_ids(times, len(pages), n_windows, window_dt), n_windows,
        n_pages)
    writes = np.bincount(owner[is_write], minlength=spec.n_shards)
    return _assemble_counters(stats, counts, writes)


def reference_tenant_counters(spec):
    """Per-tenant windowed counters of a ``tenant_mix`` spec, pooled over
    shards, from one reference run over composite window ids."""
    pages, is_write, times, tids = tenant_mix_stream(spec.traffic)
    n_windows, window_dt = spec.window_grid()
    n_tenants = len(spec.traffic.tenants)
    n_pages = spec.traffic.n_pages
    owner = fault_owner(spec, pages, times, n_pages)
    win = _window_ids(times, len(pages), n_windows, window_dt)
    stats, _ = _reference_stats(spec, pages, is_write, owner,
                                win * n_tenants + tids,
                                n_windows * n_tenants, n_pages)

    def per_tenant(a):
        return np.asarray(a).reshape(spec.n_shards, n_windows,
                                     n_tenants).sum(axis=0).T

    return TenantCounters(
        names=tuple(t.name for t in spec.traffic.tenants),
        win_requests=per_tenant(stats.win_requests),
        win_hits=per_tenant(stats.win_hits),
        win_misses=per_tenant(stats.win_misses),
    )
