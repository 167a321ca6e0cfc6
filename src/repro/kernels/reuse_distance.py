"""Reuse-distance (Mattson LRU stack distance) extraction kernel.

Per request, the number of *distinct* keys touched since that key's last
access — the quantity the classic stack-distance / miss-ratio-curve
formulation is built on: under fully-associative LRU of capacity ``C`` a
request hits iff its reuse distance ``d < C``, so one pass over the stream
yields exact hit/miss counters for *every* cache size at once
(:mod:`repro.sim.mrc` builds the counters; this module computes ``d``).

The distance is reduced to a 2-D dominance count over the host-computed
previous-occurrence index ``P`` (``P[j]`` = index of the previous access of
``pages[j]`` within its shard row, ``-1`` for a first access):

    d_j = #{ k : P[j] < k < j  and  P[k] <= P[j]  and  valid[k] }

(the in-gap positions that are the *first* in-gap occurrence of their
page). The Pallas kernel tiles this count as ``[128, 128]``
broadcast-compares per ``(shard, query-block)`` grid cell, streaming key
chunks from HBM over the range that can hold counted keys — at most
O(L^2/2) compares, VPU-friendly, no inter-step dependence (contrast the
sequential per-request ``lax.scan`` of the cache engine). Distances never
leak across shard rows (each grid cell reads only its own row) or into pad
slots (pads output ``-1`` and are excluded from every count).

The production entry point :func:`reuse_distances` runs the Pallas kernel
where the computation is lowered for a TPU and the pure-jax
:func:`repro.kernels.ref.reuse_distance_ref` elsewhere (same math, same
int32 results — bit-identical; :mod:`repro.kernels.backend`). The
interpret-mode kernel stays testable everywhere
(``reuse_distance_kernel(..., interpret=True)``).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import kernel_or_xla, record_paths
from repro.kernels.ref import DIST_INF, reuse_distance_ref

__all__ = [
    "DIST_INF",
    "prev_occurrence",
    "reuse_distance_kernel",
    "reuse_distances",
    "reuse_compile_count",
    "reset_reuse_compile_count",
]

# Key rows (of 128 positions) per streamed key chunk: 8192 keys, 32 KiB.
KEY_ROWS = 64
# Query rows (of 128 positions) per grid cell.
Q_ROWS = 8
_BIG = jnp.iinfo(jnp.int32).max

# Trace-time compile counter for the jitted distance engines (both the
# Pallas wrapper and the ref fallback) — the MRC bench gates on it exactly
# like benchmarks/bench_sweep.py gates on engine_compile_count().
_REUSE_COMPILES = [0]


def reuse_compile_count() -> int:
    """Number of XLA compiles of the distance engine so far."""
    return _REUSE_COMPILES[0]


def reset_reuse_compile_count() -> None:
    _REUSE_COMPILES[0] = 0


def prev_occurrence(sh_pages: np.ndarray, counts: np.ndarray):
    """Previous-occurrence index per request, host-side.

    ``sh_pages`` is the ``[S, L]`` partitioned key stream (per-shard
    substreams, padded at the row tails — :func:`repro.storage.
    tiered_store.partition_streams` layout); ``counts[s]`` is the number of
    real requests in row ``s``. Returns ``(prev, valid)``: int32 ``[S, L]``
    with ``prev[s, j]`` = column of the previous access of ``sh_pages[s,
    j]`` within row ``s`` (``-1`` if first access), and the bool ``[S, L]``
    real-position mask. Pads carry ``prev = -1`` and ``valid = False`` and
    never link to (or from) real positions; rows are fully independent.

    One vectorized lexsort over ``(shard, page, position)`` — O(T log T).
    """
    sh_pages = np.asarray(sh_pages)
    counts = np.asarray(counts)
    S, L = sh_pages.shape
    valid = np.arange(L)[None, :] < counts[:, None]
    shard = np.repeat(np.arange(S, dtype=np.int64), L)
    page = sh_pages.reshape(-1).astype(np.int64)
    pos = np.tile(np.arange(L, dtype=np.int64), S)
    idx = np.flatnonzero(valid.reshape(-1))
    order = idx[np.lexsort((pos[idx], page[idx], shard[idx]))]
    prev = np.full(S * L, -1, np.int64)
    if order.size > 1:
        same = (shard[order[1:]] == shard[order[:-1]]) & (
            page[order[1:]] == page[order[:-1]]
        )
        prev[order[1:][same]] = pos[order[:-1][same]]
    return prev.reshape(S, L).astype(np.int32), valid


def _dominance_kernel(q_ref, k_hbm, o_ref, kbuf, sem, *, key_rows: int,
                      q_rows: int):
    """One ``(shard, query-block)`` grid cell of the dominance count.

    ``q_ref`` holds ``q_rows * 128`` queries lane-dense (``prev``, ``-2`` at
    pads); one in-register transpose turns each 128-query row into a
    ``[128, 1]`` column that is compared against ``[1, 128]`` key rows.
    Keys stay in HBM as ``[chunks, key_rows, 128]`` per shard (``prev``,
    int32 max at pads) and stream through a two-slot VMEM buffer, one chunk
    of ``key_rows * 128`` positions per DMA, so VMEM does not grow with the
    row length. Only chunks that can hold a counted key are fetched: keys
    after a column's last query, or at or before its smallest ``prev``,
    never satisfy ``prev[j] < k < j``.
    """
    s, jb = pl.program_id(0), pl.program_id(1)
    kb = key_rows * 128
    qt = q_ref[...].T                                    # [128, q_rows]
    sub = jax.lax.broadcasted_iota(jnp.int32, (128, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (128, q_rows), 1)

    def fetch(c, slot):
        return pltpu.make_async_copy(k_hbm.at[s, c], kbuf.at[slot],
                                     sem.at[slot])

    out = jnp.zeros((128, q_rows), jnp.int32)
    for r in range(q_rows):
        pj = qt[:, r:r + 1]                              # [128, 1]
        j0 = (jb * q_rows + r) * 128
        jidx = j0 + sub
        lo = jnp.min(jnp.where(pj >= 0, pj, _BIG))
        c_hi = (j0 + 127) // kb
        c_lo = jnp.where(lo == _BIG, c_hi + 1, (lo + 1) // kb)

        @pl.when(c_lo <= c_hi)
        def _():
            fetch(c_lo, 0).start()

        def chunk(c, acc):
            slot = (c - c_lo) % 2

            @pl.when(c + 1 <= c_hi)
            def _():
                fetch(c + 1, 1 - slot).start()

            fetch(c, slot).wait()

            def row(i, acc):
                pk = kbuf[slot, pl.ds(i, 1), :]          # [1, 128]
                kidx = c * kb + i * 128 + lane
                m = (kidx > pj) & (kidx < jidx) & (pk <= pj)
                return acc + m.astype(jnp.int32)

            return jax.lax.fori_loop(0, key_rows, row, acc)

        acc = jax.lax.fori_loop(c_lo, c_hi + 1, chunk,
                                jnp.zeros((128, 128), jnp.int32))
        d = jnp.sum(acc, axis=1, keepdims=True)
        d = jnp.where(pj == -2, -1, jnp.where(pj >= 0, d, DIST_INF))
        out = jnp.where(col == r, d, out)
    o_ref[...] = out.T


@functools.partial(jax.jit, static_argnames=("interpret",))
def reuse_distance_kernel(
    prev: jnp.ndarray,   # int32[S, L] previous-occurrence index (-1 = first)
    valid: jnp.ndarray,  # bool[S, L]  real positions (False = padding)
    *,
    interpret: bool = False,
) -> jnp.ndarray:
    """Pallas dominance-count kernel: int32 ``[S, L]`` reuse distances
    (:data:`DIST_INF` for first accesses, ``-1`` at pad slots). Exact
    integer arithmetic — bit-identical to :func:`repro.kernels.ref.
    reuse_distance_ref`."""
    prev = jnp.asarray(prev, jnp.int32)
    valid = jnp.asarray(valid, bool)
    S, L = prev.shape
    key_rows = min(KEY_ROWS, pl.next_power_of_2(-(-L // 128)))
    q_rows = min(Q_ROWS, key_rows)
    kb = key_rows * 128
    Lp = -(-L // kb) * kb
    pad = ((0, 0), (0, Lp - L))
    queries = jnp.pad(jnp.where(valid, prev, -2), pad, constant_values=-2)
    keys = jnp.pad(jnp.where(valid, prev, _BIG), pad, constant_values=_BIG)
    rows = pl.BlockSpec((None, q_rows, 128), lambda s, jb: (s, jb, 0))
    out = pl.pallas_call(
        functools.partial(_dominance_kernel, key_rows=key_rows,
                          q_rows=q_rows),
        grid=(S, Lp // (q_rows * 128)),
        in_specs=[rows, pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((S, Lp // 128, 128), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((2, key_rows, 128), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="reuse_distance",
    )(queries.reshape(S, Lp // 128, 128),
      keys.reshape(S, Lp // kb, key_rows, 128))
    return out.reshape(S, Lp)[:, :L]


@functools.partial(jax.jit, static_argnames=("block",))
def _engine(prev, valid, *, block: int):
    _REUSE_COMPILES[0] += 1  # trace-time: once per XLA compile
    return kernel_or_xla(
        reuse_distance_kernel,
        functools.partial(reuse_distance_ref, block=block), prev, valid)


def reuse_distances(prev: np.ndarray, valid: np.ndarray, *,
                    block: int = 128) -> jnp.ndarray:
    """Production entry point: reuse distances ``[S, L]`` from the Pallas
    kernel where the computation is lowered for a TPU, from
    :func:`~repro.kernels.ref.reuse_distance_ref` (query blocks of
    ``block``) elsewhere — same int32 results, see
    :mod:`repro.kernels.backend`. Counts the path that ran under
    ``"reuse_distance"``."""
    dist, path = _engine(jnp.asarray(prev, jnp.int32),
                         jnp.asarray(valid, bool), block=block)
    record_paths("reuse_distance", path)
    return dist
