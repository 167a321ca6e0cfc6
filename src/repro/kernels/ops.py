"""Jit'd public wrappers for the Pallas kernels.

The kernels compile for the TPU; ``interpret=True`` runs them in Pallas
interpret mode on any platform (tests). :func:`reuse_distances` dispatches
by platform itself (:mod:`repro.kernels.backend`). The pure-jnp oracles
live in :mod:`repro.kernels.ref`.
"""
from __future__ import annotations

from typing import Optional

from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.page_gather import page_copy as _page_copy
from repro.kernels.paged_attention import paged_attention as _paged
from repro.kernels.reuse_distance import reuse_distances as _reuse
from repro.kernels.rglru_scan import rglru_scan_kernel as _rglru
from repro.kernels.ssd_scan import ssd_scan_kernel as _ssd

__all__ = ["flash_attention", "paged_attention", "page_copy",
           "reuse_distances", "rglru_scan", "ssd_scan"]


def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    block_q=128, block_kv=128, interpret: bool = False):
    return _flash(q, k, v, causal=causal, window=window, block_q=block_q,
                  block_kv=block_kv, interpret=interpret)


def paged_attention(q, pool, page_slot, lengths, *,
                    interpret: bool = False):
    return _paged(q, pool, page_slot, lengths, interpret=interpret)


def page_copy(dst, src, dst_idx, src_idx, *, interpret: bool = False):
    return _page_copy(dst, src, dst_idx, src_idx, interpret=interpret)


def reuse_distances(prev, valid, *, block=128):
    """Reuse (LRU stack) distances per request — Pallas dominance-count
    kernel on a TPU, the bit-identical pure-jax
    :func:`repro.kernels.ref.reuse_distance_ref` elsewhere."""
    return _reuse(prev, valid, block=block)


def rglru_scan(u, w_a, b_a, w_x, b_x, lam, *, block_w=128, chunk=128,
               interpret: bool = False):
    return _rglru(u, w_a, b_a, w_x, b_x, lam, block_w=block_w, chunk=chunk,
                  interpret=interpret)


def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, interpret: bool = False):
    return _ssd(x, dt, A, Bm, Cm, chunk=chunk, interpret=interpret)
