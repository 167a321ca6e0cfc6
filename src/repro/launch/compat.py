"""JAX environment shims shared by the entry points and the sweep engine.

Small, dependency-free home for the SPMD helpers used by both the
heavyweight launch layer (:mod:`repro.launch.spmd`) and light consumers like
the sweep engine (:mod:`repro.sim.sweep`), which must not drag the model /
training stack into their import graph, plus the one helper that places
JAX's persistent compilation cache for the entry points.
"""
from __future__ import annotations

import os

import numpy as np

import jax
from jax import shard_map
from jax.sharding import Mesh

__all__ = ["shard_map", "device_mesh", "use_compile_cache"]


def device_mesh(axis_name: str, devices=None) -> Mesh:
    """A 1-D mesh over ``devices`` (default: all *local* devices, so callers
    that pad host-side batches to ``jax.local_device_count()`` agree with the
    mesh size even under multi-process jax)."""
    devs = list(jax.local_devices() if devices is None else devices)
    return Mesh(np.asarray(devs), (axis_name,))


def use_compile_cache(checkout: str) -> str:
    """Point JAX's persistent compilation cache at a fixed directory and
    return it. ``JAX_COMPILATION_CACHE_DIR``, when set, is honoured as JAX
    reads it and nothing else is configured; otherwise the cache lives at
    ``<checkout>/.jax_cache`` (a fixed path: the directory is part of the
    cache key, so one that moves between runs never hits). Called by the
    entry points only — importing the library configures nothing."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
