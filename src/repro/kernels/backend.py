"""Backend dispatch for the simulator's Pallas kernels: one switch.

Each engine that has a Pallas kernel (the tier-1 cache scan, the
reuse-distance pass) also has an XLA implementation of the same semantics.
:func:`kernel_or_xla` stages both and lets :func:`jax.lax.platform_dependent`
choose by the platform the enclosing computation is *lowered* for: a TPU
gets the compiled kernel, every other platform the XLA engine. A CPU-placed
oracle in a process that also drives a TPU therefore still takes the XLA
path, and nothing reads an environment variable. Interpret-mode Pallas is
an explicit ``interpret=True`` argument of each kernel, for tests only.

Each branch also returns its path id (:data:`PALLAS` or :data:`XLA`) as an
int32 result, so what ran is read back from the outputs rather than
assumed: the callers hand the ids to :func:`record_paths`, and
:func:`engine_path_counts` reports how many calls (stream rows for the
cache scan, distance passes for the reuse-distance kernel) took each path.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = [
    "PALLAS",
    "XLA",
    "kernel_or_xla",
    "record_paths",
    "engine_path_counts",
    "reset_engine_path_counts",
]

XLA, PALLAS = 0, 1
_COUNTS: dict[str, dict[str, int]] = {}


def kernel_or_xla(kernel_fn, xla_fn, *args):
    """``(kernel_fn(*args), PALLAS)`` where the computation is lowered for a
    TPU, ``(xla_fn(*args), XLA)`` elsewhere. Both callables must return
    the same pytree of shapes and dtypes."""
    return jax.lax.platform_dependent(
        *args,
        tpu=lambda *a: (kernel_fn(*a), jnp.int32(PALLAS)),
        default=lambda *a: (xla_fn(*a), jnp.int32(XLA)),
    )


def record_paths(engine: str, paths) -> None:
    """Count the path ids an engine's outputs carried (one per call)."""
    paths = np.asarray(paths).reshape(-1)
    c = _COUNTS.setdefault(engine, {"pallas": 0, "xla": 0})
    c["pallas"] += int(np.count_nonzero(paths == PALLAS))
    c["xla"] += int(np.count_nonzero(paths == XLA))


def engine_path_counts() -> dict[str, dict[str, int]]:
    """``{engine: {"pallas": n, "xla": m}}`` since the last reset."""
    return {k: dict(v) for k, v in _COUNTS.items()}


def reset_engine_path_counts() -> None:
    _COUNTS.clear()
