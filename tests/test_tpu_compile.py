"""Compile rehearsal: the main path's Pallas kernels compile for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology. Each test compiles one program at the widths ``chip_smoke.py``
runs and checks that the kernel is in it (``tpu_custom_call``). What the
compiler refuses here — unaligned blocks, scalar reads it cannot prove,
more VMEM than the kernel may use — would otherwise surface only on the
chip. The topology is described in a fixture (never at import), and only
this file does so.
"""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.cache_scan import cache_scan_kernel, kernel_fits
from repro.kernels.reuse_distance import reuse_distance_kernel
from repro.sim.sweep import _batched_engine
from repro.storage.tiered_store import StoreConfig, StoreHyper

# chip_smoke.py phase B (bench_engine's grid x4): busiest-shard bucket,
# lines per shard, windows; phase A/C: 16 shards, per-shard bucket.
PHASE_B_LEN, PHASE_B_LINES, N_WINDOWS = 8192, 256, 32
PHASE_C_SHARDS, PHASE_C_LEN = 16, 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # A compile for a described chip cannot be read back from the
    # persistent cache; keep these out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _cache_scan_text(one_chip, length, n_lines, *, prefetch, policy_idx):
    def fn(pages, writes, win, noise, knobs):
        return cache_scan_kernel(
            pages, writes, win, noise, knobs[0], knobs[1], knobs[2],
            policy_idx, n_lines=n_lines, prefetch=prefetch,
            n_windows=N_WINDOWS)

    i32 = jnp.int32
    return _compiled_text(
        fn, _shape(one_chip, (1, length), i32),
        _shape(one_chip, (1, length), i32),
        _shape(one_chip, (1, length), i32),
        _shape(one_chip, (length, n_lines), jnp.float32),
        _shape(one_chip, (3,), jnp.float32))


@pytest.mark.parametrize("prefetch,policy_idx", [(False, -1), (True, 0)],
                         ids=["ws", "lru_prefetch"])
def test_cache_scan_kernel_compiles_at_phase_b_widths(one_chip, prefetch,
                                                      policy_idx):
    assert kernel_fits(PHASE_B_LEN, PHASE_B_LINES)
    text = _cache_scan_text(one_chip, PHASE_B_LEN, PHASE_B_LINES,
                            prefetch=prefetch, policy_idx=policy_idx)
    assert "tpu_custom_call" in text


def test_cache_scan_kernel_compiles_at_vmem_budget(one_chip):
    """The widest cache the engine-path rule sends to the kernel compiles:
    the rule's VMEM budget stays inside what Mosaic grants."""
    length = 256
    widest = max(n for n in range(128, 1 << 16, 128)
                 if kernel_fits(length, n))
    text = _cache_scan_text(one_chip, length, widest, prefetch=True,
                            policy_idx=-1)
    assert "tpu_custom_call" in text


def test_reuse_distance_kernel_compiles_at_phase_c_length(one_chip):
    text = _compiled_text(
        reuse_distance_kernel,
        _shape(one_chip, (PHASE_C_SHARDS, PHASE_C_LEN), jnp.int32),
        _shape(one_chip, (PHASE_C_SHARDS, PHASE_C_LEN), jnp.bool_))
    assert "tpu_custom_call" in text


def test_sweep_engine_lowers_to_the_kernel_for_tpu(one_chip):
    """The megabatch sweep engine (vmapped rows, platform dispatch) takes
    the Pallas branch when it is compiled for a TPU."""
    store = StoreConfig(n_lines=PHASE_B_LINES).static_config()
    eng = _batched_engine(store, 1, (one_chip.device_set.pop(),), N_WINDOWS,
                          engine="pallas", donate=False)
    n, s = 4, 4
    hyper = StoreHyper(*(_shape(one_chip, (n,), dt) for dt in
                         (jnp.float32, jnp.float32, jnp.float32, jnp.int32)))
    text = eng.lower(
        hyper, _shape(one_chip, (n, s, PHASE_B_LEN), jnp.int32),
        _shape(one_chip, (n, s, PHASE_B_LEN), jnp.bool_),
        _shape(one_chip, (n, s, PHASE_B_LEN), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text
