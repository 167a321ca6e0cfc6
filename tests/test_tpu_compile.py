"""Compile rehearsal: the main path compiles for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology. Each test compiles one program at the widths the benchmark cells
run: the reuse-distance kernel (checked to be in the program,
``tpu_custom_call``), and the tier-1 engines the replay and sweep cells
run, which are XLA alone (checked to hold no custom call). What the
compiler refuses here — unaligned blocks, scalar reads it cannot prove,
more VMEM than a kernel may use — would otherwise surface only on the
chip. The topology is described in a fixture (never at import), and only
this file does so.
"""
import os
import warnings

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.reuse_distance import reuse_distance_kernel
from repro.sim.sweep import DEFAULT_UNROLL, _batched_engine
from repro.storage.tiered_store import (
    StoreConfig,
    StoreHyper,
    init_stream_carry,
    stream_chunk_engine,
)

# chip_smoke.py phase C: 16 shards, per-shard bucket.
PHASE_C_SHARDS, PHASE_C_LEN = 16, 1 << 20
# The replay cells: 16 shards, the chunk bucket (rung) their hottest shard
# takes, 32 windows. The knob sweep: 32 points of 4 shards, the bucket of
# its busiest shard.
REPLAY_SHARDS, REPLAY_RUNG, N_WINDOWS = 16, 1 << 16, 32
SWEEP_POINTS, SWEEP_SHARDS, SWEEP_LINES, SWEEP_LEN = 32, 4, 64, 4096


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


def _compiled_text(fn, *args, donate=()) -> str:
    with warnings.catch_warnings():
        # As in the engines: donated stream operands with no same-shape
        # output can only be freed early.
        warnings.filterwarnings("ignore",
                                message="Some donated buffers were not usable")
        return (jax.jit(fn, donate_argnums=donate).lower(*args).compile()
                .as_text())


def test_reuse_distance_kernel_compiles_at_phase_c_length(one_chip):
    text = _compiled_text(
        reuse_distance_kernel,
        _shape(one_chip, (PHASE_C_SHARDS, PHASE_C_LEN), jnp.int32),
        _shape(one_chip, (PHASE_C_SHARDS, PHASE_C_LEN), jnp.bool_))
    assert "tpu_custom_call" in text


def _hyper(sharding, shape=()):
    return StoreHyper(*(_shape(sharding, shape, dt) for dt in
                        (jnp.float32, jnp.float32, jnp.float32, jnp.int32)))


@pytest.mark.parametrize("n_lines,policy", [(2048, "lru"), (4096, "ws")],
                         ids=["wl2_replay", "wl1_ws_replay"])
def test_chunk_engine_compiles_at_replay_widths(one_chip, n_lines, policy):
    """The replay cells' chunk engine (donated carry and chunk buffers)
    compiles at their rung, on XLA alone."""
    cfg = StoreConfig(n_lines=n_lines, policy=policy)
    eng = stream_chunk_engine(cfg, n_windows=N_WINDOWS)
    carry = jax.tree.map(
        lambda x: _shape(one_chip, x.shape, x.dtype),
        jax.eval_shape(lambda: init_stream_carry(
            cfg, REPLAY_SHARDS, n_windows=N_WINDOWS)))
    rows = (REPLAY_SHARDS, REPLAY_RUNG)
    text = _compiled_text(
        eng, _hyper(one_chip), carry, _shape(one_chip, rows, jnp.int32),
        _shape(one_chip, rows, jnp.bool_), _shape(one_chip, rows, jnp.int32),
        donate=(1, 2, 3, 4))
    assert "tpu_custom_call" not in text


def test_sweep_engine_compiles_at_knob_sweep_widths(one_chip):
    """The knob sweep's megabatch engine compiles at its grid, on XLA
    alone."""
    store = StoreConfig(n_lines=SWEEP_LINES).static_config()
    eng = _batched_engine(store, DEFAULT_UNROLL,
                          (one_chip.device_set.pop(),), N_WINDOWS)
    rows = (SWEEP_POINTS, SWEEP_SHARDS, SWEEP_LEN)
    text = _compiled_text(
        eng, _hyper(one_chip, (SWEEP_POINTS,)),
        _shape(one_chip, rows, jnp.int32), _shape(one_chip, rows, jnp.bool_),
        _shape(one_chip, rows, jnp.int32), donate=(1, 2, 3))
    assert "tpu_custom_call" not in text
