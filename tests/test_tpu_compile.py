"""Compile the store's device path for a described TPU v5e, with no chip.

The TPU compiler is installed wherever jax is, and it compiles for a
topology that is described rather than attached.  That checks what interpret
mode cannot: Mosaic's tiling and lowering rules, VMEM limits, and whether the
programs fit the chip's memory.  Sizes are the largest buckets the chip
smoke test's store (1M records of 1 KB) reaches.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and every
test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

FILTER_WORDS = 1 << 20    # above the largest filter: 10 bits x 1M keys / 32
HASH_BATCH = 1 << 20      # the largest run's filter build
MERGE_ROWS = 1 << 13      # > 1M keys / 256 per tile -> 8192 rows
TILE = 256                # merge_runs_tiled's default


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_bloom_probe_compiles_for_v5e(one_chip):
    for n_keys in (ops.PROBE_MIN_KEYS, ops.PROBE_BATCH):   # key bucket ends
        q = _spec((n_keys,), jnp.uint32, one_chip)
        compiled = ops._probe_jit.lower(
            q, q, _spec((FILTER_WORDS,), jnp.uint32, one_chip),
            _spec((), jnp.uint32, one_chip),
            _spec((), jnp.int32, one_chip)).compile()
        assert compiled.memory_analysis().output_size_in_bytes == n_keys


def test_merge_kernel_compiles_for_v5e(one_chip):
    rows = _spec((MERGE_ROWS, 2 * TILE), jnp.int32, one_chip)
    compiled = ops._merge_jit.lower(rows, rows, rows,
                                    interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_bloom_hash_pass_compiles_for_v5e(one_chip):
    keys = _spec((HASH_BATCH,), jnp.uint32, one_chip)
    compiled = ops._hash_jit.lower(keys, keys).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= 8 * HASH_BATCH
