"""Ahead-of-time compiles of the Pallas tile kernels for a described TPU.

Each kernel is lowered with ``interpret=False`` and compiled by the TPU
compiler for one chip of a described ``v5e:2x2`` topology: no chip is
attached, nothing runs, but block shapes the compiler refuses (tiling
alignment, VMEM) fail here.  The compiled text must hold the Mosaic
kernel (``tpu_custom_call``), so a kernel that silently became plain XLA
fails too.  The topology is described inside a fixture: only the worker
that runs these tests loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.frontier_tile import frontier_tiles
from repro.kernels.spmv_tile import spmv_tiles
from repro.kernels.tc_tile import tc_tiles

NUM_TILES = 64
BLOCK_T = 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep it out of the cache."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _args(kernel, t, sharding):
    tiles = jax.ShapeDtypeStruct((NUM_TILES, t, t), jnp.float32,
                                 sharding=sharding)
    vec = jax.ShapeDtypeStruct((NUM_TILES, t), jnp.float32, sharding=sharding)
    if kernel is tc_tiles:
        return (tiles, tiles, tiles)
    return (tiles, vec)


@pytest.mark.parametrize("tile_dim", [512, 256])
@pytest.mark.parametrize("kernel", [spmv_tiles, frontier_tiles, tc_tiles],
                         ids=["spmv_tiles", "frontier_tiles", "tc_tiles"])
def test_kernel_compiles_for_v5e(kernel, tile_dim, one_chip,
                                 no_persistent_cache):
    fn = jax.jit(lambda *a: kernel(*a, block_t=BLOCK_T, interpret=False))
    compiled = fn.lower(*_args(kernel, tile_dim, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
