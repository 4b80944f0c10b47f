"""Ahead-of-time compile of the in-core PageRank step at Kronecker scale
22 shapes, for one chip of a described ``v5e:2x2`` topology.

The step that gathers through runs of equal sources (``repro.core.runs``)
must need no more arc-sized temporaries than the per-arc step, and its
one gather in the ``gather`` scope must read one element per run, not
one per arc.
No chip is attached and nothing runs.
"""
import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.algorithms import pagerank_algorithm
from repro.core.context import Context
from repro.core.engine import _CompiledStep

# the scale-22 Kronecker store of the benchmark (seed 0, p = 8): arcs,
# and its 10,204,475 runs of equal sources, padded (repro.core.runs)
N, M, K, P = 2**22, 128_305_738, 10_223_616, 8
_ARRAYS = ("src", "dst", "edge_block", "indptr", "indices", "degrees",
           "row_block_ptr", "cuts", "sparse_edge_mask", "dense_edge_mask",
           "run_pos", "run_src")
#: fixed-size tuple buffers (loop carry, multi-output fusions)
FIXED_BUFFERS = 4 * 16 * 1024


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled(one_chip):
    """The compiled step, per arc and through runs: (temporaries in
    bytes, the shapes of the gathers in the ``gather`` scope)."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return {runs: _compile(one_chip, runs) for runs in (False, True)}
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()


def _compile(sharding, runs: bool):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    edge = sds((M,), jnp.int32)
    base = Context(
        src=None if runs else edge, dst=edge, edge_block=edge,
        indptr=sds((N + 1,), jnp.int32), indices=edge,
        degrees=sds((N,), jnp.int32),
        row_block_ptr=sds((N, P + 1), jnp.int32),
        cuts=sds((P + 1,), jnp.int32),
        sparse_edge_mask=sds((M,), jnp.bool_),
        dense_edge_mask=sds((M,), jnp.bool_),
        run_pos=sds((K,), jnp.int32) if runs else None,
        run_src=sds((K,), jnp.int32) if runs else None,
        n=N, m=M, p=P, tile_dim=512, backend="xla")
    arrays = {f: getattr(base, f) for f in _ARRAYS}
    extras = dict(inv_deg=sds((N,), jnp.float32),
                  dangling=sds((N,), jnp.bool_))
    vec = sds((N,), jnp.float32)
    state = dict(acc=vec, delta=sds((), jnp.float32), rank=vec)
    step = _CompiledStep(pagerank_algorithm())
    fn = jax.jit(lambda a, e, s, it: step._jit(
        dataclasses.replace(base, extras=e, **a), s, it, False))
    exe = fn.lower(arrays, extras, state, sds((), jnp.int32)).compile()
    gathers = re.findall(
        r"= \w+\[(\d+)\]\S* gather\(.*op_name=\"[^\"]*/sparse/gather/",
        exe.as_text())
    return exe.memory_analysis().temp_size_in_bytes, [int(g) for g in gathers]


def test_run_gather_needs_no_more_temporaries(compiled):
    # the expansion works inside the buffers the scatter-add's sort
    # already holds; what it adds is a few tuple buffers of its loop and
    # gather, 16 KiB each when aligned, whatever the number of arcs
    assert compiled[True][0] <= compiled[False][0] + FIXED_BUFFERS


def test_run_gather_reads_one_element_per_run(compiled):
    assert compiled[False][1] == [M]
    assert compiled[True][1] == [K]
