"""Pallas TPU kernel: bottom-up BFS frontier probe (BFS's K_D hot spot).

For each row u of a packed bitmap tile, find the smallest local column c
such that (u, c) is an edge AND c is in the frontier — the GPU bottom-up
step of the paper's Listing 3 ("if one of its neighbors appears in the
frontier, insert and stop") as a masked VPU row-reduction.  The "stop at
the first neighbor" early exit becomes a min-reduction, which is the
deterministic TPU equivalent.

Grid (nd, T/bt): each step loads a (bt, T) row panel and the (T,)
frontier mask; working set bt·T + T floats (≤0.6 MiB at T=1024).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

_INT_MAX = np.int32(2**31 - 1)  # numpy scalar: not a captured jax constant


def _kernel(a_ref, f_ref, out_ref):
    # compare in f32: Mosaic cannot broadcast a packed (bf16) mask
    a = a_ref[...].astype(jnp.float32)       # (bt, T) tile row panel
    f = f_ref[...].astype(jnp.float32)       # (1, T) frontier mask
    bt, t = a.shape
    colid = jax.lax.broadcasted_iota(jnp.int32, (bt, t), 1)
    hit = (a > 0) & (f > 0)
    out_ref[...] = jnp.where(hit, colid, _INT_MAX).min(axis=1).reshape(1, bt)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def frontier_tiles(tiles, fcols, *, block_t: int = 128, interpret: bool = True):
    """(nd,T,T) tiles × (nd,T) frontier → (nd,T) i32 min frontier column."""
    nb, t, _ = tiles.shape
    if block_t <= 0:
        raise ValueError(f"block_t must be a positive int; got {block_t!r}")
    # the row-panel height must divide T exactly or the BlockSpec grid
    # misses rows; shrink to the largest divisor of T ≤ block_t so
    # non-power-of-two tile dims (192, 96, ...) run correctly instead
    # of tripping a bare assert (which vanishes under ``python -O``)
    bt = max(min(block_t, t), 1)
    while t % bt:
        bt -= 1
    out = pl.pallas_call(
        _kernel,
        grid=(nb, t // bt),
        in_specs=[
            pl.BlockSpec((None, bt, t), lambda b, r: (b, r, 0)),
            pl.BlockSpec((None, 1, t), lambda b, r: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, bt), lambda b, r: (b, 0, r)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, t), jnp.int32),
        interpret=interpret,
    )(tiles, fcols.astype(tiles.dtype).reshape(nb, 1, t))
    return out.reshape(nb, t)
