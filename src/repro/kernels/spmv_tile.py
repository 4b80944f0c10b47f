"""Pallas TPU kernel: batched dense-block SpMV (PageRank's K_D hot spot).

y[b] = A[b]ᵀ · x[b] over the packed bitmap tiles: each grid step loads a
(T, bt) column panel of one tile plus the (T,) rank slice and produces a
(bt,) partial output — ``x · A_panel`` is a (1, T) × (T, bt) MXU matmul.
VMEM working set per step: T·bt + T floats (bt=128, T≤1024 → ≤0.6 MiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ref, x_ref, y_ref):
    a = a_ref[...].astype(jnp.float32)      # (T, bt) column panel
    x = x_ref[...].astype(jnp.float32)      # (1, T)
    # HIGHEST: the MXU would otherwise round the f32 ranks to bf16
    y_ref[...] = jax.lax.dot_general(
        x, a, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def spmv_tiles(tiles, xs, *, block_t: int = 128, interpret: bool = True):
    """(nd, T, T) tiles × (nd, T) slices → (nd, T): per-tile Aᵀx."""
    nb, t, _ = tiles.shape
    bt = min(block_t, t)
    assert t % bt == 0
    ys = pl.pallas_call(
        _kernel,
        grid=(nb, t // bt),
        in_specs=[
            pl.BlockSpec((None, t, bt), lambda b, c: (b, 0, c)),
            pl.BlockSpec((None, 1, t), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, bt), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, t), jnp.float32),
        interpret=interpret,
    )(tiles, xs.reshape(nb, 1, t))
    return ys.reshape(nb, t)
