"""Pallas TPU kernel: dense-block triangle counting (the K_D hot spot).

Computes  Σ_b Σ_{r,s} (A_ik[b] · A_jk[b]ᵀ)[r,s] ∘ A_ij[b][r,s]  over a
batch of packed bitmap tiles.  This is the MXU adaptation of the paper's
GPU triangle-counting kernel (Listing 5): the list intersection for a
whole (bt × bt) patch of edges becomes one (bt, T) × (T, bt) matmul.

Tiling: grid (B, T/bt, T/bt); each step loads one row-panel of A_ik, one
row-panel of A_jk and the (bt, bt) mask patch of A_ij into VMEM — the
working set is 2·bt·T + bt² floats (bt=128, T≤1024 → ≤1.1 MiB), well
inside VMEM, and the contraction dims are multiples of 128 for the MXU.
The scalar partial sums accumulate in a (1, 1) VMEM block across the
sequential grid steps of a batch entry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(a_ik_ref, a_jk_ref, a_ij_ref, out_ref):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when((i == 0) & (j == 0))
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    a = a_ik_ref[...].astype(jnp.float32)  # (bt, T)
    b = a_jk_ref[...].astype(jnp.float32)  # (bt, T)
    m = a_ij_ref[...].astype(jnp.float32)  # (bt, bt)
    w = jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                      # (bt, bt) wedge counts on the MXU
    out_ref[...] += jnp.sum(w * m, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def tc_tiles(a_ik, a_jk, a_ij, *, block_t: int = 128, interpret: bool = True):
    """Batched masked-matmul triangle count: (B,T,T)×3 → scalar f32."""
    nb, t, _ = a_ik.shape
    bt = min(block_t, t)
    assert t % bt == 0, f"tile dim {t} not divisible by block {bt}"
    grid = (nb, t // bt, t // bt)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, bt, t), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, bt, t), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bt, bt), lambda b, i, j: (b, i, j)),
        ],
        out_specs=pl.BlockSpec((None, 1, 1), lambda b, i, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, 1), jnp.float32),
        interpret=interpret,
    )(a_ik, a_jk, a_ij)
    return jnp.sum(out)
