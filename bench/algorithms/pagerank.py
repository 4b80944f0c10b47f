"""PageRank as GAP's ``pr`` kernel runs it: uniform start, damping
0.85, tolerance 1e-4, dangling mass spread over all vertices, and a cap
on the iterations a trial runs.

The yardstick for this algorithm lives here: the plan factory handed
to the program, the plain float64 numpy reference, the work functions
(arcs per trial, least bytes per iteration), the comparison that
decides ``correct``, and the lower-precision control.  Nothing here
imports the program except :func:`make`, which builds the program's
algorithm object.
"""
from __future__ import annotations

import numpy as np


def make(traffic: dict):
    """The program's PageRank for this traffic mix."""
    from repro.algorithms import pagerank_algorithm

    return pagerank_algorithm(damping=traffic["damping"],
                              tol=traffic["tolerance"],
                              max_iters=traffic["max_iters"])


def reference(indptr: np.ndarray, indices: np.ndarray, traffic: dict,
              dtype=np.float64) -> np.ndarray:
    """``max_iters`` PageRank iterations of a symmetric CSR graph, pulled
    row by row; ``dtype`` float64 is the reference."""
    n = indptr.shape[0] - 1
    d = dtype(traffic["damping"])
    deg = np.diff(indptr)
    nz = deg > 0
    starts = indptr[:-1][nz]
    inv = (1.0 / np.maximum(deg, 1)).astype(dtype)
    tele = dtype(1.0 / n)
    rank = np.full(n, tele, dtype)
    for _ in range(int(traffic["max_iters"])):
        contrib = rank * inv
        acc = np.zeros(n, dtype)
        acc[nz] = np.add.reduceat(contrib[indices], starts)
        dangling = rank[~nz].sum(dtype=dtype)
        rank = (1 - d) * tele + d * (acc + dangling * tele)
    return rank


def control(indptr: np.ndarray, indices: np.ndarray, traffic: dict):
    """The reference in the program's place one precision down: ranks,
    contributions and the scatter-add in bfloat16, on the default JAX
    device.  Its readings are the upper ends of the limits."""
    import jax
    import jax.numpy as jnp

    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    src = jnp.asarray(np.repeat(np.arange(n, dtype=np.int32), deg))
    dst = jnp.asarray(indices)
    bf = jnp.bfloat16
    d = traffic["damping"]

    @jax.jit
    def run(src, dst, deg):
        inv = (1.0 / jnp.maximum(deg, 1)).astype(bf)
        dangling = deg == 0
        rank = jnp.full((n,), 1.0 / n, bf)
        for _ in range(int(traffic["max_iters"])):
            acc = jnp.zeros((n,), bf).at[dst].add((rank * inv)[src])
            mass = jnp.sum(jnp.where(dangling, rank, 0)).astype(bf)
            rank = ((1 - d) / n + d * (acc + mass / n)).astype(bf)
        return rank

    out = run(src, dst, jnp.asarray(deg.astype(np.int32)))
    return np.asarray(out.astype(jnp.float32), np.float64)


def arcs_per_trial(n: int, m: int, traffic: dict) -> int:
    """Arcs a trial must traverse: every arc once per iteration."""
    return m * int(traffic["max_iters"])


def least_bytes_per_iteration(n: int, m: int) -> int:
    """The least HBM traffic any implementation moves in one iteration:
    4 B of neighbour index and 4 B of gathered contribution per arc,
    and per vertex its rank read, its degree read and its rank
    written (4 B each)."""
    return 8 * m + 12 * n


def check(ranks: np.ndarray, iterations: int, want: np.ndarray,
          traffic: dict) -> dict:
    """The numbers compared for one timed trial: :func:`compare`, and
    how far its iteration count is from the cap the reference ran."""
    return dict(compare(ranks, want),
                iterations_off=abs(iterations - int(traffic["max_iters"])))


def compare(ranks: np.ndarray, want: np.ndarray) -> dict:
    """The numbers compared for one trial's rank vector: the L1 distance
    to the reference (ranks sum to 1, so it is relative) and the worst
    relative error of any vertex (every reference rank is at least the
    teleport share, so it never divides by 0)."""
    got = np.asarray(ranks, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        return dict(rank_l1=float("inf"), rank_max_rel=float("inf"))
    err = np.abs(got - want)
    return dict(rank_l1=float(err.sum()),
                rank_max_rel=float((err / want).max()))
