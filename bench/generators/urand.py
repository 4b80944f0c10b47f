"""GAP "urand": a uniform random (Erdos-Renyi) graph.

As GAP's generator (``MakeUniformEL``) draws it: ``edge_factor *
2**scale`` edges with both endpoints uniform over the vertices, then
symmetrised with self-loops and duplicates removed.

As in GAP, the edges come from one fixed seed (``structure_seed``), so
every run ranks the same graph; the run's seed draws a permutation of
the vertex ids.
"""
from __future__ import annotations

import numpy as np

from bench.csr import (chunk_rngs, parallel_chunks, relabelling,
                       symmetric_csr)


def generate(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    scale = int(cfg["scale"])
    n = 1 << scale
    m = int(cfg["edge_factor"]) << scale
    src = np.empty(m, np.uint32)
    dst = np.empty(m, np.uint32)

    def fill(lo: int, hi: int, rng: np.random.Generator) -> None:
        src[lo:hi] = rng.integers(0, n, hi - lo, dtype=np.uint32)
        dst[lo:hi] = rng.integers(0, n, hi - lo, dtype=np.uint32)

    parallel_chunks(fill, m, chunk_rngs(cfg["structure_seed"]))
    perm = relabelling(seed, scale)
    return symmetric_csr(perm[src], perm[dst], scale)
