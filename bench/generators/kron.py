"""GAP "kron": a Graph500 Kronecker (R-MAT) graph.

As GAP's generator (``MakeRMatEL``) draws it: ``edge_factor * 2**scale``
edges, each placed by one float32 uniform draw per level of the
recursion into the quadrants with probabilities a, b, c and 1-a-b-c,
then a random permutation of the vertex ids, then symmetrised with
self-loops and duplicates removed.

As in GAP, the edges come from one fixed seed (``structure_seed``), so
every run ranks the same graph; the run's seed draws the permutation
of the vertex ids.
"""
from __future__ import annotations

import numpy as np

from bench.csr import (chunk_rngs, parallel_chunks, relabelling,
                       symmetric_csr)


def generate(cfg: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    scale = int(cfg["scale"])
    a, b, c = (np.float32(cfg[k]) for k in ("a", "b", "c"))
    ab, abc = a + b, a + b + c
    m = int(cfg["edge_factor"]) << scale
    src = np.zeros(m, np.uint32)
    dst = np.zeros(m, np.uint32)

    def fill(lo: int, hi: int, rng: np.random.Generator) -> None:
        s, d = src[lo:hi], dst[lo:hi]
        for _ in range(scale):
            r = rng.random(hi - lo, dtype=np.float32)
            # quadrant of the draw: [0,a) top-left, [a,a+b) top-right,
            # [a+b,a+b+c) bottom-left, the rest bottom-right
            row = r >= ab
            col = (r >= a) ^ row ^ (r >= abc)
            s <<= 1
            d <<= 1
            s |= row
            d |= col

    parallel_chunks(fill, m, chunk_rngs(cfg["structure_seed"]))
    perm = relabelling(seed, scale)
    return symmetric_csr(perm[src], perm[dst], scale)
