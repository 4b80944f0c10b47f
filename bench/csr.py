"""Edge lists to the CSR of a simple undirected graph, in bulk numpy.

Shared by the generators: GAP's graph construction symmetrises every
graph and squeezes out self-loops and duplicate edges, so each
generator hands its raw edge pairs here.  Vertex ids fit 32 bits and a
pair is packed into one uint64 key, so the dedup is one sort.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

#: the edge list is drawn in this many independently seeded chunks, so
#: a graph depends on the seed alone, never on how many threads drew it
CHUNKS = 8


def chunk_rngs(seed: int) -> list[np.random.Generator]:
    """One generator per chunk of the edge list drawn from ``seed``."""
    ss = np.random.SeedSequence(int(seed) % 2**64)
    return [np.random.default_rng(s) for s in ss.spawn(CHUNKS)]


def relabelling(seed: int, scale: int) -> np.ndarray:
    """A random permutation of the ``2**scale`` vertex ids, from ``seed``
    (any whole number; large and negative ones wrap to 64 bits)."""
    rng = np.random.default_rng(int(seed) % 2**64)
    return rng.permutation(1 << scale).astype(np.uint32)


def chunk_bounds(m: int) -> list[tuple[int, int]]:
    cuts = np.linspace(0, m, CHUNKS + 1).astype(np.int64)
    return list(zip(cuts[:-1].tolist(), cuts[1:].tolist()))


def parallel_chunks(fn, m: int, rngs) -> None:
    """Run ``fn(lo, hi, rng)`` over the chunks of ``range(m)`` on a few
    threads; numpy drops the interpreter lock inside bulk fills."""
    with ThreadPoolExecutor(max_workers=CHUNKS) as pool:
        futs = [pool.submit(fn, lo, hi, rng)
                for (lo, hi), rng in zip(chunk_bounds(m), rngs)]
        for f in futs:
            f.result()


def symmetric_csr(src: np.ndarray, dst: np.ndarray,
                  scale: int) -> tuple[np.ndarray, np.ndarray]:
    """``(indptr int64 (n+1,), indices int32 (m,))`` of the undirected
    graph on ``2**scale`` vertices with an edge for every pair, minus
    self-loops and duplicates; each row's neighbours sorted."""
    n = 1 << scale
    keep = src != dst
    s = src[keep].astype(np.uint64)
    d = dst[keep].astype(np.uint64)
    shift = np.uint64(scale)
    key = np.concatenate([(s << shift) | d, (d << shift) | s])
    del s, d, keep
    key.sort()
    first = np.empty(key.size, dtype=bool)
    first[:1] = True
    np.not_equal(key[1:], key[:-1], out=first[1:])
    key = key[first]
    del first
    starts = np.arange(n + 1, dtype=np.uint64) << shift
    indptr = np.searchsorted(key, starts).astype(np.int64)
    indices = (key & np.uint64(n - 1)).astype(np.int32)
    return indptr, indices
