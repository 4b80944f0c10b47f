"""Breadth-first search as GAP's ``bfs`` kernel runs it: Beamer's
direction-optimizing search from one root, run to the end of the root's
component.

The yardstick for this algorithm lives here: the plan factory handed to
the program, the plain numpy reference (level-synchronous, top-down),
GAP's own check of a search (exact depths and a *valid* parent tree,
not one particular tree), the nearest wrong answer, and the work
functions (arcs per trial, least bytes per trial).  Nothing here
imports the program except :func:`make`, which builds the program's
algorithm object.

A search's output is ``dist`` and ``parent``, one integer per vertex.
A vertex counts as reached where its ``dist`` lies in ``[0, n)``, and as
having a parent where its ``parent`` does.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

def make(traffic: dict):
    """The program's BFS for this traffic mix."""
    from repro.algorithms import bfs_algorithm

    return bfs_algorithm(int(traffic["source"]),
                         max_iters=int(traffic["max_iters"]),
                         beta=traffic["beta"])


class Search(NamedTuple):
    """The reference search: each vertex's depth (-1 where unreached),
    the levels it ran, the last (which finds nothing) included, and the
    CSR it ran on, which :func:`check` reads (the caller's arrays, not
    copies)."""
    depth: np.ndarray
    levels: int
    source: int
    indptr: np.ndarray
    indices: np.ndarray


def reference(indptr: np.ndarray, indices: np.ndarray,
              traffic: dict) -> Search:
    """Level-synchronous BFS of a symmetric CSR graph from ``source``,
    for at most ``max_iters`` levels."""
    return search(indptr, indices, int(traffic["source"]),
                  int(traffic["max_iters"]))


def search(indptr: np.ndarray, indices: np.ndarray, source: int,
           max_levels: int) -> Search:
    """The search from ``source`` that stops after ``max_levels`` levels
    or at the first level that finds nothing, whichever comes first."""
    n = indptr.shape[0] - 1
    depth = np.full(n, -1, np.int32)
    depth[source] = 0
    frontier = np.array([source], np.int64)
    levels = 0
    while frontier.size and levels < max_levels:
        levels += 1
        found = np.zeros(n, bool)
        found[neighbours(indptr, indices, frontier)] = True
        found &= depth < 0
        depth[found] = levels
        frontier = np.flatnonzero(found)
    return Search(depth, levels, source, indptr, indices)


def neighbours(indptr: np.ndarray, indices: np.ndarray,
               verts: np.ndarray) -> np.ndarray:
    """The neighbour lists of ``verts``, concatenated."""
    starts = indptr[verts]
    deg = indptr[verts + 1] - starts
    first = np.cumsum(deg) - deg
    return indices[np.repeat(starts - first, deg) + np.arange(deg.sum())]


def control(indptr: np.ndarray, indices: np.ndarray, traffic: dict) -> dict:
    """The nearest wrong answer: the reference stopped one level short,
    so that the deepest level's vertices stay unreached, with a valid
    parent (the least neighbour one level up) for every vertex it did
    reach.  Judged with the reference's level count, it must fail
    ``depth_mismatches``."""
    full = reference(indptr, indices, traffic)
    short = search(indptr, indices, full.source, full.levels - 2)
    return dict(dist=short.depth, parent=min_parents(short))


def min_parents(s: Search) -> np.ndarray:
    """Each reached vertex's least neighbour one level up (the root's
    parent is itself); -1 where a vertex is unreached."""
    n = s.depth.shape[0]
    deg = np.diff(s.indptr)
    rows = np.repeat(s.depth, deg)
    up = (rows > 0) & (s.depth[s.indices] == rows - 1)
    cand = np.where(up, s.indices, n)
    parent = np.full(n, n, np.int64)
    nz = deg > 0
    parent[nz] = np.minimum.reduceat(cand, s.indptr[:-1][nz])
    parent[parent == n] = -1
    parent[s.source] = s.source
    return parent


def arcs_per_trial(n: int, m: int, traffic: dict) -> int:
    """Arcs a search must traverse: GAP's count, every arc of the root's
    component, which on these graphs is every arc."""
    return m


def least_bytes_per_trial(n: int, m: int) -> int:
    """The least HBM traffic any implementation of one search moves:
    each arc's neighbour index read once (4 B), and per vertex its row
    offset read and its depth and parent written once (4 B each)."""
    return 4 * m + 12 * n


def check(out: dict, iterations: int, want: Search, traffic: dict) -> dict:
    """The numbers compared for one timed trial, GAP's verifier:

    * ``depth_mismatches``: vertices whose depth differs from the
      reference's, unreached vertices included;
    * ``bad_parents``: reached vertices other than the root whose parent
      is not a neighbour one level up, plus the root if its parent is
      not itself, plus unreached vertices that have a parent;
    * ``levels_off``: how far the trial's iteration count is from the
      levels the reference ran.
    """
    n = want.depth.shape[0]
    dist = np.asarray(out["dist"]).astype(np.int64)
    parent = np.asarray(out["parent"]).astype(np.int64)
    if dist.shape != (n,) or parent.shape != (n,):
        return dict(depth_mismatches=float("inf"), bad_parents=float("inf"),
                    levels_off=float("inf"))
    depth = np.where((dist >= 0) & (dist < n), dist, -1)
    return dict(depth_mismatches=int(np.count_nonzero(depth != want.depth)),
                bad_parents=bad_parents(parent, want),
                levels_off=abs(int(iterations) - want.levels))


def bad_parents(parent: np.ndarray, want: Search) -> int:
    """Vertices whose parent breaks the tree, by the reference's depths
    (see :func:`check`)."""
    n = want.depth.shape[0]
    has = (parent >= 0) & (parent < n)
    p = np.where(has, parent, 0).astype(np.int32)
    deg = np.diff(want.indptr)
    # v's parent is a neighbour where one of v's arcs points at it
    hit = want.indices == np.repeat(p, deg)
    is_nbr = np.zeros(n, bool)
    is_nbr[np.repeat(np.arange(n, dtype=np.int32), deg)[hit]] = True
    d = want.depth
    ok = has & is_nbr & (d[p] == d - 1)
    child = d > 0
    return int(np.count_nonzero(child & ~ok)
               + (parent[want.source] != want.source)
               + np.count_nonzero((d < 0) & has))
