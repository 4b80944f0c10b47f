"""Direction-optimized BFS (paper §3.5, Listings 3–4) — activation mode.

The paper implements top-down in K_H (CPUs are better at it) and
bottom-up in K_D (GPUs are better at it), choosing per level.  The TPU
adaptation expresses that split through the framework's push/pull
direction capability (:mod:`repro.core.direction`):

* **push** (top-down): masked scatter over the segmented COO — every
  edge whose source is in the frontier offers itself as parent of an
  unvisited destination (min-scatter picks a deterministic parent).
  The dense-path twin runs the same scatter over the dense-routed
  edges.
* **pull** (bottom-up): for each unvisited vertex, find the smallest
  frontier neighbor.  The sparse twin is a reversed edge scatter; the
  dense twin reduces the packed bitmap tiles (optionally the Pallas
  ``frontier_tile`` kernel) — the paper's Listing 3 "if one of its
  neighbors appears in the frontier, insert and stop", as a masked
  row min-reduction (the deterministic TPU analog of the early exit).

``compile_plan(..., direction="auto")`` re-creates the paper's
per-level Beamer switch from the device-computed frontier count ``nf``
— the executor's hysteresis controller replaces the old host-side
``before`` hook and its per-state ``dir_dense`` flag, and the same
decision drives every wave, mesh shard, and host-lane unit of a level,
so pull levels stay bit-identical to push.  The default (no
``direction=``) is fixed push.  Activation is realized as masking (see
DESIGN §2): inactive edges/vertices are masked out rather than
compacted, which is the static-shape analog of composing block-lists
from blocks with non-empty queues.  Every level kernel, push or pull,
names its per-arc (or per-tile-row) flag reads and masks ``gather`` and
its min-scatter of parent offers ``scatter`` (``jax.named_scope``,
inside the executor's ``sparse``/``dense`` scope), as PageRank does.

Batch axis (``sources=[...]``): the state carries a leading query axis
on ``parent``/``frontier``/``dist`` (and the per-query count ``nf``),
and the level kernels vmap the single-source level function over axis 0
against the one shared graph context.  Each row runs exactly the
traversal its solo run would, so batched results are bit-identical to
single-source runs; the direction decision is per *iteration* (the
controller sums the batched ``nf`` against ``n`` per query).  The
single-source path is the unbatched code path, unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.functors import BlockAlgorithm, Mode
from ..kernels import get_kernel

__all__ = ["bfs_algorithm", "bfs"]

_UNVISITED = np.int32(2**31 - 1)  # parent sentinel


def _init_factory(source: int):
    def _init(store):
        n = store.n
        parent = jnp.full((n,), _UNVISITED, jnp.int32).at[source].set(source)
        frontier = jnp.zeros((n,), bool).at[source].set(True)
        dist = jnp.full((n,), _UNVISITED, jnp.int32).at[source].set(0)
        return dict(
            parent=parent,
            frontier=frontier,
            dist=dist,
            nf=jnp.asarray(1, jnp.int32),
        )

    return _init


def _init_multi_factory(sources):
    srcs = np.atleast_1d(np.asarray(sources, dtype=np.int64)).ravel()
    if srcs.size == 0:
        raise ValueError("sources must name at least one vertex")

    def _init(store):
        n = store.n
        if (srcs < 0).any() or (srcs >= n).any():
            raise ValueError(
                f"sources out of range for a graph with {n} vertices")
        b = srcs.size
        rows = np.arange(b)
        parent = np.full((b, n), _UNVISITED, np.int32)
        frontier = np.zeros((b, n), bool)
        dist = np.full((b, n), _UNVISITED, np.int32)
        parent[rows, srcs] = srcs.astype(np.int32)
        frontier[rows, srcs] = True
        dist[rows, srcs] = 0
        return dict(
            parent=jnp.asarray(parent),
            frontier=jnp.asarray(frontier),
            dist=jnp.asarray(dist),
            nf=jnp.ones((b,), jnp.int32),
        )

    return _init


def _top_down(ctx, state, edge_mask):
    src, dst = ctx.src, ctx.dst
    parent, frontier = state["parent"], state["frontier"]
    n = parent.shape[0]
    # visitation is judged on `dist`, which only `post` writes — so the
    # guard sees iteration-start state no matter how the level's edge
    # work is split (sparse→dense chaining in-core, waves streamed) and
    # the level's min-scatter is order-independent.
    with jax.named_scope("gather"):
        unvisited = state["dist"] == _UNVISITED
        do = edge_mask & frontier[src] & unvisited[dst]
        tgt = jnp.where(do, dst, n)
        cand = jnp.where(do, src, _UNVISITED)
    return _min_scatter(parent, tgt, cand)


def _bottom_up_edges(ctx, state, edge_mask):
    # reversed roles: unvisited src looks for any frontier dst neighbor.
    # On the symmetrized arc multiset this scatters the same
    # (target, candidate) pairs as _top_down, so the level's min-fold
    # is bit-identical — the pull contract.
    src, dst = ctx.src, ctx.dst
    parent, frontier = state["parent"], state["frontier"]
    n = parent.shape[0]
    with jax.named_scope("gather"):
        unvisited = state["dist"] == _UNVISITED  # see _top_down
        do = edge_mask & unvisited[src] & frontier[dst]
        tgt = jnp.where(do, src, n)
        cand = jnp.where(do, dst, _UNVISITED)
    return _min_scatter(parent, tgt, cand)


def _min_scatter(parent, tgt, cand):
    """The level's parent offers folded by minimum; target ``n`` (one
    past the last vertex) takes the masked-out offers."""
    n = parent.shape[0]
    with jax.named_scope("scatter"):
        ppad = jnp.concatenate([parent,
                                jnp.asarray([_UNVISITED], jnp.int32)])
        return ppad.at[tgt].min(cand)[:n]


# the state leaves a level function reads; batched kernels vmap over
# exactly these so untouched leaves pass through by identity (the
# streaming executor's per-wave fold relies on that to tell written
# leaves from carried ones)
_LEVEL_KEYS = ("parent", "frontier", "dist")


def _level_kernel(level_fn):
    """Lift a per-query level function into a (ctx, state, it) kernel
    that vmaps over the batch axis when one is present."""

    def kernel(ctx, state, it):
        sub = {k: state[k] for k in _LEVEL_KEYS}
        if state["parent"].ndim == 2:
            parent = jax.vmap(lambda s: level_fn(ctx, s))(sub)
        else:
            parent = level_fn(ctx, sub)
        return dict(state, parent=parent)

    return kernel


def _bottom_up_tiles(ctx, state):
    tiles = ctx.tiles                      # (nd, T, T)
    t = ctx.tile_dim
    parent = state["parent"]
    n = parent.shape[0]
    fpad = jnp.concatenate([state["frontier"], jnp.zeros((t,), bool)])
    fcols = jax.vmap(
        lambda c0: jax.lax.dynamic_slice(fpad, (c0,), (t,))
    )(ctx.tile_col_start)                  # (nd, T)
    # per tile row: smallest local frontier column, else INT_MAX
    cand_local = get_kernel("frontier_tiles", ctx.backend)(tiles, fcols)
    cand = jnp.where(
        cand_local == _UNVISITED,
        _UNVISITED,
        cand_local + ctx.tile_col_start[:, None].astype(jnp.int32),
    )
    rows = ctx.tile_row_start[:, None] + jnp.arange(t)[None, :]
    rows = jnp.minimum(rows, n)            # tile rows past n are padding
    with jax.named_scope("gather"):
        unvisited_pad = jnp.concatenate(
            [state["dist"] == _UNVISITED, jnp.asarray([False])]  # see _top_down
        )
        cand = jnp.where(unvisited_pad[rows], cand, _UNVISITED)
    return _min_scatter(parent, rows, cand)


_kernel_sparse = _level_kernel(
    lambda ctx, s: _top_down(ctx, s, ctx.sparse_edge_mask))
_kernel_dense = _level_kernel(
    lambda ctx, s: _top_down(ctx, s, ctx.dense_edge_mask))
_kernel_sparse_pull = _level_kernel(
    lambda ctx, s: _bottom_up_edges(ctx, s, ctx.sparse_edge_mask))
_kernel_dense_pull = _level_kernel(_bottom_up_tiles)


def _post(ctx, state, it):
    # new frontier = vertices visited this level (elementwise, so the
    # same code serves [n] and batched [b, n] states; the axis=-1 sum
    # yields a scalar nf or one per query respectively)
    newly = (state["dist"] == _UNVISITED) & (state["parent"] != _UNVISITED)
    dist = jnp.where(newly, it + 1, state["dist"])
    nf = jnp.sum(newly.astype(jnp.int32), axis=-1)
    return dict(state, frontier=newly, dist=dist, nf=nf)


def bfs_algorithm(source: int = 0, *, sources=None, max_iters: int = 10_000,
                  beta: int = 24) -> BlockAlgorithm:
    """Single-source BFS from ``source``, or — with ``sources=[...]`` —
    a batched multi-source BFS whose state carries a leading query axis
    (one independent traversal per source; see module docstring).

    ``beta`` is the Beamer cost ratio the direction controller applies
    under ``compile_plan(..., direction="auto")`` (pull once
    ``nf * beta > n``, hysteresis on the way back)."""
    def after(host, state, it):
        return state, bool(np.any(np.asarray(
            jax.device_get(state["nf"])) > 0))

    return BlockAlgorithm(
        name="bfs",
        mode=Mode.ACTIVATION,
        kernel_sparse=_kernel_sparse,
        kernel_dense=_kernel_dense,
        kernel_sparse_pull=_kernel_sparse_pull,
        kernel_dense_pull=_kernel_dense_pull,
        post=_post,
        init_state=(_init_factory(source) if sources is None
                    else _init_multi_factory(sources)),
        after=after,
        max_iterations=max_iters,
        finalize=lambda store, state: dict(
            parent=np.asarray(state["parent"]),
            dist=np.asarray(state["dist"]),
        ),
        # mesh="shard": the level's parent min-scatter is judged on
        # post-written `dist`, so any edge/tile partition over mesh
        # devices pmin-folds to the identical (deterministic) parents
        metadata=dict(combine=dict(parent="min", dist="min"),
                      workspace_kernel="frontier_tiles",
                      workspace_kernel_pull="frontier_tiles",
                      direction=dict(frontier="nf", beta=float(beta)),
                      csr="none", mesh="shard", batch="query"),
    )


def bfs(store, source: int = 0, *, sources=None, **plan_kw) -> dict:
    from ..core.engine import compile_plan

    alg = bfs_algorithm(source, sources=sources,
                        max_iters=plan_kw.pop("max_iters", 10_000),
                        beta=plan_kw.pop("beta", 24))
    return compile_plan(alg, store, **plan_kw).run().result
