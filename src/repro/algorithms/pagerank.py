"""PageRank (paper §5.2.1) — single-block bulk-synchronous mode.

SpMV-style pull PageRank over the 2-D block layout.  Paper parameters:
damping 0.85, tolerance 1e-4, iteration limit 20.

* sparse path (K_H): masked segmented-COO scatter-add — every edge
  (u→v) deposits ``rank[u]/deg[u]`` into ``acc[v]``.  The paper notes
  atomics are the bottleneck here; XLA's deterministic segment-sum
  lowering plays the role of the atomic adds.
* dense path (K_D): packed bitmap tiles contract against the gathered
  rank slice on the MXU — ``acc[c0:c0+T] += A_bᵀ @ x[r0:r0+T]`` batched
  over tiles (optionally the Pallas ``spmv_tile`` kernel).
* post: damping + dangling mass + L1 delta, acc reset (runs once after
  both paths — the bulk-synchronous combine).

Personalization (``seeds=``): the restart vector ``r`` replaces the
uniform ``1/n`` teleport — mass ``1/len(seeds)`` at each seed, and
dangling mass is likewise redistributed over the seeds.  ``seeds=None``
keeps the exact uniform formula (bit-identical to the unseeded code
path).  The restart vector lives in the *state* pytree, so one compiled
step serves every seed set.

Batch axis: when the state carries a leading query axis
(``rank.ndim == 2``, built with :func:`repro.core.engine.batch_states`),
kernels and post vmap the single-query functions over axis 0 against the
one shared graph context.  Converged queries freeze — their rows stop
updating once ``delta <= tol`` — so each row of a batched run finishes
with exactly the state its solo run would have produced.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.functors import BlockAlgorithm, Mode
from ..core.runs import gather_src
from ..kernels import get_kernel

__all__ = ["pagerank_algorithm", "pagerank"]


def _prepare(store, sched):
    return dict(
        inv_deg=jnp.asarray(1.0 / np.maximum(store.degrees, 1).astype(np.float32)),
        dangling=jnp.asarray(store.degrees == 0),
    )


def _restart_vector(n: int, seeds) -> np.ndarray:
    s = np.atleast_1d(np.asarray(seeds, dtype=np.int64)).ravel()
    if s.size == 0:
        raise ValueError("seeds must name at least one vertex")
    if (s < 0).any() or (s >= n).any():
        raise ValueError(f"seeds out of range for a graph with {n} vertices")
    r = np.zeros(n, np.float32)
    np.add.at(r, s, np.float32(1.0 / s.size))
    return r


def _init_factory(seeds):
    def _init(store):
        n = store.n
        base = dict(
            acc=jnp.zeros((n,), jnp.float32),
            delta=jnp.asarray(jnp.inf, jnp.float32),
        )
        if seeds is None:
            return dict(base, rank=jnp.full((n,), 1.0 / n, jnp.float32))
        r = jnp.asarray(_restart_vector(n, seeds))
        return dict(base, rank=r, restart=r)

    return _init


def _scatter_sparse(ctx, rank, acc):
    with jax.named_scope("gather"):
        contrib = rank * ctx.extras["inv_deg"]
        vals = jnp.where(ctx.sparse_edge_mask, gather_src(ctx, contrib), 0.0)
    with jax.named_scope("scatter"):
        return acc.at[ctx.dst].add(vals)


def _kernel_sparse(ctx, state, it):
    if state["rank"].ndim == 2:
        acc = jax.vmap(lambda r, a: _scatter_sparse(ctx, r, a))(
            state["rank"], state["acc"])
    else:
        acc = _scatter_sparse(ctx, state["rank"], state["acc"])
    return dict(state, acc=acc)


def _scatter_dense(ctx, rank, acc):
    tiles = ctx.tiles                         # (nd, T, T) 0/1 float32
    t = ctx.tile_dim
    contrib = rank * ctx.extras["inv_deg"]
    pad = jnp.zeros((t,), contrib.dtype)
    xpad = jnp.concatenate([contrib, pad])
    xs = jax.vmap(
        lambda r0: jax.lax.dynamic_slice(xpad, (r0,), (t,))
    )(ctx.tile_row_start)                     # (nd, T)
    ys = get_kernel("spmv_tiles", ctx.backend)(tiles, xs)   # (nd, T)
    idx = ctx.tile_col_start[:, None] + jnp.arange(t)[None, :]
    acc_pad = jnp.concatenate([acc, pad]).at[idx].add(ys)
    return acc_pad[: acc.shape[0]]


def _kernel_dense(ctx, state, it):
    if state["rank"].ndim == 2:
        acc = jax.vmap(lambda r, a: _scatter_dense(ctx, r, a))(
            state["rank"], state["acc"])
    else:
        acc = _scatter_dense(ctx, state["rank"], state["acc"])
    return dict(state, acc=acc)


def _post(ctx, state, it, damping=0.85):
    n = state["rank"].shape[0]
    dangling_mass = jnp.sum(jnp.where(ctx.extras["dangling"], state["rank"], 0.0))
    new_rank = (1.0 - damping) / n + damping * (state["acc"] + dangling_mass / n)
    delta = jnp.sum(jnp.abs(new_rank - state["rank"]))
    return dict(rank=new_rank, acc=jnp.zeros_like(state["acc"]), delta=delta)


def _post_seeded(ctx, state, it, damping=0.85):
    # teleport (and dangling) mass goes to the restart distribution
    # instead of 1/n — matches networkx's personalization + dangling
    r = state["restart"]
    dangling_mass = jnp.sum(jnp.where(ctx.extras["dangling"], state["rank"], 0.0))
    new_rank = (1.0 - damping) * r + damping * (state["acc"] + dangling_mass * r)
    delta = jnp.sum(jnp.abs(new_rank - state["rank"]))
    return dict(rank=new_rank, acc=jnp.zeros_like(state["acc"]), delta=delta,
                restart=r)


def pagerank_algorithm(*, damping: float = 0.85, tol: float = 1e-4,
                       max_iters: int = 20, seeds=None) -> BlockAlgorithm:
    def post(ctx, state, it):
        single = _post_seeded if "restart" in state else _post
        if state["rank"].ndim == 2:
            new = jax.vmap(lambda s: single(ctx, s, it, damping))(state)
            # freeze converged rows: a query whose previous delta is
            # already <= tol keeps the state its solo run ended with
            active = state["delta"] > tol

            def keep(old, nw):
                a = active.reshape(active.shape + (1,) * (nw.ndim - 1))
                return jnp.where(a, nw, old)
            out = {k: keep(state[k], v) for k, v in new.items()}
            out["acc"] = new["acc"]          # zeros either way
            return out
        return single(ctx, state, it, damping)

    def after(host, state, it):
        return state, bool(np.any(np.asarray(
            jax.device_get(state["delta"])) > tol))

    return BlockAlgorithm(
        name="pagerank",
        mode=Mode.BULK,
        kernel_sparse=_kernel_sparse,
        kernel_dense=_kernel_dense,
        post=post,
        prepare=_prepare,
        init_state=_init_factory(seeds),
        after=after,
        max_iterations=max_iters,
        finalize=lambda store, state: np.asarray(state["rank"]),
        # mesh="shard": the rank scatter decomposes over any edge
        # partition judged from iteration-start rank; acc folds with
        # psum (exact for the iteration's summation structure up to
        # float order), everything else is post-written.
        # tol joins params because the batched post's freeze mask
        # traces against it — two tolerances must not share a step.
        # seeds stay OUT of params: personalization is state content
        # (restart leaf), so every seed set shares one compiled step.
        # src="gather": arc sources are read only through gather_src.
        metadata=dict(combine="add", params=dict(damping=damping, tol=tol),
                      workspace_kernel="spmv_tiles", csr="none",
                      src="gather", mesh="shard", batch="query"),
    )


def pagerank(store, **plan_kw) -> np.ndarray:
    """Convenience wrapper: compile + run PageRank on a BlockStore."""
    from ..core.engine import compile_plan

    alg = pagerank_algorithm(
        damping=plan_kw.pop("damping", 0.85),
        tol=plan_kw.pop("tol", 1e-4),
        max_iters=plan_kw.pop("max_iters", 20),
        seeds=plan_kw.pop("seeds", None),
    )
    return compile_plan(alg, store, **plan_kw).run().result
