"""Typed execution contexts: the device-side ``Context`` pytree and the
host-side ``HostCtx``.

The paper's contract (§3) is that a user writes six functors and PGAbB
owns partitioning, scheduling, and dispatch.  The original engine leaked
that plumbing through a stringly-typed ``ctx`` dict that mixed device
arrays with host objects (``store``, ``schedule``) and needed a
recursive split/merge hack to cross the jit boundary.  This module
replaces the dict with two explicit objects:

* **``Context``** — everything a *kernel* may touch inside the jitted
  step.  Device arrays are pytree children; small scalars (``n``, ``m``,
  ``p``, ``tile_dim``) and the resolved ``backend`` name are static aux
  data, so they participate in jit's cache key exactly like shapes do.
  Per-algorithm ``prepare`` outputs live in ``extras``: an arbitrary
  pytree whose ``jax.Array``/ndarray leaves are traced and whose other
  leaves (ints used as shapes, flags, ...) stay static.  Container
  structure — including tuples — round-trips unchanged.
* **``HostCtx``** — everything the *host-side hooks* (``I_B``/``I_A``)
  may touch: the ``BlockStore``, the ``Schedule`` (a first-class,
  inspectable artifact), and the same static scalars.  It never crosses
  the jit boundary.

Two graphs with identical padded shapes produce ``Context`` objects with
identical treedefs, which is what lets a compiled :class:`~repro.core.engine.Plan`
be reused across graphs without retracing.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import TYPE_CHECKING, Any

import jax
import jax.numpy as jnp
import numpy as np

from .runs import expand_runs, source_runs

if TYPE_CHECKING:  # pragma: no cover — import cycle guard, typing only
    from .blocks import BlockStore
    from .scheduler import Schedule

__all__ = ["Context", "HostCtx", "build_context", "build_host_ctx",
           "with_extras", "with_arrays", "with_arc_sources"]


# Device-array fields, in flatten order.  ``tiles``/``tile_*`` are None
# when the schedule routed nothing to the dense path.
_ARRAY_FIELDS = (
    "src", "dst", "edge_block", "indptr", "indices", "degrees",
    "row_block_ptr", "cuts", "sparse_edge_mask", "dense_edge_mask",
    "tiles", "tile_row_start", "tile_col_start", "run_pos", "run_src",
)
_STATIC_FIELDS = ("n", "m", "p", "tile_dim", "backend")


class _DynMarker:
    """Aux-data placeholder for an ``extras`` leaf that is traced."""

    def __repr__(self) -> str:  # pragma: no cover
        return "<traced>"


_TRACED = _DynMarker()


def _is_traced_leaf(leaf: Any) -> bool:
    return isinstance(leaf, (jax.Array, np.ndarray))


@dataclass(eq=False)
class Context:
    """Device-side inputs of one compiled step (a registered pytree).

    Kernels receive this as their first argument and read it by
    attribute — ``ctx.src``, ``ctx.sparse_edge_mask``, ``ctx.tiles`` —
    plus whatever their algorithm's ``prepare`` stashed in
    ``ctx.extras``.  Host objects are *not* here by construction; see
    :class:`HostCtx`.
    """

    # --- segmented COO + CSR views of the store -----------------------
    src: Any                  # None where run_pos/run_src replace it
    dst: Any
    edge_block: Any
    indptr: Any
    indices: Any
    degrees: Any
    row_block_ptr: Any
    cuts: Any
    # --- static path routing masks ------------------------------------
    sparse_edge_mask: Any
    dense_edge_mask: Any
    # --- dense bitmap tiles (None when the dense path is empty) -------
    tiles: Any = None
    tile_row_start: Any = None
    tile_col_start: Any = None
    # --- runs of equal sources, in ``src``'s place (see core.runs) -----
    run_pos: Any = None
    run_src: Any = None
    # --- per-algorithm prepare outputs --------------------------------
    extras: dict[str, Any] = field(default_factory=dict)
    # --- static scalars (jit cache key, not traced) -------------------
    n: int = 0
    m: int = 0
    p: int = 1
    tile_dim: int = 0
    backend: str = "xla"


def _context_flatten(ctx: Context):
    fixed = tuple(getattr(ctx, f) for f in _ARRAY_FIELDS)
    leaves, treedef = jax.tree_util.tree_flatten(ctx.extras)
    traced = tuple(l for l in leaves if _is_traced_leaf(l))
    markers = tuple(
        _TRACED if _is_traced_leaf(l) else l for l in leaves
    )
    statics = tuple(getattr(ctx, f) for f in _STATIC_FIELDS)
    return fixed + (traced,), (treedef, markers, statics)


def _context_unflatten(aux, children):
    treedef, markers, statics = aux
    *fixed, traced = children
    it = iter(traced)
    leaves = [next(it) if mk is _TRACED else mk for mk in markers]
    extras = jax.tree_util.tree_unflatten(treedef, leaves)
    kw = dict(zip(_ARRAY_FIELDS, fixed))
    kw.update(zip(_STATIC_FIELDS, statics))
    return Context(extras=extras, **kw)


jax.tree_util.register_pytree_node(Context, _context_flatten, _context_unflatten)


@dataclass
class HostCtx:
    """Host-side view handed to ``before``/``after`` hooks (I_B/I_A).

    Hooks may inspect the store and the schedule (both host objects),
    read scalars, and keep private scratch in ``extras`` — but nothing
    here is ever traced.
    """

    store: "BlockStore"
    schedule: "Schedule"
    backend: str
    n: int
    m: int
    p: int
    tile_dim: int
    extras: dict[str, Any] = field(default_factory=dict)

    def __getitem__(self, key: str) -> Any:
        # Legacy convenience: old hooks indexed the ctx dict (ctx["n"]).
        if key in ("n", "m", "p", "tile_dim", "backend"):
            return getattr(self, key)
        if key in ("store", "schedule"):
            return getattr(self, key)
        return self.extras[key]


# ----------------------------------------------------------------------
def build_context(store: "BlockStore", schedule: "Schedule", *,
                  backend: str = "xla",
                  extras: dict[str, Any] | None = None,
                  src_runs: bool = False) -> Context:
    """Assemble the device-side :class:`Context` for one (store, schedule).

    Mirrors what the legacy ``Engine._build_context`` produced, minus the
    host objects: segmented-COO/CSR device views, the static edge→path
    routing masks derived from the schedule's dense selection, and the
    conformal cut vector.  With ``src_runs`` (the algorithm's
    ``metadata["src"] == "gather"``), the runs of equal sources
    (:func:`repro.core.runs.source_runs`) take ``src``'s place wherever
    they are no larger than it.
    """
    runs = source_runs(store.src) if src_runs else None
    arrays = store.device_arrays(with_src=runs is None)
    dense_blocks = np.zeros(store.layout.num_blocks, dtype=bool)
    if schedule.dense_block_ids.size:
        dense_blocks[schedule.dense_block_ids] = True
    edge_dense = dense_blocks[np.asarray(store.edge_block)]
    return Context(
        src=arrays.get("src"),
        dst=arrays["dst"],
        edge_block=arrays["edge_block"],
        indptr=arrays["indptr"],
        indices=arrays["indices"],
        degrees=arrays["degrees"],
        row_block_ptr=arrays["row_block_ptr"],
        cuts=jnp.asarray(store.layout.cuts),
        sparse_edge_mask=jnp.asarray(~edge_dense),
        dense_edge_mask=jnp.asarray(edge_dense),
        tiles=arrays.get("tiles"),
        tile_row_start=arrays.get("tile_row_start"),
        tile_col_start=arrays.get("tile_col_start"),
        run_pos=None if runs is None else jnp.asarray(runs[0]),
        run_src=None if runs is None else jnp.asarray(runs[1]),
        extras=dict(extras or {}),
        n=store.n,
        m=store.m,
        p=store.p,
        tile_dim=schedule.tile_dim,
        backend=backend,
    )


def build_host_ctx(store: "BlockStore", schedule: "Schedule", *,
                   backend: str = "xla") -> HostCtx:
    return HostCtx(
        store=store,
        schedule=schedule,
        backend=backend,
        n=store.n,
        m=store.m,
        p=store.p,
        tile_dim=schedule.tile_dim,
    )


def with_arrays(ctx: Context, **arrays: Any) -> Context:
    """Return a copy of ``ctx`` with the named device-array fields (and
    optionally ``extras``) swapped out.

    This is how the streaming executor turns the *resident* context
    (vertex-level arrays, full-graph scalars) into a per-wave context:
    the segmented-COO slab, routing masks, tile set, and wave extras are
    replaced while everything resident — ``indptr``, ``degrees``,
    ``row_block_ptr``, static scalars — is shared by reference, so two
    waves with equal slab shapes produce identical treedefs and hit the
    same compiled step.
    """
    unknown = set(arrays) - set(_ARRAY_FIELDS) - {"extras"}
    if unknown:
        raise TypeError(f"unknown Context array fields: {sorted(unknown)}")
    return replace(ctx, **arrays)


def with_arc_sources(ctx: Context) -> Context:
    """``ctx`` with ``src`` expanded from its runs where they took its
    place, so that a kernel that reads ``ctx.src`` still reads the
    sources.  Inside a compiled step the compiler drops the expansion
    where no kernel reads it; where one does, it costs a prefix sum over
    the arcs per step."""
    if ctx.src is not None or ctx.run_src is None:
        return ctx
    return replace(ctx, src=expand_runs(ctx.run_pos, ctx.run_src,
                                        ctx.dst.shape[0]))


def with_extras(ctx: Context, extras: dict[str, Any]) -> Context:
    """Return a copy of ``ctx`` with ``extras`` merged in (tuples and all
    other container structure preserved — this is the typed replacement
    for the old dict-merge path)."""
    merged = dict(ctx.extras)
    merged.update(extras)
    return replace(ctx, extras=merged)
