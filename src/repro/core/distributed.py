"""Multi-device execution of block algorithms via ``shard_map``.

The paper runs tasks concurrently on CPU threads + GPU streams of one
node.  On a JAX mesh the analog is a ``blocks`` mesh axis: the scheduler
LPT-packs tasks onto devices, each device processes its own contiguous
(padded) edge partition, and global vertex attributes are combined with
collectives — ``psum`` for additive attributes (PageRank ranks, triangle
counts), ``pmin``/``pmax`` for hook/label attributes (SV, CC, BFS
parents).

The combine op is declared by the algorithm (``metadata['combine']``).
Attribute arrays are replicated; edge work is sharded.  This is the
"break the decentralized model, make blocks visible to everyone" option
the paper adopts for shared memory, generalized to a mesh: reads are
free (replicated), writes are reduced.

``make_device_edge_partition`` turns an LPT schedule into the padded
per-device COO (and, on request, conformal-CSR) slabs — it is shared by
:class:`DistributedEngine` (whole-graph, resident) and by the
mesh-cooperative streaming executor (:mod:`repro.core.stream`), which
calls it once per *wave* with a wave-local assignment and bucket-ladder
padding.  On this CPU container the same code runs with a 1-device mesh
in-process and with an 8-device host-platform mesh in the integration
tests (subprocess sets XLA_FLAGS).

The full distributed execution model — what is replicated, what is
sharded, which collective folds which attribute — is documented in
``docs/distributed.md``.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from .blocks import BlockStore
from .scheduler import Schedule

__all__ = ["make_device_edge_partition", "DistributedEngine", "combine_fn"]


def combine_fn(kind: str, axis: str) -> Callable:
    if kind == "add":
        return partial(jax.lax.psum, axis_name=axis)
    if kind == "min":
        return partial(jax.lax.pmin, axis_name=axis)
    if kind == "max":
        return partial(jax.lax.pmax, axis_name=axis)
    raise ValueError(f"unknown combine kind {kind!r}")


def make_device_edge_partition(
    store: BlockStore, sched: Schedule, *,
    assignment: np.ndarray | None = None,
    num_devices: int | None = None,
    bucket: bool = False,
    stage_csr: bool = False,
    alloc: Callable[..., np.ndarray] | None = None,
) -> dict[str, Any]:
    """Partition a schedule's tasks into padded per-device slabs.

    A device's edge set is the union of **every** block of each of its
    assigned tasks, deduplicated within the device (an earlier revision
    took only the first block of each block-list, silently dropping the
    other blocks of multi-block pattern-mode tasks).  Across devices a
    block may be staged more than once when two tasks of different
    devices share it — harmless for pattern-mode algorithms, whose
    kernels drive work from ``prepare`` items rather than the raw slab,
    and impossible for bulk/activation composition (one block per task).
    Padding uses src=dst=0 with valid=False.

    Parameters
    ----------
    assignment
        Per-task device ids; defaults to ``sched.device_assignment``
        (the global LPT packing).  The streaming executor passes a
        wave-local LPT assignment instead.
    num_devices
        Mesh size; defaults to ``sched.num_devices``.
    bucket
        Pad the slab width up the power-of-two bucket ladder
        (:func:`repro.core.membudget.bucket_size`) so all waves of one
        plan share a few slab shapes and the jitted mesh step does not
        retrace per wave.
    alloc
        ``alloc(shape, dtype) -> zeroed np.ndarray`` used for the big
        padded per-device slabs instead of ``np.zeros`` — the streaming
        executor passes its staging arena's pooled-buffer allocator so
        per-wave assembly recycles buffers instead of churning the host
        allocator.  Must return zero-filled memory (padding semantics).
    stage_csr
        Additionally build each device's conformal CSR row slices
        (:meth:`~repro.core.blocks.BlockStore.csr_slices` over the
        device's blocks): the returned dict gains ``indices`` (a padded
        ``[D, C]`` slab), ``csr_entries``/``csr_segments`` (per-device
        true lengths / coalesced gather counts) and ``csr_maps`` — the
        per-device rebased ``(row_block_ptr, indptr)`` pair an
        algorithm's ``prepare`` needs to address its device's slice.

    Returns ``dict(src, dst, edge_block, valid, blocks, edges, ...)``:
    ``[D, E]`` int32/bool slabs plus per-device block-id arrays and true
    edge counts.
    """
    from .membudget import bucket_size

    d = int(num_devices) if num_devices is not None else sched.num_devices
    assign = (
        np.asarray(assignment, dtype=np.int64)
        if assignment is not None else sched.device_assignment
    )
    if assign.shape[0] != sched.num_tasks:
        raise ValueError(
            f"assignment covers {assign.shape[0]} tasks, schedule has "
            f"{sched.num_tasks}"
        )
    blocks = [
        np.unique(sched.blocklists[assign == i]).astype(np.int64)
        if (assign == i).any() else np.zeros(0, np.int64)
        for i in range(d)
    ]
    idx = []
    seg_counts = []
    for bl in blocks:
        segs = store.edge_segments(bl)
        seg_counts.append(len(segs))
        idx.append(
            np.concatenate([np.arange(s, e, dtype=np.int64) for s, e in segs])
            if segs else np.zeros(0, np.int64)
        )
    emax = max((int(x.shape[0]) for x in idx), default=1) or 1
    eb = bucket_size(emax) if bucket else emax
    zeros = alloc if alloc is not None else np.zeros
    src = zeros((d, eb), dtype=np.int32)
    dst = zeros((d, eb), dtype=np.int32)
    edge_block = zeros((d, eb), dtype=np.int32)
    valid = zeros((d, eb), dtype=bool)
    for i, ix in enumerate(idx):
        k = ix.shape[0]
        src[i, :k] = store.src[ix]
        dst[i, :k] = store.dst[ix]
        edge_block[i, :k] = store.edge_block[ix]
        valid[i, :k] = True
    out: dict[str, Any] = dict(
        src=src, dst=dst, edge_block=edge_block, valid=valid,
        blocks=blocks, edges=[int(x.shape[0]) for x in idx],
        segments=seg_counts,
    )
    if stage_csr:
        slices = [store.csr_slices(bl) for bl in blocks]
        cmax = max((int(s[0].shape[0]) for s in slices), default=1) or 1
        cb = bucket_size(cmax) if bucket else cmax
        indices = zeros((d, cb), dtype=np.int32)
        for i, (sl, _, _, _) in enumerate(slices):
            indices[i, : sl.shape[0]] = sl
        out.update(
            indices=indices,
            csr_entries=[int(s[0].shape[0]) for s in slices],
            csr_segments=[len(s[3]) for s in slices],
            csr_maps=[(s[1], s[2]) for s in slices],
        )
    return out


class DistributedEngine:
    """Run a *bulk-synchronous* block algorithm over a device mesh.

    The algorithm provides ``edge_update(src, dst, valid, state) -> state``
    — the per-shard body (it sees only this device's edges) — and a
    ``combine`` kind for each state leaf (``metadata['combine']``:
    a single kind or a dict keyed by state field).
    """

    def __init__(
        self,
        store: BlockStore,
        sched: Schedule,
        edge_update: Callable,
        combine: str | dict[str, str] = "add",
        mesh: Mesh | None = None,
        axis: str = "blocks",
    ) -> None:
        if mesh is None:
            devs = np.array(jax.devices()[: sched.num_devices])
            mesh = Mesh(devs, (axis,))
        self.mesh = mesh
        self.axis = axis
        self.combine = combine
        self.edge_update = edge_update
        part = make_device_edge_partition(store, sched)
        shard = NamedSharding(mesh, P(axis, None))
        self.src = jax.device_put(part["src"], shard)
        self.dst = jax.device_put(part["dst"], shard)
        self.valid = jax.device_put(part["valid"], shard)

        def _step(src, dst, valid, state):
            # each shard sees (1, E_max) slabs — drop the leading axis
            new_state = self.edge_update(src[0], dst[0], valid[0], state)
            if isinstance(self.combine, str):
                new_state = jax.tree.map(
                    lambda orig, new: combine_fn(self.combine, axis)(new - orig) + orig
                    if self.combine == "add"
                    else combine_fn(self.combine, axis)(new),
                    state,
                    new_state,
                )
            else:
                out = {}
                for k, v in new_state.items():
                    kind = self.combine.get(k, "add")
                    if kind == "add":
                        out[k] = combine_fn("add", axis)(v - state[k]) + state[k]
                    else:
                        out[k] = combine_fn(kind, axis)(v)
                new_state = out
            return new_state

        self._step = jax.jit(
            shard_map(
                _step,
                mesh=mesh,
                in_specs=(P(axis, None), P(axis, None), P(axis, None), P()),
                out_specs=P(),
                check_vma=False,
            )
        )

    def step(self, state: Any) -> Any:
        return self._step(self.src, self.dst, self.valid, state)
