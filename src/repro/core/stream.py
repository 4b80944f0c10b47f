"""Out-of-core streaming executor: memory-budgeted, pipelined waves.

This subsystem makes any :class:`~repro.core.engine.Plan`-compatible
algorithm runnable under an explicit device-memory budget — the paper's
headline capability ("graphs that fit host DRAM but not device memory",
§4.3/§4.4, the block-list bound on device copies).  Five parts:

1. **Footprint model** (:mod:`repro.core.membudget`) prices each
   schedule task's COO slice, dense tiles, conformal CSR row slices
   (for ``metadata["csr"] == "slice"`` algorithms), and kernel
   workspace in bytes.  The schedule itself is built budget-aware
   (:func:`repro.core.scheduler.build_schedule` receives the budget):
   ``tile_dim`` shrinks until a staged tile fits and tasks whose dense
   working set cannot fit are routed to the sparse path up front.
2. **Wave builder** packs the LPT-ordered tasks into budget-sized
   *waves*; every wave's edge slab is padded to one of a few fixed
   bucket shapes (power-of-two ladder) so a single jitted step serves
   all waves without retracing.  Within a wave, tasks are sorted by
   leading block id so the segmented-COO gather coalesces into few
   contiguous segments — staging approaches a single slice copy.
3. **Three-stage host→device pipeline**: after a one-time *planning
   pass* (assemble every wave once: verify bytes against the budget,
   split overflows, hoist wave-invariant extras, cache each wave's
   ``prepare`` outputs), the per-iteration wave loop runs as

   * **stage 1 — background assembly** (:class:`_StagePipeline`, a
     worker thread behind a bounded queue of depth
     ``pipeline_depth``): wave ``k+2``'s numpy slab is gathered into
     pooled arena buffers while wave ``k`` computes.  ``prepare``
     outputs ride with the staged payload (cached from the planning
     pass — never a synchronous loop step);
   * **stage 2 — double-buffered ``device_put``**: wave ``k+1``'s slab
     crosses host→device while the device works on wave ``k`` (JAX
     async dispatch — the analog of the paper's CUDA copy streams);
   * **stage 3 — compute**: the jitted wave step, folding partials.

   The first executed iteration runs synchronously to calibrate
   per-phase times (assemble / device_put / compute); every later
   iteration overlaps, and ``schedule_stats`` reports the measured
   ``overlap_efficiency`` plus ``host_stage_overlap`` — the fraction
   of background host assembly hidden behind compute.
4. **Staging arena** (:class:`_HostArena`): because all slabs are
   padded to the power-of-two bucket ladder, the pipeline draws its
   host buffers from one pool per (shape, dtype) and recycles wave
   ``k``'s buffers into a later wave's assembly.  Recycling is
   *completion-gated* — ``jax.device_put`` may alias host memory on
   CPU, so a buffer re-enters the pool only once the step that read it
   reports ready (non-blocking ``is_ready`` probe; iteration end is
   the force-drain barrier).  When the device keeps up, steady-state
   staging memory approaches the model's ``(depth + 1)``-slab bound
   (:func:`repro.core.membudget.arena_model_bytes` through the
   registry's ``stage_arena`` estimator; the measured high water is
   reported as ``arena_bytes``) instead of one fresh allocation per
   wave per iteration.  On device the bucket ladder plays the same
   role: at most two staged slabs (current + prefetch) are in flight,
   each ≤ the budget, and freed buffers match the next wave's shapes
   exactly, so the device allocator reuses them instead of churning.
5. **Partial-result combination**: each wave's kernels run against the
   *iteration-start* state and its per-leaf updates are folded with the
   algorithm's declared ``metadata["combine"]`` op (``add``/``min``/
   ``max`` — the same semantics as
   :func:`repro.core.distributed.combine_fn`), so streamed results
   match the in-core bulk-synchronous step: exactly for integer/bool
   attributes, and up to float summation order for real ones.  Leaves a
   kernel passes through untouched are detected at trace time and
   carried over unchanged, so no combine kind is needed for them.
   ``post`` (and the host hooks) run once per iteration on the combined
   state, against a *resident* context that holds only vertex-level
   arrays.

Cross-wave trace stability
--------------------------
The jitted wave step retraces once per distinct (slab shapes, extras
structure) combination.  Slab shapes are already bucketed (point 2);
``prepare`` outputs are kept shape-stable by the algorithm's optional
``stage_plan`` hook (:class:`~repro.core.functors.BlockAlgorithm`):
it runs once per plan against the *full* store/schedule and its result
is passed to every per-wave (and per-device) ``prepare``, so
shape-driving decisions — TC's dp/steps bucket ladder — are made once
for the whole plan.  ``schedule_stats["streaming"]["trace_count"]``
reports the step's trace counter: with the hook it is one per distinct
bucket shape, independent of the number of waves (the TC retrace that
used to dominate high-wave-count runs).  All compiled-step flavours
share the process-wide cache in :mod:`repro.core.compilecache`.

Tail-wave rebalancing — ``rebalance_threshold``
-----------------------------------------------
Default **on** (``"auto"``): after the calibration pass, the observed
per-wave compute shares are compared against the schedule's estimate
shares (task weights); when the worst wave's observed/estimated share
diverges beyond a hysteresis band (fire ≥ 2.0×, re-arm < 1.5×) *and*
the measured times are above the noise floor (mean wave ≥ 10 ms — tiny
runs are deterministically left alone, keeping staged-byte accounting
reproducible), the remaining iterations' waves are re-packed LPT
against the observed per-task times
(:func:`repro.core.membudget.repack_waves`) — the paper's dynamic work
queue at wave granularity.  A float keeps the legacy behavior (fire
when the max/mean compute skew exceeds it); ``None`` is the explicit
off switch.  A fire disarms the trigger and the post-re-pack
recalibration only re-arms it below the low watermark — so the
automatic path re-packs at most once per plan and a still-diverged but
freshly packed queue never thrashes.  Results are unchanged by
construction (per-wave folding is partition-invariant) and every
re-packed wave is re-verified against the byte budget.

CSR streaming — ``metadata["csr"]``
-----------------------------------
What happens to the CSR adjacency (``ctx.indices``) is declared by the
algorithm:

``"slice"``
    Each wave stages only the conformal CSR row ranges its tasks touch
    (:meth:`repro.core.blocks.BlockStore.csr_slices`): ``ctx.indices``
    holds the sliced adjacency, and the *wave store* handed to
    ``prepare`` carries the rebased ``row_block_ptr``/``indptr`` so
    host-computed positions (e.g. TC's bucket items) index the slice.
    Slice lengths are rebase-invariant; global vertex attributes remain
    on ``wstore.graph``.  Kernels must size by ``ctx.indices.shape[0]``,
    never ``ctx.m``.
``"none"``
    The kernels never read the adjacency (pure COO scatter/gather
    algorithms); ``ctx.indices`` is a minimal placeholder and nothing
    edge-proportional is staged or resident.
``"resident"`` (default for custom algorithms)
    The full ``indices`` stays device-resident, as before this
    distinction existed — safe for kernels that index it with global
    positions, but the device footprint is then *not* bounded by the
    budget (``resident_bytes`` reports it honestly).

Algorithms declaring ``edge_free_iterations`` (Afforest's neighbor
sampling) additionally get a *prefix CSR* (:func:`repro.core.graph.csr_prefix`)
— the first ``k`` neighbors of every row, ``n·k`` entries — swapped in
as ``ctx.indptr``/``ctx.indices`` during those iterations, so even
adjacency-sampling rounds stay vertex-proportional on device.

The device working set is: resident vertex-level arrays (state pytree,
``indptr``/``degrees``/``row_block_ptr``/``cuts``) plus at most two
staged wave slabs (current + prefetch), each ≤ the budget — with
``"slice"``/``"none"`` algorithms, *every* edge-proportional device
allocation is bounded by ``memory_budget``.

Mesh-cooperative streaming — ``mesh=``
--------------------------------------
``compile_plan(alg, store, memory_budget=..., mesh=mesh)`` composes the
waves with :mod:`repro.core.distributed`'s execution model: the budget
becomes *per device*, waves are packed to the mesh capacity
``D × budget`` (:func:`repro.core.membudget.build_waves`), and each
wave's tasks are LPT-split over the mesh so every device stages only
its own padded COO/CSR/tile slab
(:func:`repro.core.distributed.make_device_edge_partition`, bucket
ladder — and staging arena — shared with the single-device path).  The
same three-stage pipeline stages the *sharded* slabs: the background
worker assembles wave ``k+2``'s per-device slabs into arena buffers,
wave ``k+1``'s slabs ``device_put`` with the block-axis sharding while
the mesh computes wave ``k`` under ``shard_map``; inside the shard each
device runs the kernels on its slice from iteration-start state,
per-leaf updates are combined across the mesh with the algorithm's
declared ``metadata["combine"]`` collective (``psum``/``pmin``/``pmax``
— :func:`repro.core.distributed.combine_fn`) and folded into the
running accumulator, so results stay bit-identical to in-core for
integer/bool attributes and equal up to float summation order
otherwise.  Vertex attributes, the resident context, and the state are
replicated; only edge work is sharded — the paper's "reads are free,
writes are reduced" model at wave granularity.  Algorithms opt in with
``metadata["mesh"] == "shard"``; ``prepare`` runs per device against a
device-local store view (device-rebased CSR, device tile subset), and
structurally device-varying outputs are unified by the algorithm's
``mesh_pack`` hook (see :class:`~repro.core.functors.BlockAlgorithm`).
``schedule_stats["streaming"]`` grows ``mesh_devices``,
``per_device_bytes`` (each entry ≤ the per-device budget),
``collective_bytes``, and the mesh-wide ``overlap_efficiency``.  The
full model is documented in ``docs/distributed.md``.

Heterogeneous co-scheduling — ``host_fraction``
-----------------------------------------------
The host CPU is a compute resource, not just a staging engine: each
wave splits into a *device partition* (the streamed pipeline above)
and a *host partition* — the smallest/sparsest tasks peeled off by
:func:`repro.core.membudget.peel_host_tasks` into host execution
units that run the algorithm's sparse kernel eagerly on the CPU jax
backend (:class:`_HostLane`, a ``concurrent.futures`` thread pool)
against host-side store views.  Host tasks are never ``device_put``,
so they do not touch the memory budget; their partials fold into the
per-iteration state through the same ``metadata["combine"]`` contract
as device waves and mesh shards, keeping results bit-identical to a
device-only run for integer/boolean attributes.  ``host_fraction``
is ``"auto"`` by default — zero split until the calibration pass
measures per-wave times above a noise floor, then a hide-criterion
split with probe-based host-rate measurement and hysteresis
(:func:`repro.core.membudget.hetero_split_diverged`) — or a fixed
float in [0, 1]; ``None`` disables the lane.  ``schedule_stats``
gains a ``"hetero"`` block (split ratio, host/device task counts,
per-resource makespans) and the ``host-compute`` tracer lane carries
the per-unit spans.  Full model in ``docs/heterogeneous.md``.

Entry point: ``compile_plan(alg, store, memory_budget=...)`` returns a
:class:`StreamingPlan` instead of a :class:`~repro.core.engine.Plan`.
"""
from __future__ import annotations

import concurrent.futures
import os
import queue
import threading
import time
from dataclasses import dataclass, replace as dc_replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from .. import obs
from .blocks import BlockStore
from .compilecache import alg_cache_key, shared_entry
from .context import _TRACED, Context, build_host_ctx, with_arrays
from .direction import (
    DirectionController, kernels_for, resolve_direction, workspace_kernels,
)
from .distributed import combine_fn, make_device_edge_partition
from .faults import FaultPlan, InjectedFault
from .functors import BlockAlgorithm
from .graph import csr_prefix
from .knobs import env_float as _knob_float, env_str as _knob_str
from .membudget import (
    HOST_RATIO_DEFAULT, MemoryBudget, PIPELINE_DEPTH, Wave,
    arena_model_bytes, bucket_size, build_waves, hetero_split_diverged,
    peel_host_tasks, repack_waves, resident_bytes, split_wave,
    task_footprints, tree_array_bytes,
)
from .resilience import (
    HostTaskError, ResilienceStats, RetryPolicy, WorkerDeath, classify,
)
from .scheduler import Schedule, build_schedule
from .engine import RunResult

__all__ = ["StreamingPlan", "compile_streaming_plan", "PHASES"]

#: Per-wave pipeline phases, in execution order — also the
#: ``stream.phase_seconds.<phase>`` metric-name suffixes.
PHASES = ("assemble", "prepare", "device_put", "compute", "collective",
          "host_compute")

_COMBINE_KINDS = ("add", "min", "max")
_CSR_MODES = ("resident", "slice", "none")

# Auto-rebalancing (default): fire when the *observed* wave-compute
# skew (max/mean) exceeds the skew the schedule's estimates predicted
# by _REBALANCE_HI; re-arm below _REBALANCE_LO (the hysteresis band
# keeps a borderline queue from flapping).  Comparing skews — not raw
# shares — makes the trigger insensitive to the constant per-wave
# dispatch overhead, and means "the estimate already predicted this
# imbalance" correctly stands down (LPT packed it as well as the bytes
# allow).  Below the noise floor the timings are dominated by dispatch
# jitter, so the trigger deterministically stands down — small runs
# keep reproducible staged-byte accounting.
_REBALANCE_HI = 2.0
_REBALANCE_LO = 1.5
_REBALANCE_NOISE_FLOOR_S = 10e-3


def _hetero_noise_floor_s() -> float:
    """Below this mean device-wave time the ``"auto"`` host split stays
    at zero: dispatch jitter dominates, so peeling would be decided by
    noise.  ``REPRO_HETERO_NOISE_FLOOR_S`` overrides (the hetero smoke
    lowers it to exercise the split on small CI graphs)."""
    return _knob_float("REPRO_HETERO_NOISE_FLOOR_S",
                       _REBALANCE_NOISE_FLOOR_S)


def _hetero_host_ratio_default() -> float:
    """Assumed host-vs-device slowdown before the host lane has been
    measured; ``REPRO_HETERO_HOST_RATIO`` overrides."""
    return _knob_float("REPRO_HETERO_HOST_RATIO", HOST_RATIO_DEFAULT)


def _combine_spec(alg: BlockAlgorithm):
    """metadata['combine'] → leaf-name → kind (or None when undeclared)."""
    c = alg.metadata.get("combine")
    if isinstance(c, str):
        return lambda key: c
    if isinstance(c, dict):
        return lambda key: c.get(key)
    return lambda key: None


def _combine_leaf(kind: str | None, key: str, acc, s0, new):
    if kind == "add":
        return acc + (new - s0)
    if kind == "min":
        return jnp.minimum(acc, new)
    if kind == "max":
        return jnp.maximum(acc, new)
    raise ValueError(
        f"state leaf {key!r} is modified by the kernels but declares no "
        f"combine kind in metadata['combine'] (one of {_COMBINE_KINDS}); "
        f"streaming cannot fold its per-wave partial results"
    )


class _StreamStep:
    """The jitted per-wave step: kernels from iteration-start state,
    partials folded into the running accumulator via the combine spec.

    Pass-through detection happens at trace time: a kernel that returns
    ``dict(state, acc=...)`` leaves the other values as the *same*
    tracer objects, which is exactly the contract "this wave did not
    touch that attribute"."""

    def __init__(self, alg: BlockAlgorithm, direction: str = "push") -> None:
        self.traces = 0
        spec = _combine_spec(alg)
        kernel_sparse, kernel_dense = kernels_for(alg, direction)

        def step(ctx: Context, state0, acc, it, run_dense: bool):
            self.traces += 1
            if not isinstance(state0, dict):
                raise TypeError(
                    f"{alg.name}: streaming requires a dict state pytree"
                )
            new = state0
            # the same named scopes as the in-core step, plus ``fold``
            if kernel_sparse is not None:
                with jax.named_scope("sparse"):
                    new = kernel_sparse(ctx, new, it)
            if kernel_dense is not None and run_dense:
                with jax.named_scope("dense"):
                    new = kernel_dense(ctx, new, it)
            added = set(new) - set(state0)
            if added:  # the in-core step would forward these to post;
                # per-wave there is no baseline to combine them against
                raise ValueError(
                    f"{alg.name}: kernels added state leaves "
                    f"{sorted(added)}; streaming requires kernels to "
                    f"write only leaves present in init_state (declare "
                    f"scratch attributes there)"
                )
            out = {}
            with jax.named_scope("fold"):
                for key in state0:
                    s0, nw = state0[key], new[key]
                    out[key] = (
                        acc[key] if nw is s0
                        else _combine_leaf(spec(key), key, acc[key], s0, nw)
                    )
            return out

        self._jit = jax.jit(step, static_argnums=(4,))

    def __call__(self, ctx, state0, acc, it, run_dense: bool):
        return self._jit(ctx, state0, acc, it, run_dense)


class _PostStep:
    """``post`` + trace counter, jitted once per algorithm identity."""

    def __init__(self, alg: BlockAlgorithm) -> None:
        self.traces = 0

        def step(ctx: Context, state, it):
            self.traces += 1
            with jax.named_scope("post"):
                return alg.post(ctx, state, it)

        self._jit = jax.jit(step)

    def __call__(self, ctx, state, it):
        return self._jit(ctx, state, it)


def _split_static(tree):
    """Flatten ``tree`` into (array leaves, hashable aux): the same
    traced/static split :class:`~repro.core.context.Context` applies to
    ``extras``, reused here so a wave's stacked extras can cross the
    jitted mesh step as a plain tuple of sharded arrays while ints such
    as TC's ``dp``/``steps`` stay static (they drive shapes)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    arrays = tuple(leaf for leaf in leaves if _is_array_leaf(leaf))
    markers = tuple(
        _TRACED if _is_array_leaf(leaf) else leaf for leaf in leaves
    )
    return arrays, (treedef, markers)


def _rejoin_static(aux, arrays):
    treedef, markers = aux
    arr = iter(arrays)
    leaves = [next(arr) if m is _TRACED else m for m in markers]
    return jax.tree_util.tree_unflatten(treedef, leaves)


class _MeshStreamStep:
    """The jitted mesh per-wave step: ``shard_map`` over the wave.

    Each device of the 1-D mesh receives its own shard of the wave's
    padded slab (COO, routing masks, CSR slice, tiles) plus its slice of
    the device-stacked extras, runs the kernels from the *replicated*
    iteration-start state, and the per-leaf updates are combined across
    the mesh with the algorithm's declared collective — ``psum`` for
    additive leaves (on the delta from iteration start, so replicated
    baselines are not multiplied by D), ``pmin``/``pmax`` elementwise —
    then folded into the running accumulator exactly like
    :class:`_StreamStep` does per wave.  Pass-through detection is the
    same trace-time identity test; the mesh program is SPMD, so a leaf
    is uniformly touched or untouched on every device.

    ``combined_keys`` records (at trace time) which state leaves
    actually crossed a collective — the honest basis for the
    ``collective_bytes`` accounting in ``schedule_stats``.
    """

    def __init__(self, alg: BlockAlgorithm, mesh: Mesh,
                 direction: str = "push") -> None:
        self.traces = 0
        self.combined_keys: tuple[str, ...] = ()
        spec = _combine_spec(alg)
        kernel_sparse, kernel_dense = kernels_for(alg, direction)
        axis = mesh.axis_names[0]

        def step(res_ctx, slab, ex_leaves, state0, acc, it,
                 run_dense: bool, ex_aux):
            self.traces += 1
            if not isinstance(state0, dict):
                raise TypeError(
                    f"{alg.name}: streaming requires a dict state pytree"
                )

            def body(res_ctx, slab, ex_leaves, state0, acc, it):
                # each shard sees [1, ...] slices — drop the device axis
                arrays = {k: v[0] for k, v in slab.items()}
                extras = dict(res_ctx.extras)
                if ex_aux is not None:
                    extras.update(_rejoin_static(
                        ex_aux, tuple(leaf[0] for leaf in ex_leaves)
                    ))
                ctx = with_arrays(res_ctx, extras=extras, **arrays)
                new = state0
                if kernel_sparse is not None:
                    with jax.named_scope("sparse"):
                        new = kernel_sparse(ctx, new, it)
                if kernel_dense is not None and run_dense:
                    with jax.named_scope("dense"):
                        new = kernel_dense(ctx, new, it)
                added = set(new) - set(state0)
                if added:
                    raise ValueError(
                        f"{alg.name}: kernels added state leaves "
                        f"{sorted(added)}; streaming requires kernels to "
                        f"write only leaves present in init_state (declare "
                        f"scratch attributes there)"
                    )
                out = {}
                combined = []
                with jax.named_scope("fold"):
                    for key in state0:
                        s0, nw = state0[key], new[key]
                        if nw is s0:
                            out[key] = acc[key]
                            continue
                        kind = spec(key)
                        if kind not in _COMBINE_KINDS:
                            raise ValueError(
                                f"state leaf {key!r} is modified by the kernels "
                                f"but declares no combine kind in "
                                f"metadata['combine'] (one of {_COMBINE_KINDS}); "
                                f"the mesh cannot fold its per-device partials"
                            )
                        red = combine_fn(kind, axis)(
                            nw - s0 if kind == "add" else nw
                        )
                        if kind == "add":
                            out[key] = acc[key] + red
                        elif kind == "min":
                            out[key] = jnp.minimum(acc[key], red)
                        else:
                            out[key] = jnp.maximum(acc[key], red)
                        combined.append(key)
                self.combined_keys = tuple(combined)
                return out

            P = PartitionSpec
            return shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(axis), P(axis), P(), P(), P()),
                out_specs=P(),
                check_vma=False,
            )(res_ctx, slab, ex_leaves, state0, acc, it)

        self._jit = jax.jit(step, static_argnums=(6, 7))

    def __call__(self, res_ctx, slab, ex_leaves, state0, acc, it,
                 run_dense: bool, ex_aux):
        return self._jit(res_ctx, slab, ex_leaves, state0, acc, it,
                         run_dense, ex_aux)


_STREAM_STEP_CACHE: dict[tuple, _StreamStep] = {}
_POST_STEP_CACHE: dict[tuple, _PostStep] = {}


def _stream_step_for(alg: BlockAlgorithm, backend: str, *,
                     share: bool = True,
                     direction: str = "push") -> _StreamStep:
    return shared_entry(_STREAM_STEP_CACHE,
                        alg_cache_key(alg, backend, direction),
                        lambda: _StreamStep(alg, direction), share=share)


def _post_step_for(alg: BlockAlgorithm, backend: str, *,
                   share: bool = True) -> _PostStep | None:
    if alg.post is None:
        return None
    return shared_entry(_POST_STEP_CACHE, alg_cache_key(alg, backend),
                        lambda: _PostStep(alg), share=share)


# ----------------------------------------------------------------------
class _HostArena:
    """Pooled host staging buffers, one free-list per (shape, dtype).

    Every wave slab is padded to the power-of-two bucket ladder, so a
    handful of buffer shapes serves the whole plan: the pipeline
    *takes* zeroed buffers for assembly and *gives* them back once the
    step that read them completed (completion-gated — see the plan's
    ``_park_for_recycle``), keeping steady-state staging memory near
    ``(depth + 1)`` slabs instead of a fresh allocation per wave per
    iteration.  Thread-safe (the background worker takes while the
    main loop gives)."""

    def __init__(self) -> None:
        self._free: dict[tuple, list[np.ndarray]] = {}
        self._lock = threading.Lock()
        self.bytes = 0          # high-water: total bytes ever pooled
        self.reuses = 0

    def take(self, shape, dtype=np.float64) -> np.ndarray:
        key = (tuple(np.atleast_1d(shape).tolist())
               if not np.isscalar(shape) else (int(shape),),
               np.dtype(dtype).str)
        with self._lock:
            pool = self._free.get(key)
            buf = pool.pop() if pool else None
        if buf is None:
            buf = np.zeros(shape, dtype)
            self.bytes += buf.nbytes
            return buf
        self.reuses += 1
        buf.fill(0)             # padding semantics: zeroed like np.zeros
        return buf

    def give(self, *arrays: np.ndarray) -> None:
        with self._lock:
            for a in arrays:
                if a is None:
                    continue
                key = (tuple(a.shape), a.dtype.str)
                self._free.setdefault(key, []).append(a)


class _StagePipeline:
    """Stage 1 of the pipeline: a persistent background worker that
    assembles wave slabs ahead of the compute loop, behind a bounded
    queue.

    With depth ``d`` the worker runs at most ``d`` waves ahead — wave
    ``k+2``'s gathers (and nothing else: ``prepare`` outputs were
    cached by the planning pass) happen while wave ``k`` computes and
    wave ``k+1``'s ``device_put`` crosses the bus.  The worker lives
    across iterations: the main loop *requests* each iteration's wave
    epoch, and requests the next one as soon as the current epoch's
    last slab is drained, so the next iteration's first waves assemble
    while ``post``/host hooks run — no per-iteration cold start.
    ``assemble_s`` is the worker's busy time, ``stall_s`` the main
    loop's time blocked on the queue — their ratio is the
    ``host_stage_overlap`` statistic."""

    def __init__(self, plan: "StreamingPlan", depth: int) -> None:
        self._q: queue.Queue = queue.Queue(maxsize=max(int(depth), 1))
        self._cmd: queue.Queue = queue.Queue()
        self.assemble_s = 0.0
        self.stall_s = 0.0
        self.dead = False
        self._err: BaseException | None = None
        self._t = threading.Thread(target=self._work, args=(plan,),
                                   name="repro-staging", daemon=True)
        self._t.start()

    def _work(self, plan: "StreamingPlan") -> None:
        try:
            while True:
                indices = self._cmd.get()
                if indices is None:
                    return
                for w in indices:
                    t0 = time.perf_counter()
                    slab = plan._assemble_runtime(plan._slabs[w], wave=w)
                    self.assemble_s += time.perf_counter() - t0
                    self._q.put(slab)
        except BaseException as e:  # surfaced on the consumer side
            self._err = e
            self._q.put(None)

    def request(self, indices) -> None:
        """Enqueue one epoch (an iteration's wave order) for assembly."""
        self._cmd.put(list(indices))

    def get(self) -> "_WaveSlab":
        t0 = time.perf_counter()
        with obs.span("stage_wait", lane="main"):
            slab = self._q.get()
        self.stall_s += time.perf_counter() - t0
        if slab is None:
            # the worker died; mark it so the watchdog fails over to
            # synchronous assembly instead of waiting on a dead queue
            self.dead = True
            raise WorkerDeath(self._err)
        return slab

    def close(self, arena: _HostArena) -> None:
        """Stop the worker; speculatively assembled slabs hand their
        buffers straight back to the arena (they were never staged).
        Keeps draining while the worker finishes its in-flight epoch
        (it may be blocked on the bounded queue), then joins the thread
        so teardown is deterministic — no daemon-thread leak survives
        ``StreamingPlan.close()``."""
        self._cmd.put(None)
        while self._t.is_alive() or not self._q.empty():
            try:
                slab = self._q.get(timeout=0.05)
            except queue.Empty:
                continue
            if slab is not None:
                arena.give(*slab.arena_arrays)
        self._t.join(timeout=5.0)


# ----------------------------------------------------------------------
class _HostLane:
    """The host-CPU compute lane of heterogeneous co-scheduling.

    Each execution *unit* is one wave's peeled ``host_task_ids``
    (:func:`repro.core.membudget.peel_host_tasks`).  A unit's context is
    built once — the unit's COO slice gathered from the host store, the
    global CSR views shared across every unit, and the algorithm's
    ``prepare`` outputs for the unit's restricted sub-schedule — with
    every array leaf committed to the host CPU jax backend, and the
    sparse kernel runs *eagerly* under ``jax.default_device(cpu)`` in a
    ``concurrent.futures`` thread pool while the device pipeline
    streams its own waves.  Nothing here is ever ``device_put`` to the
    accelerator: host units never touch the memory budget.

    Peeled dense tasks run the sparse formulation on the host — each
    unit's sub-schedule clears its dense routing masks, and the two
    paths agree per block-list (the same property the dense/sparse
    split relies on), so results stay bit-identical for integer/bool
    attributes.  Per-unit updates fold through the identical
    ``metadata["combine"]`` contract as device waves: ``add`` folds the
    delta from iteration-start state, ``min``/``max`` fold elementwise,
    and pass-through leaves are detected by the same identity test
    :class:`_StreamStep` applies at trace time — here evaluated
    eagerly, where it holds for exactly the same ``dict(state, k=v)``
    kernel idiom.

    ``prepare`` runs against the *global* store view (``plan=None`` —
    the unpadded branch of staged-prepare algorithms), so
    host-computed positions index the global CSR the host already
    holds; nothing is sliced or rebased for the host lane.
    """

    def __init__(self, plan: "StreamingPlan",
                 units: list[np.ndarray]) -> None:
        self.plan = plan
        self.units = [np.asarray(u, np.int64) for u in units]
        self._spec = _combine_spec(plan.alg)
        self._cpu = jax.devices("cpu")[0]
        store = plan.store
        t0 = time.perf_counter()
        with obs.span("host_lane_build", lane="main", units=len(self.units)), \
                jax.default_device(self._cpu):
            # global CSR views: converted to CPU-committed jax arrays
            # ONCE and shared by every unit context (eager lax.cond
            # traces both kernel branches, so even csr="none"
            # algorithms need indexable adjacency leaves — and numpy
            # arrays indexed by tracers would fail inside the trace)
            self._globals = {
                k: self._put(v) for k, v in dict(
                    indptr=store.indptr, indices=store.indices,
                    degrees=store.degrees,
                    row_block_ptr=store.row_block_ptr,
                    cuts=store.layout.cuts,
                ).items()
            }
            self._ctxs = [self._unit_context(ids) for ids in self.units]
        plan._phase["prepare"] += time.perf_counter() - t0
        self._pool = concurrent.futures.ThreadPoolExecutor(
            max_workers=min(len(self.units),
                            max(1, (os.cpu_count() or 2) - 1)),
            thread_name_prefix="repro-host",
        )

    def _put(self, a):
        """CPU-committed jax array from any array-like."""
        return jax.device_put(np.asarray(a), self._cpu)

    def _unit_context(self, ids: np.ndarray) -> Context:
        plan = self.plan
        store, sched = plan.store, plan.schedule
        hsched = sched.restrict(ids)
        # peeled dense tasks run the sparse formulation on the host:
        # clearing the routing masks sends every edge down the sparse
        # path and keeps prepare from bucketing dense-path work
        hsched.dense_task_mask = np.zeros(hsched.num_tasks, bool)
        hsched.dense_block_ids = np.zeros(0, np.int32)
        blocks = np.unique(hsched.blocklists)
        segments = store.edge_segments(blocks)
        idx = (
            np.concatenate([np.arange(s, e, dtype=np.int64)
                            for s, e in segments])
            if segments else np.zeros(0, np.int64)
        )
        extras = {}
        if plan.alg.prepare is not None:
            extras = _to_host(plan.alg.run_prepare(store, hsched, None))
            extras.pop("__workspace_bytes__", None)
        extras = jax.tree_util.tree_map(
            lambda l: self._put(l) if _is_array_leaf(l) else l, extras
        )
        ne = int(idx.size)
        return Context(
            extras=extras,
            n=store.n, m=store.m, p=store.p,
            tile_dim=sched.tile_dim,
            backend="reference",
            src=self._put(store.src[idx]),
            dst=self._put(store.dst[idx]),
            edge_block=self._put(store.edge_block[idx]),
            sparse_edge_mask=self._put(np.ones(ne, bool)),
            dense_edge_mask=self._put(np.zeros(ne, bool)),
            **self._globals,
        )

    def submit(self, state0, it: int, direction: str = "push") -> list:
        """Snapshot iteration-start state to the host CPU and dispatch
        every unit into the pool; returns futures for ``fold``.

        ``direction`` selects the sparse kernel variant — the host lane
        must run the *same* direction as the device waves within one
        iteration, or the push/pull bit-identity contract (which holds
        per direction, not across a mix) breaks."""
        hstate = {k: self._put(v) for k, v in state0.items()}
        iarr = self._put(np.int32(it))
        kernel, _ = kernels_for(self.plan.alg, direction)
        return [self._pool.submit(self._run_unit, u, hstate, iarr, kernel)
                for u in range(len(self.units))]

    def _run_unit(self, u: int, hstate: dict, iarr, kernel):
        it = int(np.asarray(jax.device_get(iarr)))
        try:
            return self._run_unit_inner(u, hstate, iarr, kernel)
        except HostTaskError:
            raise
        except Exception as e:
            # attach unit/task/iteration blame here, where it is known —
            # not at fold time, where the bare future exception used to
            # surface with no context at all
            raise HostTaskError(u, self.units[u].tolist(), it, e) from e

    def _run_unit_inner(self, u: int, hstate: dict, iarr, kernel):
        alg = self.plan.alg
        faults = self.plan._faults
        t0 = time.perf_counter()
        with obs.span("host_compute", lane="host-compute", unit=u,
                      tasks=int(self.units[u].size)):
            if faults is not None:
                faults.fire("host.task", unit=u)
            with jax.default_device(self._cpu):
                new = kernel(self._ctxs[u], hstate, iarr)
        added = set(new) - set(hstate)
        if added:
            raise ValueError(
                f"{alg.name}: kernels added state leaves "
                f"{sorted(added)}; streaming requires kernels to "
                f"write only leaves present in init_state (declare "
                f"scratch attributes there)"
            )
        payload = {}
        for key, s0 in hstate.items():
            nw = new[key]
            if nw is s0:
                continue
            kind = self._spec(key)
            if kind not in _COMBINE_KINDS:
                raise ValueError(
                    f"state leaf {key!r} is modified by the kernels but "
                    f"declares no combine kind in metadata['combine'] "
                    f"(one of {_COMBINE_KINDS}); the host lane cannot "
                    f"fold its per-unit partial results"
                )
            payload[key] = (
                kind,
                np.asarray(nw - s0) if kind == "add" else np.asarray(nw),
            )
        return payload, time.perf_counter() - t0

    def fold(self, results: list, acc: dict) -> tuple[dict, float]:
        """Merge every unit's payload (in unit order — deterministic)
        and fold ONCE into the device accumulator with the same
        semantics as :func:`_combine_leaf`: exact for integer/boolean
        attributes, up to summation order for floats."""
        merged: dict[str, tuple[str, np.ndarray]] = {}
        busy_s = 0.0
        for payload, dt in results:
            busy_s += dt
            for key, (kind, val) in payload.items():
                if key not in merged:
                    merged[key] = (kind, val)
                elif kind == "add":
                    merged[key] = (kind, merged[key][1] + val)
                elif kind == "min":
                    merged[key] = (kind, np.minimum(merged[key][1], val))
                else:
                    merged[key] = (kind, np.maximum(merged[key][1], val))
        out = dict(acc)
        for key, (kind, val) in merged.items():
            v = jnp.asarray(val)
            if kind == "add":
                out[key] = acc[key] + v
            elif kind == "min":
                out[key] = jnp.minimum(acc[key], v)
            else:
                out[key] = jnp.maximum(acc[key], v)
        return out, busy_s

    def close(self, wait: bool = False) -> None:
        """Shut the pool down; ``wait=True`` joins the worker threads —
        the deterministic-teardown path of ``StreamingPlan.close()``."""
        self._pool.shutdown(wait=wait, cancel_futures=True)


# ----------------------------------------------------------------------
@dataclass
class _WaveSlab:
    """Host-side staged form of one wave: padded numpy arrays ready for
    a single ``jax.device_put`` per iteration.

    Under a mesh the same fields carry a leading device axis (``[D, …]``
    per-device slabs, uniformly padded), ``staged_bytes`` totals the
    whole wave's H2D traffic, and ``per_device_bytes`` is the share one
    mesh device holds — the quantity the per-device budget bounds.
    ``arena_arrays`` names the buffers drawn from the staging arena
    (runtime assembly only) so ``_put_slab`` can recycle exactly those."""

    wave: Wave
    src: np.ndarray
    dst: np.ndarray
    edge_block: np.ndarray
    sparse_mask: np.ndarray
    dense_mask: np.ndarray
    tiles: np.ndarray | None
    tile_row_start: np.ndarray | None
    tile_col_start: np.ndarray | None
    csr: np.ndarray | None         # bucket-padded conformal CSR slice
    extras: Any                    # host pytree, or None once hoisted resident
    run_dense: bool
    staged_bytes: int
    workspace_bytes: int           # kernel scratch estimate (not staged)
    edges: int
    segments: int                  # coalesced COO slices gathered
    csr_entries: int               # unpadded CSR slice length
    csr_segments: int              # coalesced CSR row-range gathers
    per_device_bytes: int = 0      # one device's staged share (mesh)
    arena_arrays: tuple = ()       # arena-owned buffers to recycle
    prep_ws: int = 0               # prepare-declared share of workspace


@dataclass
class _WaveRecipe:
    """The retained, array-free description of one planned wave.

    The planning pass assembles every wave once (budget verification,
    splits, hoisting, byte accounting) and keeps only this recipe plus
    the cached ``prepare`` outputs — the big gather arrays are
    reproduced per iteration by the staging pipeline into arena
    buffers, so host memory holds ``O(pipeline depth)`` slabs instead
    of every wave at once."""

    wave: Wave
    run_dense: bool
    staged_bytes: int
    workspace_bytes: int
    per_device_bytes: int
    edges: int
    segments: int
    csr_entries: int
    csr_segments: int
    csr_bytes: int                 # padded CSR slab bytes (0 when none)
    src_bucket: int                # padded edge-slab width
    extras: Any = None             # cached post-hoist prepare outputs


@dataclass
class _PlanUnit:
    """One wave mid-planning: the assembled slab plus its *raw* prepare
    outputs, so :meth:`StreamingPlan._fit_unified` can re-derive the
    shared extras shapes after any split without re-running prepare."""

    slab: _WaveSlab
    dev_extras: list | None = None   # mesh: per-device raw prepare outputs
    raw_extras: Any = None           # single device: the wave's raw outputs
    base_staged: int = 0             # staged bytes excluding extras
    base_ws: int = 0
    prep_ws: int = 0                 # prepare-declared share of base_ws

    @classmethod
    def of_single(cls, slab: _WaveSlab) -> "_PlanUnit":
        return cls(
            slab=slab, raw_extras=slab.extras,
            base_staged=slab.staged_bytes - tree_array_bytes(slab.extras),
            base_ws=slab.workspace_bytes, prep_ws=slab.prep_ws,
        )


def _is_array_leaf(leaf: Any) -> bool:
    return isinstance(leaf, (np.ndarray, jax.Array))


def _to_host(tree: Any) -> Any:
    return jax.tree_util.tree_map(
        lambda l: np.asarray(l) if _is_array_leaf(l) else l, tree
    )


def _put_arrays(tree: Any) -> Any:
    """device_put only the array leaves; static leaves stay untouched."""
    return jax.tree_util.tree_map(
        lambda l: jax.device_put(l) if _is_array_leaf(l) else l, tree
    )


def _trees_equal(a: Any, b: Any) -> bool:
    la, ta = jax.tree_util.tree_flatten(a)
    lb, tb = jax.tree_util.tree_flatten(b)
    if ta != tb or len(la) != len(lb):
        return False
    for x, y in zip(la, lb):
        if _is_array_leaf(x) != _is_array_leaf(y):
            return False
        if _is_array_leaf(x):
            if not np.array_equal(np.asarray(x), np.asarray(y)):
                return False
        elif x != y:
            return False
    return True


def _block_tree(tree: Any) -> None:
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


_ABSENT = object()


# ----------------------------------------------------------------------
class StreamingPlan:
    """A compiled plan whose execution streams budget-sized waves.

    Produced by ``compile_plan(alg, store, memory_budget=...)``.  Same
    ``run()`` contract as :class:`~repro.core.engine.Plan` (hooks, post,
    iteration control, RunResult), but the per-iteration step is the
    three-stage pipelined wave loop described in the module docstring,
    and ``schedule_stats`` additionally carries a ``"streaming"`` dict:
    wave count, bytes staged per wave (each ≤ budget), resident bytes,
    per-phase wall clock, trace count, arena bytes, and the measured
    overlap efficiencies.
    """

    def __init__(self, alg: BlockAlgorithm, store: BlockStore,
                 schedule: Schedule | None = None, *,
                 memory_budget: int | str | MemoryBudget,
                 backend: str = "xla", num_devices: int = 1,
                 mode: str = "hybrid", tile_dim: int = 512,
                 dense_frac: float = 0.5, dense_density: float = 0.005,
                 rebalance_threshold: float | str | None = "auto",
                 pipeline_depth: int = PIPELINE_DEPTH,
                 share: bool = True, mesh: Mesh | None = None,
                 host_fraction: float | str | None = "auto",
                 direction: str | None = None,
                 faults: "str | FaultPlan | None" = None,
                 checkpoint_every: int | None = None,
                 checkpoint_dir: str | None = None,
                 retry_policy: RetryPolicy | None = None) -> None:
        from ..kernels.registry import host_executable, resolve_backend

        self.alg = alg
        self.store = store
        self.backend = resolve_backend(backend)
        self.direction = resolve_direction(alg, direction)
        # None keeps the pre-direction contract (plain push, no
        # controller, no schedule_stats["direction"] block)
        self._direction_requested = direction is not None
        self._direction_now = "push"    # the current iteration's choice
        self.budget = MemoryBudget.of(memory_budget)
        self._csr_mode = str(alg.metadata.get("csr", "resident"))
        if self._csr_mode not in _CSR_MODES:
            raise ValueError(
                f"{alg.name}: metadata['csr'] must be one of {_CSR_MODES}, "
                f"got {self._csr_mode!r}"
            )
        self.mesh = mesh
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError(
                    "mesh-cooperative streaming requires a 1-D mesh (one "
                    f"block-parallel axis); got axes {mesh.axis_names}"
                )
            if alg.metadata.get("mesh") != "shard":
                raise ValueError(
                    f"{alg.name}: metadata['mesh'] must declare 'shard' to "
                    "run under a mesh — the kernels must decompose over any "
                    "partition of a wave's tasks judged from iteration-start "
                    "state, and prepare must restrict to a device-local view "
                    "(see docs/distributed.md)"
                )
            self.mesh_axis = mesh.axis_names[0]
            self._mesh_devices = int(mesh.size)
        else:
            self.mesh_axis = None
            self._mesh_devices = 1
        if not (rebalance_threshold is None
                or rebalance_threshold == "auto"
                or isinstance(rebalance_threshold, (int, float))):
            raise ValueError(
                "rebalance_threshold must be 'auto' (default: deterministic "
                "estimate-vs-observed divergence trigger), a float (legacy "
                "compute-skew threshold), or None (off); got "
                f"{rebalance_threshold!r}"
            )
        self.rebalance_threshold = rebalance_threshold
        # -- heterogeneous co-scheduling: the host CPU as a resource ---
        if not (host_fraction is None or host_fraction == "auto"
                or isinstance(host_fraction, (int, float))):
            raise ValueError(
                "host_fraction must be 'auto' (default: calibrated "
                "host/device split), a float in [0, 1] (fixed share of "
                "each wave's work peeled to the host CPU), or None "
                f"(off); got {host_fraction!r}"
            )
        if (isinstance(host_fraction, (int, float))
                and not 0.0 <= float(host_fraction) <= 1.0):
            raise ValueError(
                f"host_fraction must lie in [0, 1]; got {host_fraction!r}"
            )
        host_flag = str(alg.metadata.get("host", "auto"))
        if host_flag not in ("auto", "never"):
            raise ValueError(
                f"{alg.name}: metadata['host'] must be 'auto' or "
                f"'never', got {host_flag!r}"
            )
        blockers = []
        if alg.kernel_sparse is None:
            blockers.append("the algorithm has no kernel_sparse (host "
                            "units run the sparse formulation)")
        if host_flag == "never":
            blockers.append("metadata['host'] declares 'never'")
        uncertified = [k for k in alg.metadata.get("host_kernels", ())
                       if not host_executable(k)]
        if uncertified:
            blockers.append(
                f"metadata['host_kernels'] names kernels not certified "
                f"host-executable: {uncertified}"
            )
        if mesh is not None:
            blockers.append("mesh-cooperative streaming (the mesh "
                            "already owns the wave partition)")
        self._host_capable = not blockers
        if (isinstance(host_fraction, (int, float))
                and float(host_fraction) > 0.0 and blockers):
            raise ValueError(
                f"{alg.name}: host_fraction={host_fraction!r} requires "
                f"host-lane capability — " + "; ".join(blockers)
            )
        self._host_frac_req = host_fraction
        # "auto" resolves to a zero split until calibration activates
        # it; an incapable algorithm silently stays device-only there
        self._host_frac = (
            host_fraction
            if self._host_capable and host_fraction is not None else 0.0
        )
        # -- fault tolerance: injection, retry ladder, checkpoints -----
        # REPRO_FAULTS is the env spelling of compile_plan(faults=...);
        # an explicit argument wins.  Disabled is self._faults = None —
        # every seam guards with one `is not None` check (the obs idiom)
        self._faults = FaultPlan.parse(
            faults if faults is not None else _knob_str("REPRO_FAULTS"))
        if retry_policy is not None and not isinstance(retry_policy,
                                                       RetryPolicy):
            raise TypeError(
                f"retry_policy must be a repro.core.resilience."
                f"RetryPolicy; got {type(retry_policy).__name__}")
        self._policy = retry_policy or RetryPolicy()
        if checkpoint_every is not None and int(checkpoint_every) < 1:
            raise ValueError(
                f"checkpoint_every must be >= 1; got {checkpoint_every!r}")
        if checkpoint_every is not None and checkpoint_dir is None:
            raise ValueError(
                "checkpoint_every requires checkpoint_dir (where the "
                "per-iteration snapshots persist)")
        # a directory alone means "checkpoint every iteration"
        self._ckpt_every = (int(checkpoint_every) if checkpoint_every
                            else (1 if checkpoint_dir else 0))
        self._ckpt_dir = checkpoint_dir
        self._resil = ResilienceStats()
        self._injected_pub = 0          # injections already published
        self._sync_iters_left = 0       # transient sync-assembly window
        self._worker_deaths = 0
        self._host_failures = 0
        self._host_futs: list | None = None   # in-flight host futures
        self.pipeline_depth = max(int(pipeline_depth), 0)
        self.schedule = schedule or build_schedule(
            alg, store, num_devices=max(num_devices, self._mesh_devices),
            mode=mode, tile_dim=tile_dim, dense_frac=dense_frac,
            dense_density=dense_density, memory_budget=self.budget,
            direction=self.direction,
        )
        self.host = build_host_ctx(store, self.schedule, backend=self.backend)
        # the cross-wave staging plan: shape-driving prepare decisions
        # (TC's bucket ladder) made once against the FULL schedule
        self._plan_state = (
            alg.stage_plan(store, self.schedule)
            if alg.stage_plan is not None else None
        )
        self._phase = {p: 0.0 for p in PHASES}
        self._arena = _HostArena()
        self._arena_deferred: list[tuple] = []
        self._pipe: _StagePipeline | None = None

        # "auto" prices the max over the push/pull dense variants, so
        # whichever direction an iteration picks fits the planned budget
        self._workspace_decl = workspace_kernels(alg, self.direction)
        self._footprints = task_footprints(
            store, self.schedule,
            workspace_kernel=self._workspace_decl,
            stage_csr=self._csr_mode == "slice",
        )
        self._host_ratio = _hetero_host_ratio_default()
        self._host_units: list[np.ndarray] = []
        self._host_lane: _HostLane | None = None
        self._host_seconds = 0.0
        self._host_tasks_executed = 0
        self._host_measured = False
        self._hetero_refreshes = 0
        waves = build_waves(store, self.schedule, self.budget,
                            self._footprints, devices=self._mesh_devices,
                            host_fraction=self._host_frac,
                            host_ratio=self._host_ratio)
        self._apply_waves(waves, initial=True)
        # the one-time planning pass's host cost (per-wave prepare),
        # reported separately from the per-run phase deltas
        self._planning_phase = dict(self._phase)
        self._resident = self._build_resident_context()
        self._step = _stream_step_for(alg, self.backend, share=share)
        self._mesh_step = (
            _MeshStreamStep(alg, mesh) if mesh is not None else None
        )
        # pull twins, built only when the plan may take a pull
        # iteration; each direction traces once (cache keys the variant)
        want_pull = self.direction in ("pull", "auto")
        self._step_pull = (
            _stream_step_for(alg, self.backend, share=share,
                             direction="pull") if want_pull else None
        )
        self._mesh_step_pull = (
            _MeshStreamStep(alg, mesh, "pull")
            if want_pull and mesh is not None else None
        )
        self._post = _post_step_for(alg, self.backend, share=share)
        self._calibration: dict | None = None
        self._collective_bytes = 0      # payload across mesh combines
        self._collective_unit_s = 0.0   # isolated all-reduce estimate
        self._bytes_staged = 0          # actual H2D traffic, all passes
        self._stall_s = 0.0             # main loop blocked on the queue
        self._assemble_overlapped_s = 0.0
        self._edge_free = int(alg.metadata.get("edge_free_iterations", 0))
        self._edge_free_bufs: dict | None = None
        # first-k-neighbors CSR for the edge-free sampling phase: the
        # only adjacency those iterations see (vertex-proportional)
        self._prefix_host = (
            csr_prefix(store.indptr, store.indices, self._edge_free)
            if self._edge_free > 0 else None
        )
        self._prefix_dev: dict | None = None
        self._rebalanced = False
        self._reb_armed = True
        self._last_skew: float | None = None
        self._last_divergence: float | None = None
        self.schedule.stats["waves"] = len(self._slabs)

    # -- build side (planning pass) ------------------------------------
    def _plan_recipes(self, waves: list[Wave], *,
                      initial: bool = False) -> list[_WaveRecipe]:
        """Assemble each wave once, decide hoisting (first build only),
        unify extras shapes across waves, verify/split against the
        budget, and retain only the recipes.

        Any wave whose *actual* staged bytes overflow the budget (model
        under-priced prepare extras, bucket padding) is split.  Wave-
        invariant extras are hoisted resident *before* the budget check
        — they are staged once, not per wave, so counting them per wave
        would spuriously reject (or over-split) workable budgets."""
        if self.mesh is not None:
            units = [self._make_unit(w) for w in waves]
            if initial:
                self._resident_extras: dict = {}
                self._hoisted = False
                trees = [e for u in units for e in u.dev_extras]
                if trees and all(_trees_equal(e, trees[0])
                                 for e in trees[1:]):
                    # device- and wave-invariant prepare outputs
                    # (PageRank's inv_deg, ...) are staged once,
                    # replicated over the mesh
                    self._resident_extras = trees[0]
                    self._hoisted = True
            if self._hoisted or self.alg.mesh_pack is None:
                slabs = [self._finalize_mesh_extras(u.slab, u.dev_extras)
                         for u in units]
                slabs = self._fit_slabs(slabs)
            else:
                slabs = self._fit_unified(units)
        else:
            slabs = [self._assemble(w) for w in waves]
            if initial:
                self._decide_hoist(slabs)
            else:
                # re-pack rebuild: the hoist decision stands (the
                # resident context already carries the hoisted extras)
                for s in slabs:
                    self._strip_hoisted(s)
            if self._hoisted or self.alg.mesh_pack is None:
                slabs = self._fit_slabs(slabs)
            else:
                slabs = self._fit_unified(
                    [_PlanUnit.of_single(s) for s in slabs]
                )
        return [self._recipe(s) for s in slabs]

    def _apply_waves(self, waves: list[Wave], *,
                     initial: bool = False) -> None:
        """Install a packed wave list: device tasks stay in the
        streaming pipeline (empty waves vanish), peeled
        ``host_task_ids`` become host-lane execution units, and the
        lane (thread pool + per-unit CPU contexts) is rebuilt."""
        with obs.span("plan_waves", lane="main", initial=initial,
                      waves=len(waves)):
            if self._host_lane is not None:
                self._host_lane.close()
                self._host_lane = None
            self._host_units = [w.host_task_ids for w in waves
                                if w.host_task_ids.size]
            dev_waves = [w for w in waves if w.task_ids.size]
            self._slabs = self._plan_recipes(dev_waves, initial=initial)
            edge_free = int(self.alg.metadata.get("edge_free_iterations", 0))
            if (self._host_units and not self._slabs and not self._hoisted
                    and self.alg.prepare is not None
                    and (self.alg.post is not None or edge_free > 0)):
                # fully host-peeled plan (host_fraction=1.0): post / the
                # edge-free phase still run against the resident context,
                # whose extras are normally hoisted from the device waves'
                # prepare outputs — no device wave exists here, so prepare
                # runs once against the full store instead
                extras = _to_host(self.alg.run_prepare(
                    self.store, self.schedule, self._plan_state))
                extras.pop("__workspace_bytes__", None)
                self._resident_extras = extras
                self._hoisted = True
            if self._host_units:
                self._host_lane = _HostLane(self, self._host_units)
            self.schedule.stats["waves"] = len(self._slabs)

    def _make_unit(self, wave: Wave) -> "_PlanUnit":
        """Assemble one wave into a planning unit (raw extras kept)."""
        if self.mesh is not None:
            slab, lst = self._assemble_mesh(wave)
            return _PlanUnit(slab=slab, dev_extras=lst,
                             base_staged=slab.staged_bytes,
                             base_ws=slab.workspace_bytes,
                             prep_ws=slab.prep_ws)
        slab = self._assemble(wave)
        self._strip_hoisted(slab)
        return _PlanUnit.of_single(slab)

    def _fit_unified(self, units: list["_PlanUnit"]) -> list[_WaveSlab]:
        """Cross-wave shape cache + budget fit, to fixpoint.

        Every wave's ``prepare`` outputs are padded to one shared shape
        set via the algorithm's ``mesh_pack`` — it already solves
        exactly this problem for per-device outputs (unify
        data-dependent structures like TC's bucket ladder with
        kernel-neutral padding, array leaves gaining a leading axis);
        treating the *waves* (× devices, under a mesh) as that axis
        makes every wave's extras structurally identical, so the jitted
        step traces once per distinct slab shape instead of once per
        wave.  Because padding can push a unified slab over the budget,
        the loop verifies the *unified* bytes, splits any offender, and
        re-unifies the new wave set (smaller waves shrink the shared
        caps) until every wave fits.  When even a single-task wave
        cannot afford the shared caps (very tight budgets), unification
        is abandoned for the whole plan — per-wave shapes cost extra
        jit traces but keep the ≤ budget invariant without refusing a
        runnable workload."""
        if not units:       # fully host-peeled plan: no device waves
            return []
        d = self._mesh_devices
        while True:
            slabs = [u.slab for u in units]
            if self.mesh is not None:
                flat = [e for u in units for e in u.dev_extras]
                packed = _to_host(self.alg.mesh_pack(flat))

                def sliced(w):
                    return jax.tree_util.tree_map(
                        lambda leaf: (leaf[w * d: (w + 1) * d]
                                      if _is_array_leaf(leaf) else leaf),
                        packed,
                    )
            else:
                packed = _to_host(
                    self.alg.mesh_pack([u.raw_extras for u in units])
                )

                def sliced(w):
                    return jax.tree_util.tree_map(
                        lambda leaf: (leaf[w] if _is_array_leaf(leaf)
                                      else leaf),
                        packed,
                    )
            # uniform shapes → uniform device scratch.  mesh_pack may
            # re-declare the prepare scratch for the *unified* shapes
            # (every wave now runs every bucket at the padded cap — the
            # per-wave pre-unification declarations can under-count
            # when different waves define different buckets' caps);
            # the dense-path share stays the per-wave max.
            ws_decl = None
            if isinstance(packed, dict):
                ws_decl = packed.pop("__workspace_bytes__", None)
            if ws_decl is not None:
                ws = (max(u.base_ws - u.prep_ws for u in units)
                      + int(ws_decl))
            else:
                ws = max(u.base_ws for u in units)
            for w, u in enumerate(units):
                u.slab.extras = sliced(w)
                u.slab.staged_bytes = (
                    u.base_staged + tree_array_bytes(u.slab.extras)
                )
                u.slab.workspace_bytes = ws
                if self.mesh is not None:
                    u.slab.per_device_bytes = -(-u.slab.staged_bytes // d)
            over = {
                w for w, u in enumerate(units)
                if self._budget_load(u.slab) > self.budget.total_bytes
            }
            if not over:
                return slabs
            try:
                rebuilt: list[_PlanUnit] = []
                for w, u in enumerate(units):
                    if w in over:
                        a, b = split_wave(u.slab.wave, self.schedule,
                                          self._footprints)
                        rebuilt += [self._make_unit(a), self._make_unit(b)]
                    else:
                        rebuilt.append(u)
                units = rebuilt
            except ValueError:
                # a single-task wave cannot afford the shared caps:
                # fall back to raw per-wave shapes for the whole plan
                return self._fit_slabs(
                    [self._restore_raw(u) for u in units]
                )

    def _restore_raw(self, u: "_PlanUnit") -> _WaveSlab:
        """Undo shape unification on one planning unit."""
        slab = u.slab
        slab.workspace_bytes = u.base_ws
        if self.mesh is not None:
            slab.staged_bytes = u.base_staged
            slab.extras = None
            return self._finalize_mesh_extras(slab, u.dev_extras)
        slab.extras = u.raw_extras
        slab.staged_bytes = u.base_staged + tree_array_bytes(u.raw_extras)
        return slab

    def _recipe(self, slab: _WaveSlab) -> _WaveRecipe:
        return _WaveRecipe(
            wave=slab.wave, run_dense=slab.run_dense,
            staged_bytes=slab.staged_bytes,
            workspace_bytes=slab.workspace_bytes,
            per_device_bytes=slab.per_device_bytes,
            edges=slab.edges, segments=slab.segments,
            csr_entries=slab.csr_entries, csr_segments=slab.csr_segments,
            csr_bytes=slab.csr.nbytes if slab.csr is not None else 0,
            src_bucket=int(slab.src.shape[-1]),
            extras=slab.extras,
        )

    def _reassemble(self, wave: Wave) -> _WaveSlab:
        """One wave → finished slab, honoring the standing hoist
        decision — shared by budget splits and rebalance rebuilds."""
        if self.mesh is not None:
            slab, extras_list = self._assemble_mesh(wave)
            return self._finalize_mesh_extras(slab, extras_list)
        slab = self._assemble(wave)
        self._strip_hoisted(slab)
        return slab

    def _budget_load(self, slab: _WaveSlab) -> int:
        """The bytes the budget must bound: one device's staged share
        plus its kernel scratch (per-device under a mesh; the whole
        slab on a single device)."""
        staged = (slab.per_device_bytes if self.mesh is not None
                  else slab.staged_bytes)
        return staged + slab.workspace_bytes

    def _fit_slabs(self, slabs: list[_WaveSlab]) -> list[_WaveSlab]:
        out: list[_WaveSlab] = []
        pending = list(slabs)
        while pending:
            slab = pending.pop(0)
            if self._budget_load(slab) > self.budget.total_bytes:
                # staged arrays + kernel scratch are the wave's real
                # device footprint; split_wave raises for size-1 waves —
                # the ≤ budget invariant is never silently violated
                a, b = split_wave(slab.wave, self.schedule, self._footprints)
                pending[:0] = [self._reassemble(a), self._reassemble(b)]
                continue
            out.append(slab)
        return out

    def _assemble_runtime(self, recipe: _WaveRecipe, *,
                          wave: int = -1) -> _WaveSlab:
        """Stage-1 body: reproduce one wave's slab into arena buffers.

        Pure gathers — ``prepare`` ran in the planning pass and its
        (post-hoist) outputs are cached on the recipe, so the worker
        thread never touches jax or the algorithm.  Byte accounting is
        pinned to the recipe's planned numbers (they are equal by
        construction; pinning keeps the stats deterministic).  The span
        lands on the ``staging`` lane whichever thread runs it — the
        background worker in steady state, the main loop during
        calibration and at ``pipeline_depth=0``."""
        with obs.span("assemble", lane="staging", wave=wave,
                      bytes=recipe.staged_bytes):
            if self._faults is not None:
                # fires on whichever thread assembles: a raise in the
                # background worker surfaces as WorkerDeath at get()
                self._faults.fire("stage.assemble", wave=wave)
            if self.mesh is not None:
                slab, _ = self._assemble_mesh(
                    recipe.wave, extras=recipe.extras,
                    alloc=self._arena.take,
                )
            else:
                slab = self._assemble(recipe.wave, extras=recipe.extras,
                                      alloc=self._arena.take)
        slab.staged_bytes = recipe.staged_bytes
        slab.workspace_bytes = recipe.workspace_bytes
        slab.per_device_bytes = recipe.per_device_bytes
        return slab

    def _assemble(self, wave: Wave, *, extras: Any = _ABSENT,
                  alloc=None) -> _WaveSlab:
        """Assemble one wave's padded host slab.

        Planning mode (``extras`` absent): build the wave-local store
        view, run the algorithm's ``prepare`` against it (timed into
        the ``prepare`` phase), and measure the staged bytes.  Runtime
        mode (``extras`` given — the recipe's cached outputs, possibly
        ``None`` after hoisting): gathers only, drawn from ``alloc``
        (the staging arena)."""
        store, sched = self.store, self.schedule
        zeros = alloc if alloc is not None else np.zeros
        planning = extras is _ABSENT
        wsched = sched.restrict(wave.task_ids)
        blocks = np.unique(wsched.blocklists)
        segments = store.edge_segments(blocks)
        idx = (
            np.concatenate([np.arange(s, e, dtype=np.int64)
                            for s, e in segments])
            if segments else np.zeros(0, np.int64)
        )
        ne = int(idx.size)
        eb = bucket_size(ne)
        src = zeros(eb, np.int32)
        dst = zeros(eb, np.int32)
        edge_block = zeros(eb, np.int32)
        sparse_mask = zeros(eb, bool)
        dense_mask = zeros(eb, bool)
        arena_arrays = [src, dst, edge_block, sparse_mask, dense_mask]
        if ne:
            src[:ne] = store.src[idx]
            dst[:ne] = store.dst[idx]
            edge_block[:ne] = store.edge_block[idx]
            dense_blocks = np.zeros(store.layout.num_blocks, bool)
            if wsched.dense_block_ids.size:
                dense_blocks[wsched.dense_block_ids] = True
            edense = dense_blocks[edge_block[:ne]]
            sparse_mask[:ne] = ~edense
            dense_mask[:ne] = edense

        # -- dense tiles (already materialized by build_schedule) ------
        tiles = trs = tcs = None
        run_dense = (
            self.alg.kernel_dense is not None
            and bool(wsched.dense_task_mask.any())
        )
        wstore = store
        if run_dense:
            sub, sub_rs, sub_cs = store.tile_subset(wsched.dense_block_ids)
            nd = sub.shape[0]
            tb = bucket_size(nd, minimum=1)
            t = sched.tile_dim
            tiles = zeros((tb, t, t), np.float32)
            tiles[:nd] = sub
            trs = zeros(tb, np.int64)
            trs[:nd] = sub_rs
            tcs = zeros(tb, np.int64)
            tcs[:nd] = sub_cs
            arena_arrays += [tiles, trs, tcs]
            if planning and self.alg.prepare is not None:
                wstore = dc_replace(
                    store, tile_dim=t,
                    tile_block_ids=wsched.dense_block_ids.astype(np.int32),
                    tiles=sub, tile_row_start=sub_rs, tile_col_start=sub_cs,
                )
        elif planning and self.alg.prepare is not None:
            # prepare must not see tiles the wave does not stage
            wstore = dc_replace(
                store, tile_dim=0,
                tile_block_ids=np.zeros(0, np.int32),
                tiles=np.zeros((0, 0, 0), np.float32),
                tile_row_start=np.zeros(0, np.int64),
                tile_col_start=np.zeros(0, np.int64),
            )

        # -- conformal CSR row slices (metadata["csr"] == "slice") -----
        csr = None
        csr_entries = csr_segments = 0
        if self._csr_mode == "slice":
            sl_idx, rbp_r, indptr_r, csr_segs = store.csr_slices(blocks)
            csr_entries = int(sl_idx.size)
            csr_segments = len(csr_segs)
            cb = bucket_size(csr_entries)
            csr = zeros(cb, np.int32)
            csr[:csr_entries] = sl_idx
            arena_arrays.append(csr)
            if planning and self.alg.prepare is not None:
                # prepare sees the wave-local CSR view: positions it
                # computes from row_block_ptr index the staged slice
                wstore = dc_replace(
                    wstore, indices=sl_idx, row_block_ptr=rbp_r,
                    indptr=indptr_r,
                )

        ws = prep_ws = 0
        if planning:
            t0 = time.perf_counter()
            extras = _to_host(
                self.alg.run_prepare(wstore, wsched, self._plan_state)
            )
            self._phase["prepare"] += time.perf_counter() - t0
            # prepare may declare additional device scratch (e.g. TC's
            # bucketed membership-test gather) under the reserved key;
            # it is a budget input, not a kernel input
            ws = prep_ws = int(extras.pop("__workspace_bytes__", 0))

        staged = (
            src.nbytes + dst.nbytes + edge_block.nbytes
            + sparse_mask.nbytes + dense_mask.nbytes
            + tree_array_bytes(extras)
        )
        if csr is not None:
            staged += csr.nbytes
        if tiles is not None:
            staged += tiles.nbytes + trs.nbytes + tcs.nbytes
            if planning:
                from ..kernels.registry import (
                    max_workspace_bytes, workspace_bytes,
                )

                wk = self._workspace_decl
                hints = dict(nd=int(tiles.shape[0]), tile_dim=sched.tile_dim)
                ws += (workspace_bytes(wk, **hints) if wk is not None
                       else max_workspace_bytes(**hints))
        return _WaveSlab(
            wave=wave, src=src, dst=dst, edge_block=edge_block,
            sparse_mask=sparse_mask, dense_mask=dense_mask,
            tiles=tiles, tile_row_start=trs, tile_col_start=tcs,
            csr=csr, extras=extras, run_dense=run_dense,
            staged_bytes=int(staged), workspace_bytes=int(ws),
            edges=ne, segments=len(segments),
            csr_entries=csr_entries, csr_segments=csr_segments,
            arena_arrays=tuple(arena_arrays) if alloc is not None else (),
            prep_ws=int(prep_ws),
        )

    def _assemble_mesh(self, wave: Wave, *, extras: Any = _ABSENT,
                       alloc=None) -> tuple[_WaveSlab, list]:
        """Assemble one wave as padded per-device slabs ``[D, …]``.

        The wave's tasks are LPT-split over the mesh
        (:meth:`~repro.core.scheduler.Schedule.partition_tasks` on the
        wave's restricted sub-schedule), each device's COO/CSR slices
        come from :func:`~repro.core.distributed.make_device_edge_partition`
        (every block of every assigned task, bucket-ladder padded so all
        waves share a few slab shapes), dense tiles are per-device
        subsets zero-padded to the wave's tile bucket (zero tiles are
        neutral for every shipped kernel: no set bits → no contribution),
        and — in the planning pass — ``prepare`` runs once per device
        against a device-local store view (device-rebased CSR maps,
        device tile subset) so host-computed positions index that
        device's staged slice.  Runtime re-assembly (``extras`` given)
        skips prepare and attaches the recipe's cached stacked extras.

        Returns the slab plus the per-device prepare outputs (planning
        only); :meth:`_finalize_mesh_extras` hoists or stacks them.
        """
        store, sched = self.store, self.schedule
        zeros = alloc if alloc is not None else np.zeros
        planning = extras is _ABSENT
        d = self._mesh_devices
        t = sched.tile_dim
        wsched = sched.restrict(wave.task_ids)
        assign = wsched.partition_tasks(d)
        part = make_device_edge_partition(
            store, wsched, assignment=assign, num_devices=d, bucket=True,
            stage_csr=self._csr_mode == "slice", alloc=alloc,
        )
        src, dst = part["src"], part["dst"]
        edge_block, valid = part["edge_block"], part["valid"]
        dense_blocks = np.zeros(store.layout.num_blocks, bool)
        if wsched.dense_block_ids.size:
            dense_blocks[wsched.dense_block_ids] = True
        edense = dense_blocks[edge_block] & valid
        sparse_mask = valid & ~edense
        dense_mask = edense
        arena_arrays = [src, dst, edge_block, valid]
        run_dense = (
            self.alg.kernel_dense is not None
            and bool(wsched.dense_task_mask.any())
        )
        dev_scheds = [
            wsched.restrict(np.nonzero(assign == i)[0]) for i in range(d)
        ]

        # -- per-device dense tiles, padded to the wave tile bucket ----
        tiles = trs = tcs = None
        tb = 0
        empty_sub = (np.zeros((0, t, t), np.float32),
                     np.zeros(0, np.int64), np.zeros(0, np.int64))
        dev_subs = [empty_sub] * d      # reused below for prepare views
        if run_dense:
            nds = [int(ds.dense_block_ids.size) for ds in dev_scheds]
            tb = bucket_size(max(nds), minimum=1)
            tiles = zeros((d, tb, t, t), np.float32)
            trs = zeros((d, tb), np.int64)
            tcs = zeros((d, tb), np.int64)
            arena_arrays += [tiles, trs, tcs]
            for i, ds in enumerate(dev_scheds):
                if ds.dense_block_ids.size:
                    dev_subs[i] = store.tile_subset(ds.dense_block_ids)
                    sub, sub_rs, sub_cs = dev_subs[i]
                    tiles[i, : sub.shape[0]] = sub
                    trs[i, : sub.shape[0]] = sub_rs
                    tcs[i, : sub.shape[0]] = sub_cs

        # -- per-device prepare against device-local store views -------
        ws = prep_ws = 0
        extras_list: list = []
        if planning and self.alg.prepare is not None:
            t_prep = time.perf_counter()
            for i, ds in enumerate(dev_scheds):
                if run_dense:
                    sub, sub_rs, sub_cs = dev_subs[i]
                    wstore = dc_replace(
                        store, tile_dim=t,
                        tile_block_ids=ds.dense_block_ids.astype(np.int32),
                        tiles=sub, tile_row_start=sub_rs,
                        tile_col_start=sub_cs,
                    )
                else:
                    wstore = dc_replace(
                        store, tile_dim=0,
                        tile_block_ids=np.zeros(0, np.int32),
                        tiles=np.zeros((0, 0, 0), np.float32),
                        tile_row_start=np.zeros(0, np.int64),
                        tile_col_start=np.zeros(0, np.int64),
                    )
                if self._csr_mode == "slice":
                    rbp_i, indptr_i = part["csr_maps"][i]
                    sl = part["indices"][i, : part["csr_entries"][i]]
                    wstore = dc_replace(
                        wstore, indices=sl, row_block_ptr=rbp_i,
                        indptr=indptr_i,
                    )
                dev_extras = _to_host(
                    self.alg.run_prepare(wstore, ds, self._plan_state)
                )
                ws = max(ws, int(dev_extras.pop("__workspace_bytes__", 0)))
                extras_list.append(dev_extras)
            prep_ws = ws
            self._phase["prepare"] += time.perf_counter() - t_prep
        elif planning:
            extras_list = [{} for _ in range(d)]

        if planning and run_dense:
            from ..kernels.registry import max_workspace_bytes, workspace_bytes

            wk = self._workspace_decl
            hints = dict(nd=tb, tile_dim=t)   # per-device padded count
            ws += (workspace_bytes(wk, **hints) if wk is not None
                   else max_workspace_bytes(**hints))

        csr = part.get("indices")
        if csr is not None and alloc is not None:
            arena_arrays.append(csr)
        staged = (
            src.nbytes + dst.nbytes + edge_block.nbytes
            + sparse_mask.nbytes + dense_mask.nbytes
        )
        if csr is not None:
            staged += csr.nbytes
        if tiles is not None:
            staged += tiles.nbytes + trs.nbytes + tcs.nbytes
        slab = _WaveSlab(
            wave=wave, src=src, dst=dst, edge_block=edge_block,
            sparse_mask=sparse_mask, dense_mask=dense_mask,
            tiles=tiles, tile_row_start=trs, tile_col_start=tcs,
            csr=csr, extras=None if planning else extras,
            run_dense=run_dense,
            staged_bytes=int(staged), workspace_bytes=int(ws),
            edges=int(sum(part["edges"])),
            segments=int(sum(part["segments"])),
            csr_entries=int(sum(part.get("csr_entries", []))),
            csr_segments=int(sum(part.get("csr_segments", []))),
            arena_arrays=tuple(arena_arrays) if alloc is not None else (),
            prep_ws=int(prep_ws),
        )
        return slab, extras_list

    def _finalize_mesh_extras(self, slab: _WaveSlab,
                              extras_list: list) -> _WaveSlab:
        """Attach a mesh slab's extras (hoisted → none; else stacked
        with a leading device axis) and fix the byte accounting."""
        if (self._hoisted
                and all(_trees_equal(e, self._resident_extras)
                        for e in extras_list)):
            slab.extras = None
        else:
            slab.extras = self._stack_extras(extras_list)
            if (isinstance(slab.extras, dict)
                    and "__workspace_bytes__" in slab.extras):
                # mesh_pack re-declared the prepare scratch for the
                # stacked (per-device padded) shapes — swap it in for
                # the per-device pre-pack declaration
                decl = int(slab.extras.pop("__workspace_bytes__"))
                slab.workspace_bytes += decl - slab.prep_ws
                slab.prep_ws = decl
            slab.staged_bytes += tree_array_bytes(slab.extras)
        slab.per_device_bytes = -(-slab.staged_bytes // self._mesh_devices)
        return slab

    def _stack_extras(self, extras_list: list):
        """Per-device prepare outputs → one tree with a leading device
        axis: the algorithm's ``mesh_pack`` when provided (required for
        structurally device-varying outputs like TC's bucket ladder),
        else a plain stack of structurally identical trees.  Padding is
        never invented here — a neutral pad value is algorithm
        knowledge, so shape mismatches without ``mesh_pack`` raise."""
        alg = self.alg
        if alg.mesh_pack is not None:
            return _to_host(alg.mesh_pack(extras_list))
        flat = [jax.tree_util.tree_flatten(e) for e in extras_list]
        leaves0, treedef0 = flat[0]
        err = (
            f"{alg.name}: per-device prepare outputs differ in "
            f"structure or shape across mesh devices; provide "
            f"BlockAlgorithm.mesh_pack to unify them (padding must be "
            f"neutral for the kernels)"
        )
        if any(td != treedef0 for _, td in flat[1:]):
            raise ValueError(err)
        stacked = []
        for i, leaf0 in enumerate(leaves0):
            col = [leaves for leaves, _ in flat]
            vals = [c[i] for c in col]
            if _is_array_leaf(leaf0):
                if len({np.asarray(v).shape for v in vals}) != 1:
                    raise ValueError(err)
                stacked.append(np.stack([np.asarray(v) for v in vals]))
            else:
                if any(v != leaf0 for v in vals[1:]):
                    raise ValueError(err)
                stacked.append(leaf0)
        return jax.tree_util.tree_unflatten(treedef0, stacked)

    def _decide_hoist(self, slabs: list[_WaveSlab]) -> None:
        """Wave-invariant ``prepare`` outputs (vertex-level attribute
        arrays like PageRank's ``inv_deg``) are staged once as resident
        instead of once per wave per iteration."""
        self._resident_extras: dict = {}
        self._hoisted = False
        if not slabs:
            return
        first = slabs[0].extras
        if all(_trees_equal(s.extras, first) for s in slabs[1:]):
            self._resident_extras = first
            self._hoisted = True
            for s in slabs:
                self._strip_hoisted(s)

    def _strip_hoisted(self, slab: _WaveSlab) -> None:
        """Drop a slab's extras (and their byte cost) when they match
        the hoisted resident tree — also applied to slabs rebuilt by a
        budget split after the hoist decision."""
        if (self._hoisted and slab.extras is not None
                and _trees_equal(slab.extras, self._resident_extras)):
            slab.staged_bytes -= tree_array_bytes(slab.extras)
            slab.extras = None

    def _replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, PartitionSpec())

    def _put_replicated(self, tree: Any) -> Any:
        """device_put array leaves — replicated over the mesh when one
        is set (reads are free: every device holds the vertex-level
        arrays and the state), plain single-device placement otherwise."""
        if self.mesh is None:
            return _put_arrays(tree)
        sh = self._replicated_sharding()
        return jax.tree_util.tree_map(
            lambda leaf: jax.device_put(leaf, sh)
            if _is_array_leaf(leaf) else leaf,
            tree,
        )

    def _build_resident_context(self) -> Context:
        """Vertex-level arrays only — the per-wave slab fields start
        empty and are swapped in by :func:`with_arrays` each wave.

        ``indices`` is the full CSR only in ``"resident"`` csr mode; in
        ``"slice"`` mode each wave swaps in its staged slice, and in
        ``"none"`` mode kernels never read it, so a minimal placeholder
        keeps both traced branches of conditional kernels indexable
        without holding ``m``-proportional memory.  Under a mesh every
        resident array is replicated on all devices (the model's
        "reads are free" half — writes are reduced by the collectives)."""
        store = self.store
        indices = (
            np.asarray(store.indices) if self._csr_mode == "resident"
            else np.zeros(bucket_size(0), np.int32)
        )
        arrays = self._put_replicated(dict(
            src=np.zeros(0, np.int32),
            dst=np.zeros(0, np.int32),
            edge_block=np.zeros(0, np.int32),
            indptr=np.asarray(store.indptr),
            indices=indices,
            degrees=np.asarray(store.degrees),
            row_block_ptr=np.asarray(store.row_block_ptr),
            cuts=np.asarray(store.layout.cuts),
            sparse_edge_mask=np.zeros(0, bool),
            dense_edge_mask=np.zeros(0, bool),
        ))
        return Context(
            extras=self._put_replicated(dict(self._resident_extras)),
            n=store.n,
            m=store.m,
            p=store.p,
            tile_dim=self.schedule.tile_dim,
            backend=self.backend,
            **arrays,
        )

    # -- execute side --------------------------------------------------
    @property
    def num_waves(self) -> int:
        return len(self._slabs)

    @property
    def resident_device_bytes(self) -> int:
        """Device bytes of holding this streamed plan hot, state
        excluded: the cross-wave resident arrays (vertex-level store
        arrays, hoisted extras, the global CSR only in ``"resident"``
        mode) plus the double-buffered worst-case wave — two staged
        slabs (current + prefetch) and the kernel workspace.  The
        serving admission controller prices a resident streamed plan
        with this bound; query state is priced separately per batch."""
        worst = max(
            (s.staged_bytes + s.workspace_bytes for s in self._slabs),
            default=0,
        )
        return int(
            resident_bytes(self.store,
                           include_csr=self._csr_mode == "resident")
            + tree_array_bytes(self._resident_extras)
            + 2 * worst
        )

    def _estimate_shares(self) -> np.ndarray:
        """Each wave's share of the schedule's total weight — the
        estimate the auto-rebalance trigger diverges against."""
        w = np.asarray([
            float(self.schedule.weights[s.wave.task_ids].sum())
            for s in self._slabs
        ])
        tot = w.sum()
        return w / tot if tot > 0 else np.full(w.shape, 1.0 / max(w.size, 1))

    def rebalance(self, wave_compute_s) -> bool:
        """Re-pack the wave queue against observed per-wave compute times.

        The paper's dynamic work queue at wave granularity, evaluated
        automatically after the calibration pass.  Trigger modes (see
        ``rebalance_threshold``):

        * ``"auto"`` (default) — deterministic estimate-vs-observed
          divergence with hysteresis: each wave's observed compute
          share is compared against its estimated share (schedule
          weights); the re-pack fires when the worst ratio reaches
          ``2.0`` and re-arms below ``1.5``.  Measurements below the
          noise floor (mean wave < 10 ms) never fire — dispatch jitter
          at that scale would make the staged-byte accounting
          nondeterministic.
        * float — legacy skew trigger: fire when max/mean of
          ``wave_compute_s`` exceeds the threshold.
        * ``None`` — off.

        On fire, each wave's time is attributed to its tasks
        proportionally to their schedule weights and the whole queue is
        re-packed LPT against those observed times
        (:func:`repro.core.membudget.repack_waves`) — still under the
        byte budget (re-verified per assembled wave).  Later iterations
        run the re-packed waves; per-wave partial folding makes any
        task partition produce the identical combined state, so results
        are unchanged.  Returns True when a re-pack happened.  The
        automatic path fires at most once per plan (a fire disarms the
        trigger, and the post-re-pack recalibration only re-arms it —
        never re-fires); callers feeding fresh timings through this
        method directly can fire again once an evaluation re-armed the
        latch.  The legacy float trigger stays strictly one-shot.
        """
        times = np.asarray(wave_compute_s, dtype=np.float64)
        if times.size != len(self._slabs) or len(self._slabs) < 2:
            return False
        mean = float(times.mean())
        if mean <= 0.0:
            return False
        self._last_skew = float(times.max() / mean)
        thr = self.rebalance_threshold
        if thr is None:
            return False
        if thr == "auto":
            est = self._estimate_shares()
            est_skew = float(est.max() * est.size) if est.size else 1.0
            self._last_divergence = self._last_skew / max(est_skew, 1.0)
            if mean < _REBALANCE_NOISE_FLOOR_S:
                return False            # noise-dominated: stand down
            # the hysteresis latch: a fire disarms the trigger, and the
            # post-re-pack recalibration re-evaluates here — a queue
            # that is still diverged (≥ LO) stays disarmed rather than
            # thrashing through another re-pack; only once an
            # evaluation sees divergence back under LO does the trigger
            # re-arm (relevant to callers feeding rebalance() fresh
            # timings per run — the automatic path fires at most once)
            if self._last_divergence < _REBALANCE_LO:
                self._reb_armed = True
                return False
            if not self._reb_armed or self._last_divergence < _REBALANCE_HI:
                return False            # inside the band, or disarmed
            self._reb_armed = False
        else:
            if self._rebalanced:
                return False            # legacy float trigger: one-shot
            if self._last_skew <= float(thr):
                return False
        task_t = np.zeros(self.schedule.num_tasks, dtype=np.float64)
        for t_w, slab in zip(times, self._slabs):
            ids = slab.wave.task_ids
            wts = self.schedule.weights[ids].astype(np.float64)
            tot = float(wts.sum())
            task_t[ids] = (t_w * wts / tot) if tot > 0 else t_w / ids.size
        if self._host_units:
            # host tasks never ran on the device: give them device-
            # equivalent times at the measured device rate so the
            # re-pack sees the whole schedule, then re-peel to preserve
            # the standing host/device split across the new packing
            dev_w = float(sum(self.schedule.weights[s.wave.task_ids].sum()
                              for s in self._slabs))
            dev_rate = float(times.sum()) / dev_w if dev_w > 0 else 0.0
            for ids in self._host_units:
                task_t[ids] = self.schedule.weights[ids] * dev_rate
        new_waves = repack_waves(self.schedule, self.budget,
                                 self._footprints, task_t,
                                 devices=self._mesh_devices)
        if self._host_units:
            new_waves = peel_host_tasks(
                self.schedule, new_waves, self._host_frac,
                task_times=task_t, host_ratio=self._host_ratio,
                footprints=self._footprints,
            )
        self._apply_waves(new_waves)
        self._edge_free_bufs = None     # stale slab-0 reference
        self._rebalanced = True
        obs.metrics.counter("stream.rebalances").inc()
        obs.instant("rebalance", lane="main", skew=self._last_skew,
                    waves=len(self._slabs))
        return True

    @property
    def compile_count(self) -> int:
        steps = ((self._mesh_step, self._mesh_step_pull)
                 if self._mesh_step is not None
                 else (self._step, self._step_pull))
        return sum(s.traces for s in steps if s is not None)

    def _active_steps(self):
        """The (single-device, mesh) step pair for the direction the
        controller picked for the current iteration."""
        if self._direction_now == "pull":
            return self._step_pull, self._mesh_step_pull
        return self._step, self._mesh_step

    # -- arena recycling ------------------------------------------------
    # ``jax.device_put`` of a numpy array may alias the host memory
    # instead of copying (CPU zero-copy), so a slab's arena buffers are
    # only safe to reuse once the step that read them has COMPLETED —
    # not merely been dispatched.  Each staged slab is parked with a
    # probe leaf of its step's output; ``is_ready()`` (non-blocking)
    # gates the hand-back, and a barrier point (iteration end, where
    # ``_block_tree`` already waits) force-drains the queue.
    def _park_for_recycle(self, slab: _WaveSlab, acc) -> None:
        if not slab.arena_arrays:
            return
        probe = next(
            (leaf for leaf in jax.tree_util.tree_leaves(acc)
             if hasattr(leaf, "is_ready")), None,
        )
        self._arena_deferred.append((probe, slab.arena_arrays))

    def _drain_recycle(self, *, force: bool = False) -> None:
        while self._arena_deferred:
            probe, arrays = self._arena_deferred[0]
            if not (force or probe is None or probe.is_ready()):
                return
            self._arena.give(*arrays)
            self._arena_deferred.pop(0)

    def _put_slab(self, slab: _WaveSlab, *, wave: int = -1):
        """Stage 2: one host→device copy of an assembled wave slab.

        Single device: a dict of device buffers.  Mesh: the ``[D, …]``
        slabs are ``device_put`` with the block-axis sharding (one row
        per device) and the stacked extras travel as a tuple of sharded
        leaves plus their hashable static aux — the pipeline overlaps
        exactly this transfer with the previous wave's compute."""
        if self._faults is not None:
            self._faults.fire("stage.device_put", wave=wave)
        self._bytes_staged += slab.staged_bytes
        t0 = time.perf_counter()
        with obs.span("device_put", lane="device", wave=wave,
                      devices=self._mesh_devices, bytes=slab.staged_bytes):
            arrays = dict(
                src=slab.src, dst=slab.dst, edge_block=slab.edge_block,
                sparse_edge_mask=slab.sparse_mask,
                dense_edge_mask=slab.dense_mask,
            )
            if slab.tiles is not None:
                arrays.update(tiles=slab.tiles,
                              tile_row_start=slab.tile_row_start,
                              tile_col_start=slab.tile_col_start)
            if slab.csr is not None:
                arrays["indices"] = slab.csr
            if self.mesh is None:
                bufs = jax.device_put(arrays)
                if slab.extras is not None:
                    bufs["extras"] = _put_arrays(slab.extras)
            else:
                shard = NamedSharding(self.mesh, PartitionSpec(self.mesh_axis))
                slab_bufs = jax.device_put(arrays, {k: shard for k in arrays})
                if slab.extras is not None:
                    ex_leaves, ex_aux = _split_static(slab.extras)
                    ex_leaves = tuple(
                        jax.device_put(leaf, shard) for leaf in ex_leaves
                    )
                else:
                    ex_leaves, ex_aux = (), None
                bufs = (slab_bufs, ex_leaves, ex_aux)
        self._phase["device_put"] += time.perf_counter() - t0
        return bufs

    def _wave_context(self, bufs: dict) -> Context:
        arrays = {k: v for k, v in bufs.items() if k != "extras"}
        extras = bufs.get("extras")
        if extras is not None:
            return with_arrays(self._resident, extras=extras, **arrays)
        return with_arrays(self._resident, **arrays)

    def _step_wave(self, w: int, bufs, state0, acc, iarr):
        """Stage 3: dispatch one staged wave into the right jitted step."""
        run_dense = self._slabs[w].run_dense
        step, mesh_step = self._active_steps()
        faults = self._faults
        if self.mesh is None:
            with obs.span("compute", lane="device", wave=w,
                          devices=self._mesh_devices):
                out = step(self._wave_context(bufs), state0, acc,
                           iarr, run_dense)
            if faults is not None:
                # firing on the accumulator lets `corrupt` damage the
                # wave's folded partial — recovery must discard it
                out = faults.fire("wave.compute", out, wave=w)
            return out
        with obs.span("compute", lane="device", wave=w,
                      devices=self._mesh_devices):
            slab_bufs, ex_leaves, ex_aux = bufs
            out = mesh_step(self._resident, slab_bufs, ex_leaves,
                            state0, acc, iarr, run_dense, ex_aux)
        if faults is not None:
            out = faults.fire("wave.compute", out, wave=w)
            out = faults.fire("mesh.collective", out, wave=w)
        # per-device collective payload: each combined leaf crosses one
        # all-reduce per wave step (trace-time combined_keys is exact)
        cbytes = sum(
            int(state0[k].nbytes) for k in mesh_step.combined_keys
            if hasattr(state0[k], "nbytes")
        )
        self._collective_bytes += cbytes
        self._phase["collective"] += self._collective_unit_s
        # the real all-reduce is fused inside the shard_map step, so the
        # timeline carries its attributable stand-in cost as a span
        obs.add_span("collective", self._collective_unit_s, lane="device",
                     wave=w, devices=self._mesh_devices, bytes=cbytes)
        return out

    def _measure_collective_unit(self, state0) -> None:
        """Estimate one wave step's collective cost: an isolated, jitted
        all-reduce of the combined state leaves across the mesh, timed
        after a warm-up call.  The real collective is fused inside the
        ``shard_map`` step, so this is the attributable stand-in the
        phase breakdown reports (× wave steps executed)."""
        keys = self._mesh_step.combined_keys if self._mesh_step else ()
        if self.mesh is None or not keys:
            return
        axis = self.mesh_axis
        tree = {k: state0[k] for k in keys if hasattr(state0[k], "nbytes")}
        if not tree:
            return

        def allreduce(t):
            return shard_map(
                lambda x: jax.tree_util.tree_map(combine_fn("add", axis), x),
                mesh=self.mesh,
                in_specs=(PartitionSpec(),), out_specs=PartitionSpec(),
                check_vma=False,
            )(t)

        fn = jax.jit(allreduce)
        _block_tree(fn(tree))           # compile
        t0 = time.perf_counter()
        _block_tree(fn(tree))
        self._collective_unit_s = time.perf_counter() - t0

    def _calibrate(self, state0, acc, iarr, it: int):
        """The synchronous first iteration: trace every distinct wave
        shape (warm-up, result discarded), then time each phase —
        assemble / device_put / compute — per wave, so the overlap and
        phase statistics measure steady state rather than compilation."""
        nw = len(self._slabs)
        warm = state0
        for w in range(nw):
            t0 = time.perf_counter()
            slab = self._assemble_runtime(self._slabs[w], wave=w)
            self._phase["assemble"] += time.perf_counter() - t0
            warm = self._step_wave(w, self._put_slab(slab, wave=w), state0,
                                   warm, iarr)
            self._park_for_recycle(slab, warm)
            # keep the pool at its (depth+1)-slab bound even here: on a
            # caught-up device the previous wave's buffers are already
            # reusable
            self._drain_recycle()
        _block_tree(warm)
        self._drain_recycle(force=True)
        if self.mesh is not None and self._collective_unit_s == 0.0:
            self._measure_collective_unit(state0)
        assemble_s = put_s = compute_s = 0.0
        wave_s: list[float] = []
        for w in range(nw):
            t0 = time.perf_counter()
            slab = self._assemble_runtime(self._slabs[w], wave=w)
            dt = time.perf_counter() - t0
            assemble_s += dt
            put0 = self._phase["device_put"]
            bufs = self._put_slab(slab, wave=w)
            _block_tree(bufs)
            put_s += self._phase["device_put"] - put0
            t0 = time.perf_counter()
            acc = self._step_wave(w, bufs, state0, acc, iarr)
            _block_tree(acc)
            dt = time.perf_counter() - t0
            compute_s += dt
            wave_s.append(dt)
            # the blocking wait above is the safe recycle point
            self._arena.give(*slab.arena_arrays)
        self._phase["assemble"] += assemble_s
        self._phase["compute"] += compute_s
        self._calibration = dict(
            stage_s=assemble_s + put_s, compute_s=compute_s,
            assemble_s=assemble_s, put_s=put_s, wave_compute_s=wave_s,
        )
        # a re-pack only pays off if another iteration will run it — on
        # the final possible iteration it would rebuild (and report)
        # slabs that never execute
        if (self.rebalance_threshold is not None
                and it + 1 < self.alg.max_iterations
                and self.rebalance(wave_s)):
            # the measured stage/compute baseline described the old
            # packing — recalibrate on the next iteration so
            # overlap_efficiency reflects the re-packed waves
            # (at most once: rebalance() is one-shot per plan)
            self._calibration = None
        return acc

    def _run_waves(self, state0, it: int):
        """One iteration's kernel work: the three-stage pipeline over
        every wave, folding partials; calibration (synchronous, timed)
        on the first executed iteration, pipelined overlap afterwards."""
        acc = state0
        nw = len(self._slabs)
        lane = self._host_lane
        if nw == 0 and lane is None:
            return acc, 0.0
        iarr = jnp.int32(it)
        if it < self._edge_free:
            # the algorithm declared these iterations edge-free
            # (kernels read no slab fields and at most the prefix CSR —
            # e.g. Afforest's neighbor-sampling rounds): one
            # representative wave, staged once and cached across the
            # edge-free phase, gives the identical combined result —
            # W-1 redundant full-vertex passes and all repeat stagings
            # saved
            if self._prefix_dev is None and self._prefix_host is not None:
                pptr, pidx = self._prefix_host
                self._prefix_dev = self._put_replicated(
                    dict(indptr=pptr, indices=pidx)
                )
                # replicated puts copy to every mesh device
                self._bytes_staged += (
                    (pptr.nbytes + pidx.nbytes) * self._mesh_devices
                )
            if self.mesh is not None or nw == 0:
                # edge-free kernels consume no per-device data, so the
                # mesh runs them replicated — every device computes the
                # identical full-vertex update from replicated inputs,
                # no collectives needed (a psum here would D-multiply
                # additive leaves); the plain per-wave fold applies.
                # A fully host-peeled plan (no device waves) takes the
                # same resident-context path: the edge-free kernel is
                # full-vertex, so running it once here is the whole
                # iteration and the host lane correctly idles (its
                # units would recompute the identical update, double-
                # applying additive folds)
                ctx = self._resident
                if self._prefix_dev is not None:
                    ctx = with_arrays(ctx, **self._prefix_dev)
                acc = self._active_steps()[0](ctx, state0, acc, iarr, False)
                return acc, 0.0
            if self._edge_free_bufs is None:
                slab = self._assemble_runtime(self._slabs[0], wave=0)
                # the cached device bufs outlive this iteration (and may
                # alias the host arrays), so these buffers never
                # re-enter the arena — they free with the cache
                self._edge_free_bufs = self._put_slab(slab, wave=0)
            ctx = self._wave_context(self._edge_free_bufs)
            if self._prefix_dev is not None:
                # adjacency sampling reads the first-k-neighbors CSR,
                # not the (unbounded) global one
                ctx = with_arrays(ctx, **self._prefix_dev)
            acc = self._active_steps()[0](ctx, state0, acc, iarr,
                                          self._slabs[0].run_dense)
            return acc, 0.0
        self._edge_free_bufs = None     # release once edge work begins
        self._prefix_dev = None
        # host units dispatch FIRST — they run concurrently with the
        # whole device wave loop and are gathered after it, so host
        # work hides behind device compute (both partitions judge the
        # same iteration-start state; per-wave folding is partition-
        # invariant, so the merge order cannot change results)
        host_futs = (lane.submit(state0, it, self._direction_now)
                     if lane is not None else None)
        # stashed so a failure anywhere in the wave loop can wait the
        # in-flight host work out before the iteration retries
        self._host_futs = host_futs
        if nw == 0:
            # fully host-peeled: the host lane IS the iteration
            acc = self._gather_host(host_futs, acc)
            return acc, 0.0
        if self._calibration is None:
            # gather host partials BEFORE the timed calibration pass:
            # the fold order is immaterial (partition-invariant), the
            # host threads stop competing for CPU with the phase
            # timings, and a rebalance fired inside _calibrate may
            # rebuild the host lane — in-flight futures must be done
            acc = self._gather_host(host_futs, acc)
            with obs.span("calibrate", lane="main", it=it, waves=nw):
                acc = self._calibrate(state0, acc, iarr, it)
            with obs.span("split_refresh", lane="main", it=it) as sp:
                sp.set(applied=self._maybe_refresh_split(it))
            return acc, 0.0
        t0 = time.perf_counter()
        put0 = self._phase["device_put"]
        pipe = self._pipe
        if (pipe is None and self.pipeline_depth > 0
                and self._sync_iters_left == 0):
            # persistent worker, created at the first overlapped
            # iteration; later iterations find their first waves
            # already assembled (the epoch below is requested early)
            pipe = self._pipe = _StagePipeline(self, self.pipeline_depth)
            pipe.request(range(nw))
        a0 = pipe.assemble_s if pipe is not None else 0.0
        s0 = pipe.stall_s if pipe is not None else 0.0
        fetched = 0

        def next_slab(i: int) -> _WaveSlab:
            nonlocal fetched
            if pipe is None:
                # synchronous baseline (pipeline_depth=0): assembly
                # runs inline on the critical path
                ta = time.perf_counter()
                s = self._assemble_runtime(self._slabs[i], wave=i)
                self._phase["assemble"] += time.perf_counter() - ta
                return s
            s = pipe.get()
            fetched += 1
            if fetched == nw and it + 1 < self.alg.max_iterations:
                # epoch drained: speculatively queue the next
                # iteration's waves so they assemble during post/host
                # hooks (an early-terminating run reclaims them)
                pipe.request(range(nw))
            return s

        slab = next_slab(0)
        bufs = self._put_slab(slab, wave=0)
        for w in range(nw):
            # fail fast on host-lane failures: a unit that already blew
            # up should abort the iteration now, not after every device
            # wave has streamed only to die at fold time
            if host_futs is not None:
                for f in host_futs:
                    if f.done() and f.exception() is not None:
                        raise f.exception()
            # async dispatch: the step for wave w starts on the device
            # (or the whole mesh, under shard_map)...
            acc = self._step_wave(w, bufs, state0, acc, iarr)
            self._park_for_recycle(slab, acc)
            self._drain_recycle()   # non-blocking: feed the worker's pool
            # ...while wave w+1's (sharded) slab crosses host→device and
            # the background worker assembles wave w+2 into the arena.
            # Rebinding `bufs` releases the previous slab's device
            # buffers as soon as the step consumes them (two slabs max
            # in flight per device).
            if w + 1 < nw:
                slab = next_slab(w + 1)
                bufs = self._put_slab(slab, wave=w + 1)
            else:
                slab, bufs = None, None
        # the host partition ran concurrently with the loop above; any
        # overhang past the last device wave is waited out here (and
        # lands in the wall clock honestly)
        acc = self._gather_host(host_futs, acc)
        _block_tree(acc)
        self._drain_recycle(force=True)
        wall = time.perf_counter() - t0
        put_d = self._phase["device_put"] - put0
        stall = 0.0
        if pipe is not None:
            asm = pipe.assemble_s - a0
            stall = pipe.stall_s - s0
            self._assemble_overlapped_s += asm
            self._stall_s += stall
            self._phase["assemble"] += asm
        self._phase["compute"] += max(wall - put_d - stall, 0.0)
        return acc, wall

    def _gather_host(self, futs, acc):
        """Wait on the host lane's unit futures and fold their partials
        into the running accumulator; publishes the host metrics."""
        if futs is None:
            return acc
        with obs.span("host_wait", lane="main", units=len(futs)):
            results = [f.result() for f in futs]
        self._host_futs = None
        acc, busy_s = self._host_lane.fold(results, acc)
        self._phase["host_compute"] += busy_s
        self._host_seconds += busy_s
        self._last_host_busy_s = busy_s
        ntasks = int(sum(u.size for u in self._host_units))
        self._host_tasks_executed += ntasks
        obs.metrics.counter("stream.host_tasks").inc(ntasks)
        obs.metrics.counter("stream.host_seconds").inc(busy_s)
        return acc

    # -- graceful degradation: the recovery ladder ---------------------
    def _run_waves_resilient(self, state0, it: int):
        """One iteration's wave work under the retry ladder.

        The fast path is a bare call — no bookkeeping when nothing
        fails.  On failure, every in-flight resource is quiesced, the
        failure is classified (oom / worker / host / fault), the
        matching recovery action reshapes the plan, and the *whole
        iteration* re-runs from ``state0`` — the combine contract folds
        partials from iteration-start state, so a retry can never
        double-count, whatever had already folded.  Bounded by
        ``RetryPolicy.max_retries``; an exhausted ladder re-raises."""
        policy = self._policy
        res = self._resil
        attempts = 0
        oom_count = 0
        while True:
            try:
                out = self._run_waves(state0, it)
                if self._sync_iters_left > 0:
                    self._sync_iters_left -= 1
                return out
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                kind = classify(e)
                res.detected += 1
                attempts += 1
                obs.instant("failure", lane="resilience", it=it, kind=kind,
                            attempt=attempts,
                            error=f"{type(e).__name__}: {e}")
                self._abort_inflight()
                if attempts > policy.max_retries:
                    res.record("exhausted", it=it, kind=kind)
                    raise
                if kind == "oom":
                    oom_count += 1
                    if (oom_count >= policy.demote_after
                            and self._host_capable):
                        self._demote_wave(e)
                        res.demotions += 1
                        obs.metrics.counter("stream.fault_demotions").inc()
                        res.record("demote", it=it, oom_count=oom_count)
                    else:
                        self._shrink_repack(oom_count)
                        res.oom_repacks += 1
                        res.record("oom_repack", it=it,
                                   factor=policy.backoff ** oom_count)
                elif kind == "worker":
                    self._worker_deaths += 1
                    res.failovers += 1
                    obs.metrics.counter("stream.fault_failovers").inc()
                    if self._worker_deaths >= policy.failover_after:
                        # the worker keeps dying: synchronous assembly
                        # (pipeline_depth=0 semantics) becomes permanent
                        self.pipeline_depth = 0
                        res.record("failover_permanent", it=it,
                                   deaths=self._worker_deaths)
                    else:
                        self._sync_iters_left = 1
                        res.record("failover_sync", it=it,
                                   deaths=self._worker_deaths)
                elif kind == "host":
                    self._host_failures += 1
                    if self._host_failures >= policy.failover_after:
                        self._disable_host_lane()
                        res.host_failovers += 1
                        res.record("host_disable", it=it,
                                   unit=getattr(e, "unit", None))
                    else:
                        res.record("host_retry", it=it,
                                   unit=getattr(e, "unit", None))
                else:
                    res.record("retry", it=it, kind=kind)
                res.retries += 1
                obs.metrics.counter("stream.fault_retries").inc()
                obs.instant("recovery", lane="resilience", it=it,
                            action=res.actions[-1]["action"])

    def _abort_inflight(self) -> None:
        """Quiesce every in-flight resource so a retry starts clean:
        close the staging pipe (drains a dead or a live worker alike),
        wait out dispatched host futures (their partials are discarded
        — the retry folds from iteration-start state), and force-
        recycle parked arena buffers."""
        if self._pipe is not None:
            try:
                self._pipe.close(self._arena)
            finally:
                self._pipe = None
        futs, self._host_futs = self._host_futs, None
        for f in futs or ():
            try:
                f.result(timeout=60.0)
            except Exception:
                pass            # the retry re-dispatches from scratch
        self._drain_recycle(force=True)

    def _shrink_repack(self, oom_count: int) -> None:
        """Device OOM: re-pack the device waves under an exponentially
        shrunk *effective* capacity (``budget × backoff**oom_count``),
        so each wave stages less at once.  The per-task bound is never
        relaxed — ``_fit_slabs`` still verifies every rebuilt wave
        against the ORIGINAL budget, and ``split_wave`` raises rather
        than admit a single task that cannot fit.  The standing host
        partition is preserved exactly."""
        eff = self.budget.scaled(self._policy.backoff ** oom_count)
        task_t = self.schedule.weights.astype(np.float64)
        packed = repack_waves(self.schedule, eff, self._footprints,
                              task_t, devices=self._mesh_devices)
        host_ids = (np.concatenate(self._host_units) if self._host_units
                    else np.zeros(0, np.int64))
        waves: list[Wave] = []
        for w in packed:
            dev = w.task_ids[~np.isin(w.task_ids, host_ids)]
            if dev.size:
                waves.append(Wave(
                    task_ids=dev,
                    est_bytes=int(self._footprints[dev].sum()),
                ))
        for ids in self._host_units:
            waves.append(Wave(task_ids=np.zeros(0, np.int64), est_bytes=0,
                              host_task_ids=ids))
        self._apply_waves(waves)
        self._calibration = None        # re-time the re-packed queue
        self._edge_free_bufs = None     # stale slab-0 reference

    def _demote_wave(self, exc: BaseException) -> None:
        """Repeated OOM: move the offending wave's tasks to the host
        lane wholesale (they are never staged there, so they stop
        pressing on device memory).  The wave is identified from the
        failure's ``wave=`` context when present, else the largest
        staged slab takes the blame."""
        if not self._slabs:
            return
        w = None
        ctx = getattr(exc, "ctx", None)
        if isinstance(ctx, dict):
            cw = ctx.get("wave")
            if isinstance(cw, int) and 0 <= cw < len(self._slabs):
                w = cw
        if w is None:
            w = max(range(len(self._slabs)),
                    key=lambda i: self._slabs[i].staged_bytes)
        waves: list[Wave] = []
        for i, r in enumerate(self._slabs):
            if i == w:
                waves.append(Wave(
                    task_ids=np.zeros(0, np.int64), est_bytes=0,
                    host_task_ids=np.sort(r.wave.task_ids),
                ))
            else:
                waves.append(Wave(task_ids=r.wave.task_ids,
                                  est_bytes=r.wave.est_bytes))
        for ids in self._host_units:
            waves.append(Wave(task_ids=np.zeros(0, np.int64), est_bytes=0,
                              host_task_ids=ids))
        self._apply_waves(waves)
        self._calibration = None
        self._edge_free_bufs = None
        obs.instant("demote", lane="resilience", wave=w)

    def _disable_host_lane(self) -> None:
        """Repeated host-task failure: run device-only.  Every peeled
        task returns to the device wave queue and the auto split stays
        off for the rest of the plan's life."""
        self._host_capable = False
        self._host_frac = 0.0
        task_t = self.schedule.weights.astype(np.float64)
        waves = repack_waves(self.schedule, self.budget, self._footprints,
                             task_t, devices=self._mesh_devices)
        self._apply_waves(waves)
        self._calibration = None
        self._edge_free_bufs = None
        obs.instant("host_disable", lane="resilience")

    def _maybe_refresh_split(self, it: int) -> bool:
        """Adapt the ``"auto"`` host/device split to measured times.

        Runs right after each calibration pass.  Per-task device-
        equivalent times come from the calibrated wave computes (device
        tasks: wave time attributed by weight share; host tasks: their
        weight at the device rate); the schedule is re-packed LPT
        against them and re-peeled under the hide criterion
        (:func:`repro.core.membudget.peel_host_tasks`).  The new split
        is applied only when it diverged beyond the hysteresis band
        (:func:`repro.core.membudget.hetero_split_diverged`) or flipped
        between zero and nonzero — borderline proposals never thrash
        the wave queue.  The first activation forces one *probe* task
        per multi-task wave so a host rate gets measured at all; once
        measured, the observed host/device ratio replaces the assumed
        ``REPRO_HETERO_HOST_RATIO`` default.  Below the noise floor
        (``REPRO_HETERO_NOISE_FLOOR_S``) the split deterministically
        stays at its current value.  Each application invalidates the
        calibration, so the re-packed device waves are re-timed before
        the next evaluation.  Returns whether a new split was applied."""
        if self._host_frac != "auto" or not self._host_capable:
            return False
        if it + 1 >= self.alg.max_iterations:
            return False            # no later iteration would run it
        cal = self._calibration
        if cal is None or not self._slabs:
            return False            # a rebalance just re-packed
        wave_s = list(cal.get("wave_compute_s", []))
        if not wave_s or float(np.mean(wave_s)) < _hetero_noise_floor_s():
            return False
        dev_w = float(sum(self.schedule.weights[s.wave.task_ids].sum()
                          for s in self._slabs))
        if dev_w <= 0.0:
            return False
        dev_rate = float(sum(wave_s)) / dev_w
        busy_s = getattr(self, "_last_host_busy_s", 0.0)
        if self._host_units and busy_s > 0.0 and dev_rate > 0.0:
            host_w = float(sum(self.schedule.weights[u].sum()
                               for u in self._host_units))
            if host_w > 0.0:
                self._host_ratio = max((busy_s / host_w) / dev_rate, 1e-6)
                self._host_measured = True
        task_t = np.zeros(self.schedule.num_tasks, dtype=np.float64)
        for t_w, slab in zip(wave_s, self._slabs):
            ids = slab.wave.task_ids
            wts = self.schedule.weights[ids].astype(np.float64)
            tot = float(wts.sum())
            task_t[ids] = ((t_w * wts / tot) if tot > 0
                           else t_w / max(ids.size, 1))
        for ids in self._host_units:
            task_t[ids] = self.schedule.weights[ids] * dev_rate
        waves = repack_waves(self.schedule, self.budget,
                             self._footprints, task_t,
                             devices=self._mesh_devices)
        waves = peel_host_tasks(
            self.schedule, waves, "auto", task_times=task_t,
            host_ratio=self._host_ratio, footprints=self._footprints,
            min_tasks=0 if self._host_measured else 1,
        )
        host_ids = [w.host_task_ids for w in waves if w.host_task_ids.size]
        new_split = (self.schedule.weight_share(np.concatenate(host_ids))
                     if host_ids else 0.0)
        cur_split = (self.schedule.weight_share(
            np.concatenate(self._host_units)) if self._host_units else 0.0)
        if not (hetero_split_diverged(cur_split, new_split)
                or (new_split == 0.0) != (cur_split == 0.0)):
            return False
        self._apply_waves(waves)
        self._edge_free_bufs = None     # stale slab-0 reference
        self._hetero_refreshes += 1
        self._calibration = None
        obs.instant("hetero_refresh", lane="main", split=float(new_split),
                    host_tasks=int(sum(u.size for u in self._host_units)),
                    waves=len(self._slabs))
        return True

    def _hetero_stats(self, phase_delta: dict) -> dict:
        """The ``schedule_stats["hetero"]`` block: the resolved
        host/device split, executed host work, and the per-resource
        makespans of this run."""
        host_ids = (np.concatenate(self._host_units) if self._host_units
                    else np.zeros(0, np.int64))
        return dict(
            enabled=bool(self._host_capable
                         and self._host_frac_req is not None),
            host_fraction=self._host_frac_req,
            resolved_split=(float(self.schedule.weight_share(host_ids))
                            if host_ids.size else 0.0),
            host_tasks=int(host_ids.size),
            device_tasks=int(self.schedule.num_tasks - host_ids.size),
            host_units=len(self._host_units),
            host_ratio=float(self._host_ratio),
            host_ratio_measured=bool(self._host_measured),
            refreshes=int(self._hetero_refreshes),
            host_tasks_executed=int(self._host_tasks_executed),
            host_seconds=float(self._host_seconds),
            makespan=dict(
                device_s=float(phase_delta.get("compute", 0.0)),
                host_s=float(phase_delta.get("host_compute", 0.0)),
            ),
        )

    def run(self, store: BlockStore | None = None,
            state: Any | None = None, *,
            _start_it: int = 0, _start_cont: bool = True,
            _ctrl_restore: dict | None = None) -> RunResult:
        """Execute the streamed iteration loop (same contract as
        :meth:`repro.core.engine.Plan.run`).

        The underscored keywords are :meth:`resume`'s continuation
        protocol — iteration counter, loop-continue flag, and the
        direction controller's restored decision history — not public
        surface."""
        if store is not None and store is not self.store:
            raise TypeError(
                "StreamingPlan is bound to the store it was compiled "
                "against; compile a new plan for a different graph"
            )
        alg = self.alg
        if state is None:
            assert alg.init_state is not None, f"{alg.name}: init_state required"
            state = alg.init_state(self.store)
        if self._host_units and self._host_lane is None:
            # close() tore the lane down; rebuild it for this run
            self._host_lane = _HostLane(self, self._host_units)
        ctrl = (DirectionController(alg, self.direction, self.store.n)
                if self._direction_requested else None)
        if ctrl is not None and _ctrl_restore is not None:
            # bit-identical hysteresis across a resume: the controller's
            # latch state and decision history ARE its inputs
            ctrl.current = str(_ctrl_restore["current"])
            ctrl.switches = int(_ctrl_restore["switches"])
            ctrl.decisions = list(_ctrl_restore["decisions"])
            ctrl.densities = list(_ctrl_restore["densities"])
        self._direction_now = "push"
        t0 = time.perf_counter()
        it = int(_start_it)
        cont = bool(_start_cont)
        overlapped_wall = 0.0
        overlapped_iters = 0
        staged_before = self._bytes_staged
        phase_before = dict(self._phase)
        asm_before = self._assemble_overlapped_s
        stall_before = self._stall_s
        try:
            while cont and it < alg.max_iterations:
                with obs.span("iteration", lane="main", it=it, alg=alg.name):
                    if alg.before is not None:
                        state = alg.before(self.host, state, it)
                    if ctrl is not None:
                        # one direction per iteration, across device
                        # waves, mesh shards, AND the host lane — the
                        # bit-identity contract holds per direction,
                        # never across a mix
                        self._direction_now = ctrl.decide(state, it)
                    if self.mesh is not None:
                        # the state is replicated on every mesh device
                        # (writes are reduced by the step's collectives;
                        # host hooks may have injected fresh uncommitted
                        # leaves) — a no-op for leaves already placed
                        state = self._put_replicated(state)
                    state, wall = self._run_waves_resilient(state, it)
                    if wall > 0.0:
                        overlapped_wall += wall
                        overlapped_iters += 1
                    if self._post is not None:
                        state = self._post(self._resident, state,
                                           jnp.int32(it))
                    if alg.after is not None:
                        state, cont = alg.after(self.host, state, it)
                it += 1
                if self._ckpt_every and (it % self._ckpt_every == 0
                                         or not cont):
                    self._save_checkpoint(state, it, cont, ctrl)
        finally:
            if self._pipe is not None:
                self._pipe.close(self._arena)
                self._pipe = None
        state = jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            state,
        )
        dt = time.perf_counter() - t0
        result = alg.finalize(self.store, state) if alg.finalize else state
        phase_delta = {k: self._phase[k] - phase_before[k]
                       for k in self._phase}
        self._publish_metrics(
            iterations=it, seconds=dt,
            staged_delta=self._bytes_staged - staged_before,
            phase_delta=phase_delta,
        )
        stats = dict(
            self.schedule.stats,
            streaming=self._streaming_stats(
                state, overlapped_wall, overlapped_iters,
                staged_delta=self._bytes_staged - staged_before,
                phase_delta=phase_delta,
                asm_delta=self._assemble_overlapped_s - asm_before,
                stall_delta=self._stall_s - stall_before,
            ),
            hetero=self._hetero_stats(phase_delta),
        )
        if ctrl is not None:
            stats["direction"] = ctrl.stats()
        if (self._faults is not None or self._ckpt_every
                or self._resil.fired):
            # emitted only when fault tolerance is configured or a
            # recovery actually fired — existing callers see unchanged
            # schedule_stats keys
            stats["resilience"] = self._resil.snapshot(self._faults)
        return RunResult(
            result=result,
            state=state,
            iterations=it,
            seconds=dt,
            schedule_stats=stats,
        )

    # -- checkpoint / resume -------------------------------------------
    def _save_checkpoint(self, state, it: int, cont: bool, ctrl) -> None:
        """Atomically persist ``(state, it, cont, controller state)``
        through :mod:`repro.checkpoint` after iteration ``it - 1``."""
        from ..checkpoint.runstate import save_runstate

        with obs.span("checkpoint", lane="resilience", it=it):
            save_runstate(self._ckpt_dir, state, it=it, cont=cont,
                          ctrl=ctrl)
        self._resil.checkpoints += 1
        obs.metrics.counter("stream.checkpoints").inc()

    def resume(self, ckpt_dir: str | None = None, *,
               step: int | None = None) -> RunResult:
        """Continue a checkpointed run from its latest (or ``step``'s)
        snapshot; bit-identical to the uninterrupted run for integer/
        boolean attributes (the same guarantee the per-wave combine
        contract gives within a run).  ``RunResult.iterations`` stays
        the absolute iteration count."""
        from ..checkpoint.runstate import load_runstate

        d = ckpt_dir if ckpt_dir is not None else self._ckpt_dir
        if d is None:
            raise ValueError(
                "resume() needs a checkpoint directory: pass ckpt_dir "
                "or compile the plan with checkpoint_dir=...")
        assert self.alg.init_state is not None
        snap = load_runstate(d, self.alg.init_state(self.store),
                             step=step)
        return self.run(state=snap.state, _start_it=snap.it,
                        _start_cont=snap.cont, _ctrl_restore=snap.ctrl)

    # -- deterministic teardown ----------------------------------------
    def close(self) -> None:
        """Tear down every background resource deterministically: the
        staging worker thread (joined, not leaked), the host-lane
        thread pool, and the parked arena buffers.  Idempotent, and
        safe mid-run cleanup after a ``KeyboardInterrupt`` — ``run()``
        rebuilds both lazily, so a closed plan can run again."""
        if self._pipe is not None:
            self._pipe.close(self._arena)
            self._pipe = None
        if self._host_lane is not None:
            self._host_lane.close(wait=True)
            self._host_lane = None
        self._host_futs = None
        self._drain_recycle(force=True)

    def __enter__(self) -> "StreamingPlan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()

    def _publish_metrics(self, *, iterations: int, seconds: float,
                         staged_delta: int, phase_delta: dict) -> None:
        """Publish one run's deltas into the process-wide registry.

        ``schedule_stats`` stays the per-run source of truth; the
        registry accumulates across runs (and plans) so the unified
        run-report and obs-smoke gate read one place."""
        m = obs.metrics
        m.counter("stream.runs").inc()
        m.counter("stream.iterations").inc(iterations)
        m.histogram("stream.run_seconds").observe(seconds)
        for k, v in phase_delta.items():
            m.counter(f"stream.phase_seconds.{k}").inc(max(v, 0.0))
        m.counter("stream.bytes_staged").inc(max(int(staged_delta), 0))
        m.gauge("stream.arena_bytes").set_max(self._arena.bytes)
        m.gauge("stream.waves").set(len(self._slabs))
        m.gauge("stream.mesh_devices").set(self._mesh_devices)
        m.gauge("stream.budget_bytes").set(self.budget.total_bytes)
        if self._slabs:
            m.gauge("stream.budget_high_water_bytes").set_max(
                max(self._budget_load(r) for r in self._slabs))
        if self._faults is not None:
            new = self._faults.injected - self._injected_pub
            if new > 0:
                m.counter("stream.fault_injected").inc(new)
            self._injected_pub = self._faults.injected

    def _streaming_stats(self, state, overlapped_wall: float,
                         overlapped_iters: int, *,
                         staged_delta: int, phase_delta: dict,
                         asm_delta: float, stall_delta: float) -> dict:
        bytes_per_wave = [s.staged_bytes for s in self._slabs]
        calib = self._calibration or dict(stage_s=0.0, compute_s=0.0)
        eff = 0.0
        denom = min(calib["stage_s"], calib["compute_s"])
        if overlapped_iters and denom > 0:
            serial = calib["stage_s"] + calib["compute_s"]
            mean_wall = overlapped_wall / overlapped_iters
            eff = max(0.0, min(1.0, (serial - mean_wall) / denom))
        # how much of the background assembly the pipeline actually hid
        # THIS run: the worker's busy time minus the main loop's queue
        # stalls, over the busy time (1.0 = staging fully off the
        # critical path)
        host_overlap = 0.0
        if asm_delta > 0:
            host_overlap = max(0.0, min(
                1.0, (asm_delta - stall_delta) / asm_delta,
            ))
        prefix_bytes = 0
        if self._prefix_host is not None:
            pptr, pidx = self._prefix_host
            prefix_bytes = pptr.nbytes + pidx.nbytes
        return dict(
            num_waves=len(self._slabs),
            budget_bytes=self.budget.total_bytes,
            bytes_per_wave=bytes_per_wave,
            # mesh composition: how many devices cooperate per wave, the
            # worst single device's staged share (each ≤ budget_bytes —
            # on one device this equals bytes_per_wave), and the
            # per-device payload that crossed the combine collectives
            # (psum/pmin/pmax) over the whole run
            mesh_devices=self._mesh_devices,
            per_device_bytes=[
                s.per_device_bytes if self.mesh is not None
                else s.staged_bytes
                for s in self._slabs
            ],
            collective_bytes=int(self._collective_bytes),
            csr_mode=self._csr_mode,
            # per-wave staged CSR slice bytes (bucket-padded, already
            # included in bytes_per_wave) — all zeros unless "slice"
            csr_bytes_per_wave=[s.csr_bytes for s in self._slabs],
            csr_segments=[s.csr_segments for s in self._slabs],
            # actual H2D traffic this run, counting the calibration
            # warm-up pass and edge-free single-wave iterations honestly
            bytes_staged_total=int(staged_delta),
            resident_bytes=(
                resident_bytes(self.store, state,
                               include_csr=self._csr_mode == "resident")
                + tree_array_bytes(self._resident_extras)
                + tree_array_bytes(state)     # the accumulator copy
            ),
            # first-k-neighbors CSR, device-held only during the
            # edge-free sampling phase (vertex-proportional)
            edge_free_prefix_bytes=int(prefix_bytes),
            edge_buckets=sorted({s.src_bucket for s in self._slabs}),
            coalesced_segments=[s.segments for s in self._slabs],
            overlap_efficiency=eff,
            # three-stage pipeline observability -----------------------
            pipeline_depth=self.pipeline_depth,
            host_stage_overlap=host_overlap,
            # jit traces of the wave step (process-wide when the step is
            # shared); with stage_plan algorithms this is one per
            # distinct bucket shape, independent of the wave count
            trace_count=int(self.compile_count),
            # staging arena: measured pooled-buffer high water vs the
            # footprint model's (depth+1)-slab bound
            arena_bytes=int(self._arena.bytes),
            arena_model_bytes=arena_model_bytes(
                bytes_per_wave, depth=max(self.pipeline_depth, 1),
            ),
            arena_reuses=int(self._arena.reuses),
            # this run's wall clock per phase; the one-time planning
            # pass (per-wave prepare + verification assembly) is broken
            # out so repeated runs stay attributable
            phase_seconds={k: float(v) for k, v in phase_delta.items()},
            planning_phase_seconds={
                k: float(v) for k, v in self._planning_phase.items()
            },
            calibration=dict(calib),
            overlapped_iterations=overlapped_iters,
            rebalanced=self._rebalanced,
            rebalance_mode=(
                "off" if self.rebalance_threshold is None
                else "auto" if self.rebalance_threshold == "auto"
                else "skew"
            ),
            rebalance_skew=self._last_skew,
            rebalance_divergence=self._last_divergence,
        )


def compile_streaming_plan(alg: BlockAlgorithm, store: BlockStore,
                           schedule: Schedule | None = None,
                           **kw) -> StreamingPlan:
    """Explicit spelling of ``compile_plan(..., memory_budget=...)``."""
    return StreamingPlan(alg, store, schedule, **kw)
