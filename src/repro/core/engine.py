"""Compiled execution plans (paper §4.1, Fig. 2) — build/compile vs execute.

Execution flow reproduced from the paper:

  read → partition into blocks → compose block-lists (P_C/P_G) →
  estimate (E) & sort → [ I_B → run kernels on all tasks → I_A ]*

The API separates the two halves of that pipeline:

* :func:`compile_plan` does everything *before* the bracket once —
  schedule composition, dense-tile materialization, algorithm
  ``prepare``, backend resolution — and returns a :class:`Plan` that
  owns the jitted per-iteration step.
* :meth:`Plan.run` executes the bracketed loop: ``I_B`` and ``I_A`` run
  host-side between steps (they may look at global attributes, flip
  direction flags, and decide termination, exactly like the paper);
  the step itself runs the sparse (K_H analog) and dense (K_D analog)
  kernels back-to-back over their own slices of the work.

A ``Plan`` is reusable across runs and across *graphs*: the jitted step
is fetched from a process-wide cache keyed on
``(algorithm name, params, backend)``, and jit's own shape bucketing
makes a second graph with the same padded shapes hit the compiled
executable instead of retracing.  Kernels receive a typed
:class:`~repro.core.context.Context` (device arrays + static scalars);
hooks receive a :class:`~repro.core.context.HostCtx` (store, schedule).
Host objects never cross the jit boundary, so there is no ctx
split/merge machinery anymore.

The legacy :class:`Engine` remains as a thin deprecated shim over
``compile_plan``.
"""
from __future__ import annotations

import time
import warnings
from dataclasses import dataclass
from typing import TYPE_CHECKING, Any

if TYPE_CHECKING:  # pragma: no cover — import cycle guard, typing only
    from .stream import StreamingPlan

import jax
import jax.numpy as jnp

from .. import obs
from .blocks import BlockStore
from .compilecache import alg_cache_key, shared_entry
from .context import Context, HostCtx, build_context, build_host_ctx
from .direction import DirectionController, kernels_for, resolve_direction
from .faults import FaultPlan
from .functors import BlockAlgorithm
from .knobs import env_str as _knob_str
from .resilience import ResilienceStats, RetryPolicy, classify
from .scheduler import Schedule, build_schedule

__all__ = ["Plan", "compile_plan", "RunResult", "Engine", "run",
           "batch_states", "unbatch_state"]


# ----------------------------------------------------------------------
# Batched-state entry point.  Algorithms that declare
# ``metadata["batch"] == "query"`` accept a state pytree with a leading
# query axis (their kernels vmap per-query state over the one shared
# graph context).  These helpers build and take apart that axis; both
# Plan.run(state=...) and StreamingPlan.run(state=...) execute the
# batched state unchanged.  The batch axis is orthogonal to the mesh
# block axis: under ``mesh=`` the batched state is replicated like any
# other state and per-wave partials fold leaf-wise, so batch × mesh
# composes without new machinery.
def batch_states(states, *, pad_to: int | None = None):
    """Stack per-query state pytrees into one batched state.

    Every state must share one tree structure and per-leaf shapes
    (compatible queries).  With ``pad_to`` (a bucket from
    :func:`repro.core.membudget.bucket_size`), the batch is padded by
    replicating the last query's state so the compiled step traces once
    per bucket; padded rows compute real results that callers discard.
    """
    states = list(states)
    if not states:
        raise ValueError("batch_states needs at least one state")
    if pad_to is not None:
        if pad_to < len(states):
            raise ValueError(
                f"pad_to={pad_to} is smaller than the batch of {len(states)}")
        states = states + [states[-1]] * (pad_to - len(states))
    return jax.tree.map(
        lambda *leaves: jnp.stack([jnp.asarray(x) for x in leaves]), *states)


def unbatch_state(state, index: int):
    """Slice query ``index``'s row out of a batched state pytree."""
    return jax.tree.map(lambda leaf: leaf[index], state)


@dataclass
class RunResult:
    result: Any
    state: Any
    iterations: int
    seconds: float
    schedule_stats: dict


# ----------------------------------------------------------------------
# Shared compiled steps: one entry per (alg identity, backend).  jit's
# internal cache buckets by context/state shapes below this, so two
# same-shape graphs — or two Plans for the same algorithm — share one
# compilation.
class _CompiledStep:
    def __init__(self, alg: BlockAlgorithm, direction: str = "push") -> None:
        self.traces = 0
        kernel_sparse, kernel_dense = kernels_for(alg, direction)

        def step(ctx: Context, state, it, run_dense: bool):
            self.traces += 1  # trace-time side effect == compile counter
            obs.metrics.counter("compile.traces").inc()
            # named scopes reach the device trace as each operation's
            # op_name; they change no operation
            if kernel_sparse is not None:
                with jax.named_scope("sparse"):
                    state = kernel_sparse(ctx, state, it)
            if kernel_dense is not None and run_dense:
                with jax.named_scope("dense"):
                    state = kernel_dense(ctx, state, it)
            if alg.post is not None:
                with jax.named_scope("post"):
                    state = alg.post(ctx, state, it)
            return state

        self._jit = jax.jit(step, static_argnums=(3,))

    def __call__(self, ctx: Context, state, it, run_dense: bool):
        return self._jit(ctx, state, it, run_dense)


_STEP_CACHE: dict[tuple, _CompiledStep] = {}

# The keying/share-gating logic lives in repro.core.compilecache so the
# in-core and streaming executors cannot diverge; the old private names
# stay importable for downstream code.
_alg_cache_key = alg_cache_key
_shared_entry = shared_entry


def _compiled_step_for(alg: BlockAlgorithm, backend: str, *,
                       share: bool = True,
                       direction: str = "push") -> _CompiledStep:
    return shared_entry(_STEP_CACHE, alg_cache_key(alg, backend, direction),
                        lambda: _CompiledStep(alg, direction), share=share)


# ----------------------------------------------------------------------
@dataclass
class _Binding:
    """Per-store compiled inputs: the typed contexts + static routing."""

    store: BlockStore
    schedule: Schedule
    context: Context
    host: HostCtx
    run_dense: bool


class Plan:
    """A compiled, reusable execution plan for one algorithm.

    Produced by :func:`compile_plan`.  ``plan.run()`` executes on the
    store it was compiled against; ``plan.run(other_store)`` binds and
    runs another graph — reusing the jitted step outright when the
    padded shapes match (no recompilation).
    """

    def __init__(self, alg: BlockAlgorithm, store: BlockStore,
                 schedule: Schedule | None, *, backend: str,
                 num_devices: int, mode: str, tile_dim: int,
                 dense_frac: float, dense_density: float,
                 share: bool = True, direction: str | None = None,
                 faults: "str | FaultPlan | None" = None,
                 checkpoint_every: int | None = None,
                 checkpoint_dir: str | None = None,
                 retry_policy: RetryPolicy | None = None) -> None:
        from ..kernels.registry import resolve_backend

        # same fault-tolerance contract as StreamingPlan: the in-core
        # step is the "wave.compute" seam, iterations are idempotent
        # (the step maps iteration-start state to the next state), and
        # checkpoints land on iteration boundaries
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
        self._ckpt_every = (int(checkpoint_every) if checkpoint_every
                            else (1 if checkpoint_dir else 0))
        self._ckpt_dir = checkpoint_dir
        self._resil = ResilienceStats()
        self._injected_pub = 0
        self.alg = alg
        self.backend = resolve_backend(backend)
        self.direction = resolve_direction(alg, direction)
        # None keeps the pre-direction contract: plain push, no
        # controller, no schedule_stats["direction"] block
        self._direction_requested = direction is not None
        self._sched_kw = dict(
            num_devices=num_devices, mode=mode, tile_dim=tile_dim,
            dense_frac=dense_frac, dense_density=dense_density,
        )
        self._steps = {
            "push": _compiled_step_for(alg, self.backend, share=share),
        }
        if self.direction in ("pull", "auto"):
            self._steps["pull"] = _compiled_step_for(
                alg, self.backend, share=share, direction="pull")
        self._step = self._steps["push"]
        self._bindings: dict[int, _Binding] = {}
        self._default = self.bind(store, schedule)

    # Non-default bindings are memoized with a small FIFO cap so a sweep
    # over many graphs doesn't pin every store's device arrays forever.
    _MAX_BINDINGS = 8

    # -- build/compile side -------------------------------------------
    def bind(self, store: BlockStore,
             schedule: Schedule | None = None) -> _Binding:
        """Build (and memoize) the typed contexts for ``store``."""
        cached = self._bindings.get(id(store))
        if (cached is not None and cached.store is store
                and (schedule is None or cached.schedule is schedule)):
            return cached
        sched = schedule or build_schedule(self.alg, store, **self._sched_kw)
        # stage_plan exists to keep per-wave prepare outputs
        # shape-stable across a streamed plan's waves; the in-core Plan
        # has exactly one context and one trace, so it passes None and
        # prepare keeps its unpadded single-shot form
        extras = self.alg.run_prepare(store, sched, None)
        # reserved declaration for the streaming executor's footprint
        # model — not a kernel input (see stream._assemble)
        extras.pop("__workspace_bytes__", None)
        binding = _Binding(
            store=store,
            schedule=sched,
            context=build_context(store, sched, backend=self.backend,
                                  extras=extras),
            host=build_host_ctx(store, sched, backend=self.backend),
            run_dense=(
                self.alg.kernel_dense is not None
                and bool(sched.dense_task_mask.any())
            ),
        )
        self._bindings.pop(id(store), None)
        self._bindings[id(store)] = binding
        if len(self._bindings) > self._MAX_BINDINGS:
            default = getattr(self, "_default", None)
            for key in list(self._bindings):
                if len(self._bindings) <= self._MAX_BINDINGS:
                    break
                if self._bindings[key] is not default:
                    del self._bindings[key]
        return binding

    @property
    def store(self) -> BlockStore:
        return self._default.store

    @property
    def schedule(self) -> Schedule:
        """The schedule is a first-class artifact — inspect it freely."""
        return self._default.schedule

    @property
    def context(self) -> Context:
        return self._default.context

    @property
    def host(self) -> HostCtx:
        return self._default.host

    @property
    def compile_count(self) -> int:
        """Number of times the step has been traced (≈ jit compilations).

        Shared across every Plan using the same cached step; the reuse
        tests assert this stays at 1 across same-shape graphs.  With a
        direction-optimizing plan this sums the push and pull steps —
        each variant traces once.
        """
        return sum(step.traces for step in self._steps.values())

    @property
    def resident_device_bytes(self) -> int:
        """Device bytes of holding this plan hot (default binding's
        context: graph arrays + prepared extras), state excluded — the
        serving admission controller's price for a resident in-core
        plan.  Query state is priced separately per batch."""
        from .membudget import tree_array_bytes

        return tree_array_bytes(self._default.context)

    # -- execute side --------------------------------------------------
    def run(self, store: BlockStore | None = None,
            state: Any | None = None, *,
            _start_it: int = 0, _start_cont: bool = True,
            _ctrl_restore: dict | None = None) -> RunResult:
        """Execute the iteration loop; see module docstring for the contract.

        With ``alg.after`` present, iterate while it returns True (up to
        ``max_iterations``); without it, run exactly ``max_iterations``
        steps.  The underscored keywords are :meth:`resume`'s
        continuation protocol, not public surface.
        """
        alg = self.alg
        b = self._default if store is None else self.bind(store)
        if state is None:
            assert alg.init_state is not None, f"{alg.name}: init_state required"
            state = alg.init_state(b.store)
        ctrl = (DirectionController(alg, self.direction, b.store.n)
                if self._direction_requested else None)
        if ctrl is not None and _ctrl_restore is not None:
            ctrl.current = str(_ctrl_restore["current"])
            ctrl.switches = int(_ctrl_restore["switches"])
            ctrl.decisions = list(_ctrl_restore["decisions"])
            ctrl.densities = list(_ctrl_restore["densities"])
        t0 = time.perf_counter()
        it = int(_start_it)
        cont = bool(_start_cont)
        while cont and it < alg.max_iterations:
            with obs.span("iteration", lane="main", it=it, alg=alg.name):
                if alg.before is not None:
                    state = alg.before(b.host, state, it)
                step = (self._steps[ctrl.decide(state, it)]
                        if ctrl is not None else self._step)
                state = self._step_resilient(step, b, state, it)
                if alg.after is not None:
                    state, cont = alg.after(b.host, state, it)
            it += 1
            if self._ckpt_every and (it % self._ckpt_every == 0
                                     or not cont):
                self._save_checkpoint(state, it, cont, ctrl)
        state = jax.tree.map(
            lambda x: x.block_until_ready() if hasattr(x, "block_until_ready") else x,
            state,
        )
        dt = time.perf_counter() - t0
        m = obs.metrics
        m.counter("engine.runs").inc()
        m.counter("engine.iterations").inc(it)
        m.histogram("engine.run_seconds").observe(dt)
        if self._faults is not None:
            new = self._faults.injected - self._injected_pub
            if new > 0:
                m.counter("stream.fault_injected").inc(new)
                self._injected_pub = self._faults.injected
        result = alg.finalize(b.store, state) if alg.finalize else state
        stats = b.schedule.stats
        if ctrl is not None:
            stats = dict(stats, direction=ctrl.stats())
        # only runs that opted into fault tolerance (or actually
        # recovered) grow the stats dict — existing callers see
        # unchanged keys
        if (self._faults is not None or self._ckpt_every
                or self._resil.fired):
            stats = dict(stats, resilience=self._resil.snapshot(self._faults))
        return RunResult(
            result=result,
            state=state,
            iterations=it,
            seconds=dt,
            schedule_stats=stats,
        )

    def _step_resilient(self, step, b: _Binding, state, it: int):
        """One device step with the fault seam + bounded retry.

        The compiled step maps iteration-start state to the next state
        without mutating its input, so a failed attempt is discarded
        wholesale and retried from the same ``state`` — recovery is
        idempotent by construction.  ``KeyboardInterrupt``/``SystemExit``
        always propagate.
        """
        faults, policy, res = self._faults, self._policy, self._resil
        attempts = 0
        while True:
            try:
                with obs.span("compute", lane="device", it=it):
                    out = step(b.context, state, jnp.int32(it), b.run_dense)
                    if faults is not None:
                        out = faults.fire("wave.compute", out, it=it)
                return out
            except (KeyboardInterrupt, SystemExit):
                raise
            except Exception as e:
                kind = classify(e)
                res.detected += 1
                attempts += 1
                obs.instant("failure", lane="resilience", it=it,
                            kind=kind, error=type(e).__name__)
                if attempts > policy.max_retries:
                    res.record("exhausted", it=it, kind=kind,
                               attempts=attempts)
                    raise
                res.record("retry", it=it, kind=kind, attempts=attempts)
                res.retries += 1
                obs.metrics.counter("stream.fault_retries").inc()
                obs.instant("recovery", lane="resilience", it=it,
                            action="retry")

    def _save_checkpoint(self, state, it: int, cont: bool, ctrl) -> None:
        from ..checkpoint.runstate import save_runstate

        with obs.span("checkpoint", lane="resilience", it=it):
            save_runstate(self._ckpt_dir, state, it=it, cont=cont,
                          ctrl=ctrl)
        self._resil.checkpoints += 1
        obs.metrics.counter("stream.checkpoints").inc()

    def resume(self, ckpt_dir: str | None = None, *,
               step: int | None = None) -> RunResult:
        """Continue from the newest (or ``step``'s) snapshot in
        ``ckpt_dir`` (defaults to this plan's ``checkpoint_dir``).

        Bit-identical for integer/boolean attributes: the loop restarts
        at the stored iteration boundary with the stored continue flag
        and direction-controller history.
        """
        from ..checkpoint.runstate import load_runstate

        d = ckpt_dir if ckpt_dir is not None else self._ckpt_dir
        if d is None:
            raise ValueError(
                "resume() needs a checkpoint directory: pass ckpt_dir or "
                "build the plan with checkpoint_dir=...")
        assert self.alg.init_state is not None
        snap = load_runstate(d, self.alg.init_state(self.store), step=step)
        return self.run(state=snap.state, _start_it=snap.it,
                        _start_cont=snap.cont, _ctrl_restore=snap.ctrl)


def compile_plan(
    alg: BlockAlgorithm,
    store: BlockStore,
    schedule: Schedule | None = None,
    *,
    backend: str | None = None,
    num_devices: int = 1,
    mode: str = "hybrid",
    tile_dim: int = 512,
    dense_frac: float = 0.5,
    dense_density: float = 0.005,
    share: bool = True,
    use_pallas: bool = False,
    direction: str | None = None,
    memory_budget: "int | str | None" = None,
    rebalance_threshold: "float | str | None" = "auto",
    pipeline_depth: int | None = None,
    mesh=None,
    host_fraction: "float | str | None" = "auto",
    faults: "str | None" = None,
    checkpoint_every: int | None = None,
    checkpoint_dir: str | None = None,
    retry_policy=None,
) -> "Plan | StreamingPlan":
    """Build + compile: schedule, prepare, typed contexts, jitted step.

    ``backend`` selects kernel implementations per the registry
    (``"reference" | "xla" | "pallas"``, default ``"xla"``);
    ``"pallas"`` raises when no Pallas runtime is available.
    ``use_pallas=True`` is the deprecated spelling of
    ``backend="pallas"`` (an explicit ``backend`` wins).  ``share=False``
    opts out of the process-wide compiled-step cache (use it for ad-hoc
    algorithms that reuse a registered name with different kernels).

    ``direction`` selects the kernel direction for algorithms that
    declare the ``metadata["direction"]`` capability
    (:mod:`repro.core.direction`): ``"push"`` / ``"pull"`` pin one
    variant, ``"auto"`` decides per iteration from the frontier density
    behind a hysteresis band — one direction per iteration across
    waves, mesh shards, and the host lane, so results stay
    bit-identical to fixed push for integer/bool attributes.  Each
    variant's step traces once (the compiled-step cache keys the
    direction) and every decision is recorded in
    ``schedule_stats["direction"]``.  ``None`` (the default) keeps the
    plain push step with no controller.

    ``memory_budget`` (bytes, or a string like ``"64MB"``) switches to
    the out-of-core streaming executor: the result is a
    :class:`~repro.core.stream.StreamingPlan` whose ``run`` drives a
    three-stage host→device pipeline over budget-sized waves of tasks —
    background slab assembly into a staging arena, double-buffered
    ``device_put``, compute — instead of shipping the whole edge set
    to the device up front.  The schedule is then built budget-aware
    (dense cut-offs sized so waves fit).  Same ``run()`` contract;
    ``schedule_stats["streaming"]`` reports waves, bytes staged per
    wave (CSR broken out), per-phase wall clock, trace counts, arena
    bytes, and the measured overlap efficiencies.
    ``rebalance_threshold`` (streaming only) controls tail-wave
    rebalancing, default **on** (``"auto"``): after the calibration
    pass, the wave queue is re-packed against observed task times when
    the estimate-vs-observed divergence trigger fires (hysteresis band
    2.0/1.5, deterministic noise floor).  A float keeps the legacy
    compute-skew trigger; ``None`` switches rebalancing off.
    ``pipeline_depth`` (streaming only) bounds how many waves the
    background staging worker assembles ahead (default 2; ``0`` runs
    staging synchronously in the wave loop — the benchmark baseline).

    ``host_fraction`` (streaming only) co-schedules the host CPU as a
    compute resource: each wave is split into a device partition and a
    host partition; the host tasks run the algorithm's sparse kernel
    eagerly on the CPU backend in a thread pool, overlapped with the
    device wave, and their partials fold through the same
    ``metadata["combine"]`` contract as mesh partials — bit-identical
    to a device-only run for integer/bool attributes.  ``"auto"`` (the
    default) starts device-only and peels the light/sparse tail of each
    wave only once calibration shows the host can hide behind the
    device; a float in ``[0, 1]`` pins the host share of per-wave work
    (``0.0`` disables, ``1.0`` runs everything on the host); ``None``
    disables the host lane entirely.  Host tasks are never staged, so
    every staged device slab stays within ``memory_budget``.
    ``schedule_stats["hetero"]`` reports the resolved split, host/device
    task counts, measured host/device throughput ratio, and per-resource
    makespans.  See ``docs/heterogeneous.md``.

    ``mesh`` (streaming only; a 1-D ``jax.sharding.Mesh``) composes the
    waves with the distributed execution model of
    :mod:`repro.core.distributed`: ``memory_budget`` becomes *per
    device*, each wave's tasks are LPT-split over the mesh into padded
    per-device COO/CSR/tile slabs, the double-buffered stager
    ``device_put``\\ s wave k+1's sharded slabs while the mesh computes
    wave k under ``shard_map``, and per-wave partials fold with the
    algorithm's ``metadata["combine"]`` collectives (psum/pmin/pmax) —
    bit-identical to in-core for integer/bool attributes.  Requires the
    algorithm to declare ``metadata["mesh"] == "shard"``; see
    ``docs/distributed.md``.

    ``faults`` / ``checkpoint_every`` / ``checkpoint_dir`` /
    ``retry_policy`` opt into the fault-tolerant runtime (both
    executors): ``faults`` is a seeded injection spec
    (``"site:action[:trigger]"``, ``;``-joined — see
    :mod:`repro.core.faults` and ``docs/resilience.md``; defaults to the
    ``REPRO_FAULTS`` env knob), ``checkpoint_dir`` persists atomic
    per-iteration run snapshots every ``checkpoint_every`` iterations
    (default every iteration) which ``plan.resume()`` continues
    bit-identically for integer/bool attributes, and ``retry_policy``
    (a :class:`repro.core.resilience.RetryPolicy`) bounds the
    retry/backoff/demotion recovery ladder.  All disabled by default
    with zero overhead; recoveries surface in
    ``schedule_stats["resilience"]``.
    """
    if backend is None:
        backend = "pallas" if use_pallas else "xla"
    if (rebalance_threshold not in (None, "auto")
            and memory_budget is None):
        raise ValueError(
            "rebalance_threshold only applies to the streaming executor; "
            "pass memory_budget=... as well (the in-core Plan has no waves "
            "to rebalance)"
        )
    if pipeline_depth is not None and memory_budget is None:
        raise ValueError(
            "pipeline_depth only applies to the streaming executor; "
            "pass memory_budget=... as well (the in-core Plan stages no "
            "waves)"
        )
    if host_fraction not in (None, "auto") and memory_budget is None:
        raise ValueError(
            "host_fraction only applies to the streaming executor; "
            "pass memory_budget=... as well (the in-core Plan has no "
            "waves to split across host and device)"
        )
    if mesh is not None and memory_budget is None:
        raise ValueError(
            "mesh= composes the *streaming* executor with a device mesh; "
            "pass memory_budget=... as well (for whole-graph resident mesh "
            "execution use repro.core.distributed.DistributedEngine)"
        )
    if memory_budget is not None:
        from .membudget import PIPELINE_DEPTH
        from .stream import StreamingPlan

        return StreamingPlan(
            alg, store, schedule,
            memory_budget=memory_budget,
            backend=backend, num_devices=num_devices, mode=mode,
            tile_dim=tile_dim, dense_frac=dense_frac,
            dense_density=dense_density, share=share,
            direction=direction,
            rebalance_threshold=rebalance_threshold,
            pipeline_depth=(PIPELINE_DEPTH if pipeline_depth is None
                            else pipeline_depth),
            mesh=mesh,
            host_fraction=host_fraction,
            faults=faults, checkpoint_every=checkpoint_every,
            checkpoint_dir=checkpoint_dir, retry_policy=retry_policy,
        )
    return Plan(
        alg, store, schedule,
        backend=backend, num_devices=num_devices, mode=mode,
        tile_dim=tile_dim, dense_frac=dense_frac,
        dense_density=dense_density, share=share, direction=direction,
        faults=faults, checkpoint_every=checkpoint_every,
        checkpoint_dir=checkpoint_dir, retry_policy=retry_policy,
    )


# ----------------------------------------------------------------------
# Legacy shim
class Engine:
    """Deprecated: use :func:`compile_plan` → :meth:`Plan.run`.

    Kwarg mapping: ``use_pallas=True`` → ``backend="pallas"`` (else
    ``"xla"``); everything else passes through unchanged.
    """

    def __init__(
        self,
        alg: BlockAlgorithm,
        store: BlockStore,
        schedule: Schedule | None = None,
        *,
        num_devices: int = 1,
        mode: str = "hybrid",
        use_pallas: bool = False,
        backend: str | None = None,
        tile_dim: int = 512,
        dense_frac: float = 0.5,
        dense_density: float = 0.005,
    ) -> None:
        warnings.warn(
            "Engine is deprecated; use compile_plan(alg, store, ...).run()",
            DeprecationWarning,
            stacklevel=2,
        )
        self.plan = compile_plan(
            alg, store, schedule,
            backend=backend, use_pallas=use_pallas,
            num_devices=num_devices, mode=mode, tile_dim=tile_dim,
            dense_frac=dense_frac, dense_density=dense_density,
        )
        self.alg = alg
        self.store = store

    @property
    def schedule(self) -> Schedule:
        return self.plan.schedule

    def run(self, state: Any | None = None) -> RunResult:
        return self.plan.run(state=state)


def run(alg: BlockAlgorithm, store: BlockStore, **kw) -> RunResult:
    """One-shot convenience: compile a plan and execute it."""
    return compile_plan(alg, store, **kw).run()
