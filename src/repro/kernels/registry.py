"""Kernel-backend registry: named kernels × {reference, xla, pallas}.

Replaces the boolean ``use_pallas`` flag.  Each hot-spot kernel is
registered once per backend it supports; dispatch happens at trace time
(the backend name is static aux data on the :class:`~repro.core.context.Context`),
so the jitted step bakes in exactly one implementation.

Backends
--------
``reference``
    The pure-jnp oracle from :mod:`repro.kernels.ref` — the mathematical
    definition, used by tests and as the last-resort fallback.
``xla``
    The vectorized einsum/gather formulation that XLA fuses well — the
    default on any backend.
``pallas``
    The hand-tiled Pallas kernels (native on TPU, ``interpret=True``
    elsewhere).

``backend="pallas"`` never degrades quietly: when no Pallas runtime is
importable :func:`resolve_backend` raises.  The one fallback it keeps is
per kernel: a kernel with no Pallas registration (the sparse paths have
none) runs its ``xla`` registration, and never the ``reference`` one.
``backend="xla"`` falls back to ``reference`` for a kernel with no
``xla`` registration.  Dense and sparse paths dispatch independently —
registration is per kernel name, not global.
"""
from __future__ import annotations

from typing import Callable

__all__ = [
    "BACKENDS", "register_kernel", "get_kernel", "resolve_backend",
    "pallas_available", "registered", "register_workspace", "workspace_bytes",
    "max_workspace_bytes", "registered_workspaces",
    "register_host_executable", "host_executable",
    "registered_host_executable",
]

BACKENDS = ("reference", "xla", "pallas")
_FALLBACK = {"pallas": "xla", "xla": "reference"}

_REGISTRY: dict[tuple[str, str], Callable] = {}

# Test hook: force the availability probe (None = auto-detect).
_FORCE_PALLAS_AVAILABLE: bool | None = None


def pallas_available() -> bool:
    """Whether a Pallas lowering path exists in this runtime."""
    if _FORCE_PALLAS_AVAILABLE is not None:
        return _FORCE_PALLAS_AVAILABLE
    try:
        import jax.experimental.pallas  # noqa: F401

        from . import ops  # noqa: F401
    except Exception:  # pragma: no cover — container without pallas
        return False
    return True


def resolve_backend(backend: str) -> str:
    """Validate ``backend``: unknown names raise, and so does ``pallas``
    when no Pallas runtime is importable (never a quiet ``xla``)."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}"
        )
    if backend == "pallas" and not pallas_available():
        raise RuntimeError(
            "backend='pallas' requested but no Pallas runtime is importable"
        )
    return backend


def register_kernel(name: str, backend: str) -> Callable[[Callable], Callable]:
    """Decorator: register ``fn`` as the ``backend`` implementation of ``name``."""
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")

    def deco(fn: Callable) -> Callable:
        _REGISTRY[(name, backend)] = fn
        return fn

    return deco


def get_kernel(name: str, backend: str) -> Callable:
    """Resolve ``name`` for ``backend``: its own registration, else one
    step down (``pallas → xla``, ``xla → reference``) for a kernel that
    has no registration for ``backend``; see the module docstring."""
    b = resolve_backend(backend)
    for cand in (b, _FALLBACK.get(b)):
        fn = _REGISTRY.get((name, cand))
        if fn is not None:
            return fn
    raise KeyError(
        f"kernel {name!r} has no registration reachable from "
        f"backend {backend!r}"
    )


def registered(name: str) -> dict[str, Callable]:
    """All registered implementations of ``name``, keyed by backend."""
    return {b: fn for (n, b), fn in _REGISTRY.items() if n == name}


# ----------------------------------------------------------------------
# Host-executable capability: kernel names certified safe to run
# eagerly on the host CPU (pure jnp reference path, no Pallas/XLA
# custom calls, bit-identical int/bool results).  The heterogeneous
# streaming executor consults this before peeling an algorithm's tasks
# to the host lane — an algorithm that names an uncertified kernel in
# metadata["host_kernels"] stays device-only.
_HOST_OK: set[str] = set()


def register_host_executable(name: str) -> None:
    """Certify kernel ``name`` as host-executable (see module docs)."""
    _HOST_OK.add(str(name))


def host_executable(name: str) -> bool:
    """Whether ``name`` is certified to run on the host CPU lane."""
    return str(name) in _HOST_OK


def registered_host_executable() -> tuple[str, ...]:
    """Sorted names currently certified host-executable."""
    return tuple(sorted(_HOST_OK))


# ----------------------------------------------------------------------
# Per-kernel workspace estimators: the memory-budget footprint model
# (repro.core.membudget) asks the registry how much device scratch a
# kernel needs on top of its staged inputs — e.g. spmv's gathered
# xs/ys slices.  Estimators take keyword shape hints and return bytes;
# unknown kernels price as 0 so the model degrades gracefully.
#
# Every estimator also understands a ``devices`` hint (default 1): the
# mesh-cooperative streaming executor spreads one wave's work over a
# device mesh, so scratch that scales with item/tile counts is priced
# per device as ceil(count / devices) — the worst single device after
# an LPT split, which is what a per-device memory budget must bound.
_WORKSPACE: dict[str, Callable[..., int]] = {}


def _per_device(count: int, devices: int) -> int:
    """Worst-device share of ``count`` items split over ``devices``."""
    d = max(int(devices), 1)
    return -(-int(count) // d)


def register_workspace(name: str) -> Callable[[Callable], Callable]:
    """Decorator: register a workspace-bytes estimator for kernel ``name``."""

    def deco(fn: Callable[..., int]) -> Callable:
        _WORKSPACE[name] = fn
        return fn

    return deco


def registered_workspaces() -> tuple[str, ...]:
    """Names with a workspace estimator (declaration-typo guard)."""
    return tuple(_WORKSPACE)


def workspace_bytes(name, **shape_hints) -> int:
    """Estimated scratch bytes for ``name`` given shape hints (0 if none).

    ``name`` may be a sequence of kernel names, priced as the *maximum*
    over them — how a direction-optimizing plan charges for whichever
    of its push/pull dense variants is costlier, so a mid-stream switch
    never exceeds a budget the planner verified."""
    if not isinstance(name, str):
        return max((workspace_bytes(nm, **shape_hints) for nm in name),
                   default=0)
    fn = _WORKSPACE.get(name)
    return int(fn(**shape_hints)) if fn is not None else 0


def max_workspace_bytes(**shape_hints) -> int:
    """Worst case over every registered estimator — what the footprint
    model charges when an algorithm does not name its dense kernel."""
    return max(
        (int(fn(**shape_hints)) for fn in _WORKSPACE.values()), default=0
    )


# ``nd`` means "tiles staged in the batch" for every estimator below.
@register_workspace("spmv_tiles")
def _spmv_workspace(nd: int, tile_dim: int, devices: int = 1) -> int:
    # gathered xs + produced ys, one (nd, T) float32 slab each
    return 2 * _per_device(nd, devices) * tile_dim * 4


# CSR estimators: what the sparse/CSR path stages or scratches per wave.
# They take their own hints (``csr_edges``, ``items``/``depth``) and
# swallow the dense hints so max_workspace_bytes stays callable with
# (nd, tile_dim) alone.
@register_workspace("csr_slice")
def _csr_slice_workspace(csr_edges: int = 0, devices: int = 1,
                         **_hints) -> int:
    # the conformal CSR row slices staged as the wave's ctx.indices
    # (int32 per adjacency entry) — see BlockStore.csr_slices.  A mesh
    # device stages only its own tasks' row slices, hence the split.
    return _per_device(int(csr_edges) * 4, devices)


@register_workspace("csr_bucket_search")
def _csr_bucket_search_workspace(items: int = 0, depth: int = 0,
                                 devices: int = 1, **_hints) -> int:
    # TC-style membership test over staged CSR slices: gathered values
    # plus lo/hi binary-search bounds, one (items, depth) int32 each
    return 3 * _per_device(items, devices) * int(depth) * 4


@register_workspace("stage_arena")
def _stage_arena_workspace(slab_bytes: int = 0, depth: int = 2,
                           devices: int = 1, **_hints) -> int:
    # Pipelined staging (repro.core.stream._StagePipeline) keeps up to
    # ``depth`` assembled host slabs in flight plus the one crossing the
    # bus: the arena's pooled buffers are bounded by (depth + 1) × the
    # largest slab.  Host-side memory — the *device* bound stays the
    # per-slab ≤ budget invariant (at most current + prefetch resident),
    # but the footprint model prices the arena so callers can see the
    # true steady-state staging residency.
    return _per_device(int(slab_bytes) * (max(int(depth), 1) + 1), devices)


@register_workspace("frontier_tiles")
def _frontier_workspace(nd: int, tile_dim: int, devices: int = 1) -> int:
    # gathered frontier columns (bool) + candidate mins (int32)
    return _per_device(nd, devices) * tile_dim * (1 + 4)


@register_workspace("tc_tiles")
def _tc_workspace(nd: int, tile_dim: int, devices: int = 1) -> int:
    # the gathered tile operands of the masked matmul (one per staged
    # tile: each triple reads its 3 tiles, nd counts all of them)
    return _per_device(nd, devices) * tile_dim * tile_dim * 4


# ----------------------------------------------------------------------
# Built-in registrations for the dense-path tile kernels.  Pallas
# implementations import lazily inside the wrapper so merely selecting
# the backend never pays (or breaks on) the Pallas import.
def _register_builtin() -> None:
    import jax
    import jax.numpy as jnp

    from . import ref

    @register_kernel("spmv_tiles", "reference")
    def _spmv_reference(tiles, xs):
        return ref.spmv_tiles_ref(tiles, xs)

    @register_kernel("spmv_tiles", "xla")
    def _spmv_xla(tiles, xs):
        # HIGHEST: a TPU's default f32 matmul rounds xs to bf16
        return jnp.einsum("brc,br->bc", tiles, xs,
                          precision=jax.lax.Precision.HIGHEST)

    @register_kernel("spmv_tiles", "pallas")
    def _spmv_pallas(tiles, xs):
        from . import ops

        return ops.spmv_tiles(tiles, xs)

    @register_kernel("frontier_tiles", "reference")
    def _frontier_reference(tiles, fcols):
        return ref.frontier_tiles_ref(tiles, fcols)

    @register_kernel("frontier_tiles", "xla")
    def _frontier_xla(tiles, fcols):
        t = tiles.shape[-1]
        colid = jnp.arange(t, dtype=jnp.int32)[None, None, :]
        masked = jnp.where((tiles > 0) & fcols[:, None, :], colid, ref.INT_MAX)
        return masked.min(axis=2)

    @register_kernel("frontier_tiles", "pallas")
    def _frontier_pallas(tiles, fcols):
        from . import ops

        return ops.frontier_tiles(tiles, fcols)

    @register_kernel("tc_tiles", "reference")
    def _tc_reference(a_ik, a_jk, a_ij):
        return ref.tc_tiles_ref(a_ik, a_jk, a_ij)

    @register_kernel("tc_tiles", "xla")
    def _tc_xla(a_ik, a_jk, a_ij):
        wedges = jnp.einsum("brc,bsc->brs", a_ik, a_jk)
        return jnp.sum(wedges * a_ij)

    @register_kernel("tc_tiles", "pallas")
    def _tc_pallas(a_ik, a_jk, a_ij):
        from . import ops

        return ops.tc_tiles(a_ik, a_jk, a_ij)

    for _name in ref.HOST_EXECUTABLE:
        register_host_executable(_name)


_register_builtin()
