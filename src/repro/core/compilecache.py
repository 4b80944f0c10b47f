"""Share-gated compiled-step caching, common to both executors.

The in-core :class:`~repro.core.engine.Plan` and the streaming
:class:`~repro.core.stream.StreamingPlan` each own jitted step flavours
(`_CompiledStep`, `_StreamStep`, `_PostStep`, ...).  All of them are
cached process-wide under the same identity — ``(algorithm name,
trace-affecting params, backend)`` — so that two plans for the same
algorithm share one compilation, and jit's own shape bucketing makes
same-shape graphs hit the compiled executable instead of retracing.

This module is the single home of that keying/invalidation logic:
``alg_cache_key`` builds the identity tuple, ``shared_entry`` is the
share-gated lookup every cache flavour goes through.  Keeping them in
one place means a change to the cache contract (new key component,
eviction, ...) cannot silently diverge between the executors.

Execution-time configuration — fault-injection plans, checkpoint
settings, retry policies (:mod:`repro.core.faults`,
:mod:`repro.core.resilience`) — must NEVER enter a cache key: it does
not affect the traced computation, and keying on it would force
needless retraces (and let a chaos run pollute the cache for the
fault-free plans that share its steps).

Across processes, :func:`use_persistent_cache` points JAX's on-disk
compilation cache at a fixed directory.  Entry points call it; nothing
calls it at import.
"""
from __future__ import annotations

import os
from typing import TYPE_CHECKING, Callable, TypeVar

from .. import obs

if TYPE_CHECKING:  # pragma: no cover — typing only, avoids an import cycle
    from .functors import BlockAlgorithm

__all__ = ["alg_cache_key", "shared_entry", "use_persistent_cache"]


def use_persistent_cache(checkout: str) -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` wins and is left to JAX, which
    reads it itself.  Otherwise the cache lives at the fixed path
    ``<checkout>/.jax_cache``: the path is part of the cache key, so it
    is never built from a temporary name, a process id or the time.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    path = os.path.join(os.path.abspath(checkout), ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path

T = TypeVar("T")


def alg_cache_key(alg: "BlockAlgorithm", backend: str,
                  direction: str = "push") -> tuple:
    """Algorithms are identified by (name, trace-affecting params,
    backend, kernel direction).

    Factories record trace-affecting parameters under
    ``metadata["params"]``; two factory calls with equal params produce
    behaviourally identical kernels and may share a compiled step.  The
    ``direction`` component keys the push/pull kernel variant
    (:mod:`repro.core.direction`) so each direction traces exactly once
    and an auto plan's two steps never collide in the cache.
    """
    params = alg.metadata.get("params")
    return (alg.name, repr(sorted(params.items())) if params else None,
            backend, direction)


def shared_entry(cache: dict, key: tuple, factory: Callable[[], T], *,
                 share: bool = True) -> T:
    """The one share-gated cache lookup used for every compiled-step
    flavour (in-core step in engine.py; wave/post/mesh steps in
    stream.py).  ``share=False`` bypasses the cache for ad-hoc
    algorithms that reuse a registered name with different kernels."""
    if not share:
        obs.metrics.counter("compile.cache.bypasses").inc()
        return factory()
    entry = cache.get(key)
    if entry is None:
        obs.metrics.counter("compile.cache.misses").inc()
        entry = cache[key] = factory()
    else:
        obs.metrics.counter("compile.cache.hits").inc()
    return entry
