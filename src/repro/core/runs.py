"""Runs of equal arc sources, and the gather that reads each source once
per run.

``build_block_store`` sorts arcs by (block, src, dst), so a source's arcs
inside a block lie next to each other, in runs of equal ``src``.  A
sparse kernel that needs ``x[src]`` at every arc can gather ``x`` once per
run instead and expand the run values to the arcs exactly:

1. gather ``x`` at each run's source (K elements);
2. take each run's bit pattern minus the previous run's, as integers;
3. write those differences at the runs' first arcs (sorted, unique);
4. take a wrapping integer prefix sum over the arcs and bitcast back.

Integer overflow wraps, so the prefix sum gives back every arc's bit
pattern: -0.0, inf and NaN included.

The in-core context holds the run tables (``run_pos``: each run's first
arc, ``run_src``: its source) in ``src``'s place when the algorithm
declares ``metadata["src"] == "gather"`` (its kernels read arc sources
only through :func:`gather_src`) and the tables are no larger than
``src``.  The number of runs is rounded up, by splitting runs, to its
first :data:`RUN_BITS` binary digits (and to a multiple of
:data:`RUN_QUANTUM`).  The compiled step's shapes then follow the
graph's size, as they do per arc, and not its run count: graphs of one
size share one compiled step, as ``Plan.run(other_store)`` and
``GraphServer``'s plans over same-shape graphs do (``engine.py``), where
each graph's own run count would compile the step anew.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["RUN_BITS", "RUN_QUANTUM", "source_runs", "expand_runs",
           "gather_src"]

#: the run count is padded to a multiple of this (one int32 (8, 128) tile)
RUN_QUANTUM = 1024
#: ... and to this many leading binary digits: at most 1/64 more runs.
#: A vertex renumbering of one graph moves the count by about its square
#: root, which a finer step would turn into a new compiled shape per run.
RUN_BITS = 7
#: arcs per step of the prefix sum.  XLA's scan of a whole array holds
#: three copies of it; a loop over chunks, written back in place, holds
#: one, and three of a chunk.
SCAN_CHUNK = 1 << 20

_INT_OF_SIZE = {1: jnp.int8, 2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


def source_runs(src: np.ndarray):
    """``(run_pos, run_src)``, int32, of the runs of equal consecutive
    values of ``src``, or None where the two tables would be larger than
    ``src`` (more than ``len(src) // 2`` runs).

    Runs are split until their count is rounded up to :data:`RUN_BITS`
    binary digits and a multiple of :data:`RUN_QUANTUM`, but never past
    ``len(src) // 2``: a run split in two is still a run of one source,
    so the expansion stays exact."""
    src = np.asarray(src)
    m = int(src.shape[0])
    pos = _run_starts(src)
    k = int(pos.shape[0])
    if 2 * k > m:
        return None
    step = max(RUN_QUANTUM, 1 << max(k.bit_length() - RUN_BITS, 0))
    target = min(-(-k // step) * step, m // 2)
    pos = _split_runs(pos, m, target - k)
    return pos.astype(np.int32), src[pos].astype(np.int32)


def _run_starts(src: np.ndarray) -> np.ndarray:
    """The first arc of each run of equal consecutive values of ``src``."""
    pos = np.flatnonzero(src[1:] != src[:-1]) + 1
    if src.shape[0]:
        pos = np.concatenate([np.zeros(1, pos.dtype), pos])
    return pos


def _split_runs(pos: np.ndarray, m: int, extra: int) -> np.ndarray:
    """``pos`` with ``extra`` more run starts, at the first arcs inside
    runs (positions that start no run), taken in order."""
    if extra <= 0:
        return pos
    inner = np.append(pos[1:], m) - pos - 1     # arcs inside each run
    upto = np.cumsum(inner)
    # the first runs that hold ``extra`` inner arcs between them
    r = int(np.searchsorted(upto, extra)) + 1
    inner, upto = inner[:r], upto[:r]
    take = np.clip(extra - (upto - inner), 0, inner)
    runs = np.flatnonzero(take)
    t = take[runs]
    new = (np.repeat(pos[runs] + 1, t)
           + np.arange(int(t.sum())) - np.repeat(np.cumsum(t) - t, t))
    return np.insert(pos, np.searchsorted(pos, new), new)


def expand_runs(run_pos, values, m: int):
    """``values[i]`` at every arc of run ``i``: an ``(m, ...)`` array from
    a ``(K, ...)`` one, bit for bit."""
    as_bool = values.dtype == jnp.bool_
    v = values.astype(jnp.int8) if as_bool else values
    ity = _INT_OF_SIZE[v.dtype.itemsize]
    bits = jax.lax.bitcast_convert_type(v, ity)
    prev = jnp.concatenate([jnp.zeros_like(bits[:1]), bits[:-1]])
    starts = jnp.zeros((m,) + bits.shape[1:], ity).at[run_pos].set(
        bits - prev, indices_are_sorted=True, unique_indices=True,
        mode="promise_in_bounds", wrap_negative_indices=False)
    out = jax.lax.bitcast_convert_type(_prefix_sum(starts), v.dtype)
    return out.astype(jnp.bool_) if as_bool else out


def _prefix_sum(a):
    """Inclusive, wrapping prefix sum of integer ``a`` along axis 0, in
    place, :data:`SCAN_CHUNK` rows at a time."""
    steps = a.shape[0] // SCAN_CHUNK

    def chunk(buf, start, length, total):
        part = jnp.cumsum(jax.lax.dynamic_slice_in_dim(buf, start, length),
                          axis=0, dtype=buf.dtype) + total
        return jax.lax.dynamic_update_slice_in_dim(buf, part, start, 0), part[-1]

    def body(i, carry):
        return chunk(carry[0], i * SCAN_CHUNK, SCAN_CHUNK, carry[1])

    total = jnp.zeros(a.shape[1:], a.dtype)
    if steps:
        a, total = jax.lax.fori_loop(0, steps, body, (a, total))
    tail = a.shape[0] - steps * SCAN_CHUNK
    if tail:
        a, _ = chunk(a, steps * SCAN_CHUNK, tail, total)
    return a


def gather_src(ctx, x):
    """``x[ctx.src]``, bit for bit: through the context's runs of equal
    sources where it holds them (one read of ``x`` per run), per arc
    otherwise."""
    if ctx.run_src is None:
        return x[ctx.src]
    vals = x.at[ctx.run_src].get(mode="promise_in_bounds",
                                 wrap_negative_indices=False)
    return expand_runs(ctx.run_pos, vals, ctx.dst.shape[0])
