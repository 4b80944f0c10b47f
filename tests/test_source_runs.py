"""The gather through runs of equal sources (``repro.core.runs``): bit
for bit equal to ``x[src]``, engaged only where the algorithm declares it
and the tables are no larger than ``src``, and invisible in every result
of the in-core and streamed PageRank."""
import dataclasses
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.algorithms import (
    bfs_algorithm, hits_algorithm, kcore_algorithm, pagerank_algorithm,
    sv_algorithm,
)
from repro.core import (
    batch_states, build_block_store, compile_plan, erdos_renyi, from_edges,
    rmat,
)
from repro.core import runs
from repro.core.runs import (
    RUN_BITS, RUN_QUANTUM, _run_starts, gather_src, source_runs,
)

SPECIAL = np.array([-0.0, 0.0, np.inf, -np.inf, np.nan, 1e-45, -3.4e38,
                    1.0], np.float32)


def _store(name):
    if name == "rmat":
        return build_block_store(rmat(9, 8, seed=3), 4)
    if name == "random":
        return build_block_store(erdos_renyi(300, 6.0, seed=1), 4)
    raise KeyError(name)


def _src_of(case):
    """Arc sources of a store, or a hand-made sequence."""
    if case in ("rmat", "random"):
        return np.asarray(_store(case).src)
    return {
        "one_arc": np.array([5], np.int32),
        "no_arcs": np.zeros(0, np.int32),
        "all_distinct": np.arange(40, dtype=np.int32)[::-1].copy(),
        "one_run": np.full(33, 7, np.int32),
    }[case]


def _values(n, dtype, seed=0):
    rng = np.random.default_rng(seed)
    if dtype == np.float32:
        x = rng.standard_normal(n).astype(np.float32)
        x[: min(n, SPECIAL.size)] = SPECIAL[: min(n, SPECIAL.size)]
        # a NaN with a payload of its own
        if n > SPECIAL.size:
            x[SPECIAL.size] = np.array(0x7FC01234, np.uint32).view(np.float32)
        return x
    if dtype == np.int32:
        return rng.integers(-2**31, 2**31 - 1, n, dtype=np.int64).astype(
            np.int32)
    return rng.random(n) < 0.5


def _ctx(src):
    """A context holding the unpadded runs of ``src``."""
    pos = _run_starts(src)
    return SimpleNamespace(run_pos=jnp.asarray(pos),
                           run_src=jnp.asarray(src[pos]),
                           src=None, dst=jnp.zeros(src.shape[0], jnp.int32))


def _bits(a):
    a = np.asarray(a)
    return a.view(np.uint32) if a.dtype == np.float32 else a


@pytest.mark.parametrize("chunk", [runs.SCAN_CHUNK, 16, 7])
@pytest.mark.parametrize("dtype", [np.float32, np.int32, np.bool_])
@pytest.mark.parametrize("case", ["rmat", "random", "one_arc", "no_arcs",
                                  "all_distinct", "one_run"])
def test_gather_equals_per_arc_bit_for_bit(monkeypatch, case, dtype, chunk):
    monkeypatch.setattr(runs, "SCAN_CHUNK", chunk)
    src = _src_of(case)
    n = int(src.max()) + 1 if src.size else 4
    x = _values(n, dtype)
    got = jax.jit(lambda x: gather_src(_ctx(src), x))(jnp.asarray(x))
    assert got.dtype == x.dtype and got.shape == src.shape
    np.testing.assert_array_equal(_bits(got), _bits(x[src]))


@pytest.mark.parametrize("case", ["rmat", "random"])
def test_gather_under_vmap(case):
    src = _src_of(case)
    xs = np.stack([_values(int(src.max()) + 1, np.float32, seed=s)
                   for s in range(3)])
    got = jax.vmap(lambda x: gather_src(_ctx(src), x))(jnp.asarray(xs))
    np.testing.assert_array_equal(_bits(got), _bits(xs[:, src]))


def test_masked_dense_block_arcs_and_padding():
    """The kernel's form: padded tables, dense-block arcs masked after the
    gather, on a store whose schedule routes blocks to dense tiles."""
    store = _store("rmat")
    plan = compile_plan(pagerank_algorithm(), store, mode="hybrid",
                        dense_density=0.001, share=False)
    ctx = plan.context
    assert ctx.src is None and ctx.run_src is not None
    msk = np.asarray(ctx.sparse_edge_mask)
    assert 0 < msk.sum() < msk.size          # both paths hold arcs
    x = _values(store.n, np.float32)
    got = jax.jit(lambda c, x: jnp.where(c.sparse_edge_mask,
                                         gather_src(c, x), 0.0))(ctx, x)
    want = np.where(msk, x[np.asarray(store.src)], np.float32(0))
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_run_crossing_a_block_boundary():
    """One source's arcs in two adjacent blocks make one run, and the
    expansion still puts the source's value on every arc of both."""
    # the partitioner cuts this graph at vertex 3: vertex 2's arc to 1
    # ends block (0, 0) and its arc to 6 starts block (0, 1)
    store = build_block_store(
        from_edges(np.array([0, 1, 2, 4, 5]), np.array([1, 2, 6, 5, 7]),
                   n=8), 2)
    src = np.asarray(store.src)
    blocks = np.asarray(store.edge_block)
    pos = _run_starts(src)
    crossing = [i for i, p in enumerate(pos)
                if len(set(blocks[p:(pos[i + 1] if i + 1 < len(pos)
                                     else src.size)])) > 1]
    assert crossing, (src, blocks)
    x = _values(8, np.float32)
    got = gather_src(_ctx(src), jnp.asarray(x))
    np.testing.assert_array_equal(_bits(got), _bits(x[src]))


# --- the engagement rule --------------------------------------------------
def _per_arc(alg):
    """The same algorithm without the declaration: it keeps ``src``."""
    meta = {k: v for k, v in alg.metadata.items() if k != "src"}
    return dataclasses.replace(alg, metadata=meta)


def test_tables_replace_src_with_the_declaration():
    store = _store("rmat")
    k = _run_starts(store.src).size
    assert 2 * k <= store.m
    runs_plan = compile_plan(pagerank_algorithm(), store, share=False)
    per_arc = compile_plan(_per_arc(pagerank_algorithm()), store,
                           share=False)
    ctx = runs_plan.context
    assert ctx.src is None
    padded = ctx.run_src.shape[0]
    assert k <= padded <= store.m // 2
    assert padded % RUN_QUANTUM == 0 or padded == store.m // 2
    assert ctx.run_pos.dtype == ctx.run_src.dtype == jnp.int32
    assert per_arc.context.run_src is None
    assert per_arc.context.src is not None
    # the resident context holds the tables in src's place, never more
    saved = per_arc.resident_device_bytes - runs_plan.resident_device_bytes
    assert saved == 4 * (store.m - 2 * padded)


def test_tables_stay_off_when_larger_than_src():
    # every vertex has one neighbour: each arc starts a run, 2K > m
    n = 64
    store = build_block_store(
        from_edges(np.arange(0, n, 2), np.arange(1, n, 2), n=n), 2)
    assert source_runs(store.src) is None
    plan = compile_plan(pagerank_algorithm(), store, share=False)
    assert plan.context.run_src is None and plan.context.src is not None
    assert plan.run().schedule_stats["sparse_gather"] == dict(
        arcs=store.m, gathered=store.m, engaged=False)


@pytest.mark.parametrize("make", [
    lambda: bfs_algorithm(source=0), sv_algorithm, lambda: kcore_algorithm(2),
    hits_algorithm,
], ids=["bfs", "sv", "kcore", "hits"])
def test_undeclared_algorithms_keep_src(make):
    plan = compile_plan(make(), _store("rmat"), share=False)
    assert plan.context.src is not None and plan.context.run_src is None


@pytest.mark.parametrize("k", [1_050_000, 1_060_000, 1_064_960])
def test_padding_shares_one_shape_between_nearby_counts(k):
    """Run counts a renumbering apart pad to one length, at most 1/64
    above the count."""
    src = np.repeat(np.arange(k, dtype=np.int32), 3)
    pos, run_src = source_runs(src)
    assert np.array_equal(run_src, src[pos])
    step = 1 << (k.bit_length() - RUN_BITS)
    assert step > RUN_QUANTUM
    assert pos.size == 1_064_960 == 65 * step
    assert k <= pos.size <= k + k // 64


def test_padding_splits_runs_exactly():
    src = np.repeat(np.arange(50, dtype=np.int32), 40)   # 50 runs of 40
    pos, run_src = source_runs(src)
    assert pos.size == min(RUN_QUANTUM, src.size // 2)
    assert np.all(np.diff(pos) > 0) and pos[0] == 0
    assert np.array_equal(run_src, src[pos])
    x = _values(50, np.float32)
    got = gather_src(SimpleNamespace(run_pos=jnp.asarray(pos),
                                     run_src=jnp.asarray(run_src), src=None,
                                     dst=jnp.zeros(src.size, jnp.int32)),
                     jnp.asarray(x))
    np.testing.assert_array_equal(_bits(got), _bits(x[src]))


# --- PageRank through both paths ----------------------------------------
def _ranks(alg, store, state=None, **kw):
    plan = compile_plan(alg, store, share=False, **kw)
    return plan, plan.run(state=state)


@pytest.mark.parametrize("mode", ["sparse_only", "hybrid"])
@pytest.mark.parametrize("kind", ["plain", "seeded", "batched"])
def test_incore_pagerank_bit_identical(kind, mode):
    store = _store("rmat")
    kw = dict(mode=mode, dense_density=0.001)
    if kind == "batched":
        alg = pagerank_algorithm(tol=0.0, max_iters=6)
        inits = [pagerank_algorithm(seeds=s).init_state(store)
                 for s in ([1], [4, 9], [30])]
        state = batch_states(inits)
    else:
        alg = pagerank_algorithm(seeds=[3, 17] if kind == "seeded" else None,
                                 tol=0.0, max_iters=6)
        state = None
    plan, got = _ranks(alg, store, state, **kw)
    ref_plan, want = _ranks(_per_arc(alg), store, state, **kw)
    assert plan.context.run_src is not None
    assert ref_plan.context.run_src is None
    assert got.iterations == want.iterations == 6
    for key in want.state:
        np.testing.assert_array_equal(_bits(got.state[key]),
                                      _bits(want.state[key]))


def test_src_reads_right_inside_the_step():
    """A kernel that still reads ``ctx.src`` on a context with runs gets
    the sources, expanded inside the compiled step."""
    from repro.core import BlockAlgorithm

    def kernel(ctx, state, it):
        return dict(state, s=state["s"] + jnp.sum(ctx.src))

    alg = BlockAlgorithm(name="sum_src", kernel_sparse=kernel,
                         init_state=lambda st: dict(s=jnp.int32(0)),
                         metadata=dict(src="gather"))
    store = _store("rmat")
    plan = compile_plan(alg, store, mode="sparse_only", share=False)
    assert plan.context.src is None
    assert int(plan.run().state["s"]) == int(np.asarray(store.src).sum())


def _permuted(seed):
    g = rmat(9, 8, seed=3)
    perm = np.random.default_rng(seed).permutation(g.n)
    s, d = g.coo()
    return build_block_store(from_edges(perm[s], perm[d], n=g.n), 4)


def test_graphs_of_one_size_share_one_compiled_step():
    """Two orders of one graph hold different numbers of runs, padded to
    one length: the second graph runs on the first one's compiled step,
    as it does per arc, and its ranks equal a fresh plan's."""
    a, b = _store("rmat"), _permuted(5)
    assert (a.n, a.m) == (b.n, b.m)
    assert _run_starts(a.src).size != _run_starts(b.src).size
    alg = pagerank_algorithm(tol=0.0, max_iters=4)
    plan = compile_plan(alg, a, mode="sparse_only", share=False)
    plan.run()
    assert plan.compile_count == 1
    via_reuse = plan.run(b)
    assert plan.compile_count == 1
    assert via_reuse.schedule_stats["sparse_gather"]["engaged"]
    fresh = compile_plan(alg, b, mode="sparse_only", share=False).run()
    np.testing.assert_array_equal(_bits(via_reuse.result),
                                  _bits(fresh.result))


def test_streamed_contexts_keep_src_and_ranks():
    store = _store("rmat")
    alg = pagerank_algorithm(tol=0.0, max_iters=4)
    kw = dict(mode="sparse_only", memory_budget="24KB", pipeline_depth=0,
              host_fraction=None, rebalance_threshold=None)
    plan, got = _ranks(alg, store, **kw)
    _, want = _ranks(_per_arc(alg), store, **kw)
    assert got.schedule_stats["streaming"]["num_waves"] >= 2
    assert plan._resident.run_src is None
    assert plan._resident.src is not None
    # every wave stages a per-arc source slab
    assert all(r.edges > 0 and r.src_bucket >= r.edges for r in plan._slabs)
    assert "sparse_gather" not in got.schedule_stats
    np.testing.assert_array_equal(_bits(got.result), _bits(want.result))


# --- the counter -----------------------------------------------------------
def test_sparse_gather_stats_and_gauge():
    store = _store("rmat")
    obs.REGISTRY.reset()
    plan = compile_plan(pagerank_algorithm(), store, share=False)
    st = plan.run().schedule_stats["sparse_gather"]
    k = int(plan.context.run_src.shape[0])
    assert st == dict(arcs=store.m, gathered=k, engaged=True)
    assert obs.metrics.snapshot()["sparse.arcs_per_gather"] == store.m / k


@pytest.mark.parametrize("make", [
    lambda: _per_arc(pagerank_algorithm()), lambda: bfs_algorithm(source=0),
], ids=["pagerank_undeclared", "bfs"])
def test_no_sparse_gather_block_without_the_declaration(make):
    obs.REGISTRY.reset()
    res = compile_plan(make(), _store("rmat"), share=False).run()
    assert "sparse_gather" not in res.schedule_stats
    assert "sparse.arcs_per_gather" not in obs.metrics.snapshot()
