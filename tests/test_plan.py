"""Tests for the typed Context + compiled Plan API and the backend registry."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    rmat, from_edges, build_block_store, build_schedule, compile_plan,
    BlockAlgorithm, Context, Engine,
)
from repro.core.context import build_context, with_extras
from repro.algorithms import pagerank_algorithm
from repro.kernels import registry


def _permuted_copy(g, seed=0):
    """Same n/m, different labels — a genuinely different graph."""
    perm = np.random.default_rng(seed).permutation(g.n)
    s, d = g.coo()
    return from_edges(perm[s], perm[d], n=g.n)


# ----------------------------------------------------------------- Plan
def test_plan_reuse_across_graphs_compiles_once():
    g1 = rmat(7, 8, seed=3)
    g2 = _permuted_copy(g1)
    assert (g1.n, g1.m) == (g2.n, g2.m)
    s1, s2 = build_block_store(g1, 4), build_block_store(g2, 4)
    plan = compile_plan(pagerank_algorithm(), s1, mode="sparse_only",
                        share=False)
    r1 = plan.run()
    assert plan.compile_count == 1
    r2 = plan.run(s2)
    assert plan.compile_count == 1  # same padded shapes → no retrace
    assert abs(np.asarray(r1.result).sum() - 1.0) < 1e-3
    assert abs(np.asarray(r2.result).sum() - 1.0) < 1e-3


def test_plan_results_match_per_graph_compilation():
    g1 = rmat(7, 8, seed=5)
    g2 = _permuted_copy(g1, seed=1)
    s2a, s2b = build_block_store(g2, 4), build_block_store(g2, 4)
    shared = compile_plan(pagerank_algorithm(), build_block_store(g1, 4),
                          mode="sparse_only", share=False)
    via_reuse = shared.run(s2a).result
    fresh = compile_plan(pagerank_algorithm(), s2b, mode="sparse_only",
                         share=False).run().result
    np.testing.assert_allclose(via_reuse, fresh, atol=1e-7)


def test_cross_plan_step_cache_shared_by_name_and_params():
    g = rmat(6, 6, seed=9)
    s1, s2 = build_block_store(g, 2), build_block_store(g, 2)
    p1 = compile_plan(pagerank_algorithm(), s1, mode="sparse_only")
    p1.run()
    c = p1.compile_count
    p2 = compile_plan(pagerank_algorithm(), s2, mode="sparse_only")
    p2.run()
    assert p2.compile_count == c  # second Plan reused the compiled step
    # different trace-affecting params must NOT share
    p3 = compile_plan(pagerank_algorithm(damping=0.5), s2, mode="sparse_only")
    assert p3._step is not p2._step


def test_plan_iterates_max_iterations_without_after():
    """Regression: the legacy engine silently ran once when after=None."""
    g = rmat(6, 4, seed=0)
    store = build_block_store(g, 2)
    alg = BlockAlgorithm(
        name="count_iters",
        kernel_sparse=lambda ctx, state, it: dict(x=state["x"] + 1),
        init_state=lambda store: dict(x=jnp.asarray(0, jnp.int32)),
        max_iterations=5,
    )
    res = compile_plan(alg, store, mode="sparse_only", share=False).run()
    assert res.iterations == 5
    assert int(res.state["x"]) == 5


def test_bind_respects_explicit_schedule():
    """Regression: a memoized binding must not shadow a caller's schedule."""
    g = rmat(6, 6, seed=4)
    store = build_block_store(g, 2)
    plan = compile_plan(pagerank_algorithm(), store, mode="sparse_only",
                        share=False)
    auto = plan.bind(store).schedule
    custom = build_schedule(plan.alg, store, mode="sparse_only", num_devices=2)
    assert custom is not auto
    assert plan.bind(store, custom).schedule is custom
    assert plan.bind(store).schedule is custom  # new binding sticks


def test_binding_cache_is_bounded():
    """Regression: sweeping many graphs through one plan must not retain
    every store's device arrays forever."""
    g = rmat(6, 6, seed=4)
    plan = compile_plan(pagerank_algorithm(), build_block_store(g, 2),
                        mode="sparse_only", share=False)
    stores = [build_block_store(_permuted_copy(g, seed=i), 2)
              for i in range(plan._MAX_BINDINGS + 4)]
    for s in stores:
        plan.run(s)
    assert len(plan._bindings) <= plan._MAX_BINDINGS
    assert any(b is plan._default for b in plan._bindings.values())
    assert plan.compile_count == 1  # eviction never forces a retrace


def test_engine_shim_still_works():
    g = rmat(7, 8, seed=11)
    store = build_block_store(g, 4)
    with pytest.warns(DeprecationWarning):
        eng = Engine(pagerank_algorithm(), store, mode="hybrid",
                     dense_density=0.001)
    res = eng.run()
    assert abs(np.asarray(res.result).sum() - 1.0) < 1e-3
    assert eng.schedule.stats["num_tasks"] == 16


# -------------------------------------------------------------- Context
def _small_context(extras=None):
    g = rmat(6, 4, seed=2)
    store = build_block_store(g, 2)
    sched = build_schedule(pagerank_algorithm(), store, mode="sparse_only")
    return build_context(store, sched, extras=extras or {})


def test_context_roundtrips_through_jit():
    ctx = _small_context(extras={"w": jnp.arange(3.0)})
    out = jax.jit(lambda c: c)(ctx)
    assert isinstance(out, Context)
    np.testing.assert_array_equal(np.asarray(out.src), np.asarray(ctx.src))
    np.testing.assert_array_equal(np.asarray(out.extras["w"]),
                                  np.asarray(ctx.extras["w"]))
    assert out.n == ctx.n and out.backend == ctx.backend
    # flatten/unflatten is an identity on structure
    leaves, treedef = jax.tree_util.tree_flatten(ctx)
    ctx2 = jax.tree_util.tree_unflatten(treedef, leaves)
    assert jax.tree_util.tree_structure(ctx2) == treedef


def test_context_extras_preserve_tuples():
    """Regression: the old dict merge rebuilt tuples as lists, silently
    changing the pytree structure between traces."""
    extras = {
        "pair": (jnp.ones(3), jnp.zeros(2)),
        "mixed": (jnp.arange(4), 7, "tag"),
        "nested": {"t": (1, 2, 3), "arrs": [jnp.ones(1), (jnp.ones(2),)]},
        "none": None,
    }
    ctx = _small_context(extras=extras)
    out = jax.jit(lambda c: c)(ctx)
    assert isinstance(out.extras["pair"], tuple)
    assert isinstance(out.extras["mixed"], tuple)
    assert out.extras["mixed"][1] == 7 and out.extras["mixed"][2] == "tag"
    assert out.extras["nested"]["t"] == (1, 2, 3)
    assert isinstance(out.extras["nested"]["arrs"], list)
    assert isinstance(out.extras["nested"]["arrs"][1], tuple)
    assert out.extras["none"] is None
    # identical treedef across two traces of the same structure → one jit entry
    t1 = jax.tree_util.tree_structure(ctx)
    t2 = jax.tree_util.tree_structure(with_extras(ctx, {}))
    assert t1 == t2


def test_context_static_leaves_stay_static_under_jit():
    ctx = _small_context(extras={"steps": 3, "xs": jnp.arange(5.0)})

    @jax.jit
    def f(c):
        # a static int must be usable as a Python shape/loop bound
        acc = jnp.zeros(c.extras["steps"])
        return acc + c.extras["xs"][: c.extras["steps"]]

    np.testing.assert_allclose(np.asarray(f(ctx)), [0.0, 1.0, 2.0])


# ------------------------------------------------------------- registry
def test_registry_resolution_and_fallback(monkeypatch):
    assert registry.resolve_backend("xla") == "xla"
    with pytest.raises(ValueError):
        registry.resolve_backend("cuda")
    # a registered Pallas kernel is returned as itself, never its twin
    assert (registry.get_kernel("spmv_tiles", "pallas")
            is registry.registered("spmv_tiles")["pallas"])
    # a kernel with no Pallas registration takes one step, to xla only
    monkeypatch.setitem(registry._REGISTRY, ("_xla_only", "xla"), len)
    monkeypatch.setitem(registry._REGISTRY, ("_ref_only", "reference"), abs)
    assert registry.get_kernel("_xla_only", "pallas") is len
    assert registry.get_kernel("_ref_only", "xla") is abs
    with pytest.raises(KeyError):
        registry.get_kernel("_ref_only", "pallas")
    # no Pallas runtime: an explicit pallas request raises, not xla
    monkeypatch.setattr(registry, "_FORCE_PALLAS_AVAILABLE", False)
    with pytest.raises(RuntimeError, match="no Pallas runtime"):
        registry.resolve_backend("pallas")
    with pytest.raises(RuntimeError, match="no Pallas runtime"):
        registry.get_kernel("spmv_tiles", "pallas")


def test_compile_plan_pallas_falls_back_cleanly(monkeypatch):
    """Without a Pallas runtime, backend="pallas" fails at compile_plan
    with a clear error instead of quietly running the xla kernels."""
    monkeypatch.setattr(registry, "_FORCE_PALLAS_AVAILABLE", False)
    g = rmat(7, 8, seed=3)
    store = build_block_store(g, 4)
    with pytest.raises(RuntimeError, match="no Pallas runtime"):
        compile_plan(pagerank_algorithm(), store, mode="hybrid",
                     dense_density=0.001, backend="pallas", share=False)
    plan = compile_plan(pagerank_algorithm(), store, mode="hybrid",
                        dense_density=0.001, backend="xla", share=False)
    assert abs(np.asarray(plan.run().result).sum() - 1.0) < 1e-3


@pytest.mark.parametrize("backend", ["reference", "xla"])
def test_backends_agree_on_tile_kernels(backend):
    nd, t = 3, 8
    rng = np.random.default_rng(0)
    tiles = jnp.asarray((rng.random((nd, t, t)) < 0.3).astype(np.float32))
    xs = jnp.asarray(rng.random((nd, t)).astype(np.float32))
    want = registry.get_kernel("spmv_tiles", "reference")(tiles, xs)
    got = registry.get_kernel("spmv_tiles", backend)(tiles, xs)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6)
    fcols = jnp.asarray(rng.random((nd, t)) < 0.5)
    want_f = registry.get_kernel("frontier_tiles", "reference")(tiles, fcols)
    got_f = registry.get_kernel("frontier_tiles", backend)(tiles, fcols)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))


def test_no_host_objects_in_context():
    """The typed contract: Context holds no store/schedule, HostCtx does."""
    g = rmat(6, 4, seed=2)
    store = build_block_store(g, 2)
    plan = compile_plan(pagerank_algorithm(), store, mode="sparse_only")
    leaves = jax.tree_util.tree_leaves(plan.context)
    assert all(isinstance(l, (jax.Array, np.ndarray)) for l in leaves)
    assert plan.host.store is store
    assert plan.host.schedule is plan.schedule
