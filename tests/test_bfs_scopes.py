"""BFS names its level kernels and counts its levels by direction: the
push and pull steps carry ``gather`` and ``scatter`` scopes inside
``sparse``, ``direction="auto"`` in-core searches equal push-only ones
and a networkx reference, and the direction controller's two level
counters add up to the levels run, in-core and streamed."""
import re

import jax.numpy as jnp
import networkx as nx
import numpy as np
import pytest

from repro import obs
from repro.algorithms import bfs_algorithm
from repro.core import build_block_store, compile_plan, erdos_renyi, rmat

_UNVISITED = 2**31 - 1


@pytest.fixture(scope="module")
def store():
    return build_block_store(rmat(8, 8, seed=3), 4)


def _op_names(compiled) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


@pytest.mark.parametrize("direction", ["push", "pull"])
def test_level_steps_carry_gather_and_scatter_scopes(store, direction):
    alg = bfs_algorithm(0)
    plan = compile_plan(alg, store, mode="sparse_only", share=False,
                        direction="auto")
    b = next(iter(plan._bindings.values()))
    names = _op_names(plan._steps[direction]._jit.lower(
        b.context, alg.init_state(store), jnp.int32(0),
        b.run_dense).compile())
    for scope in ("sparse/gather/", "sparse/scatter/", "/post/"):
        assert any(scope in n for n in names), (scope, sorted(names))
    # the min-scatter itself sits in the scatter scope
    assert any(n.endswith("sparse/scatter/scatter-min") for n in names), \
        sorted(names)


def _nx_depths(g, source):
    G = nx.Graph()
    G.add_nodes_from(range(g.n))
    src, dst = g.coo()
    G.add_edges_from(zip(src.tolist(), dst.tolist()))
    got = nx.single_source_shortest_path_length(G, source)
    return np.array([got.get(v, -1) for v in range(g.n)]), G


@pytest.mark.parametrize("graph, source", [
    (rmat(8, 8, seed=3), 0), (rmat(9, 8, seed=11), 5),
    (erdos_renyi(400, 6.0, seed=2), 17)], ids=["rmat8", "rmat9", "er"])
@pytest.mark.parametrize("mode", ["hybrid", "sparse_only"])
def test_auto_direction_matches_push_and_reference(graph, source, mode):
    store = build_block_store(graph, 4)
    alg = bfs_algorithm(source)
    push = compile_plan(alg, store, mode=mode).run()
    auto = compile_plan(alg, store, mode=mode, direction="auto").run()
    assert "pull" in auto.schedule_stats["direction"]["decisions"]
    assert auto.iterations == push.iterations
    for k in ("dist", "parent"):
        np.testing.assert_array_equal(auto.result[k], push.result[k])
    depth, G = _nx_depths(graph, source)
    dist = auto.result["dist"]
    np.testing.assert_array_equal(np.where(dist == _UNVISITED, -1, dist),
                                  depth)
    assert auto.iterations == depth.max() + 1
    # the program's tree: each reached vertex's least neighbour one
    # level up, the root its own parent
    parent = auto.result["parent"]
    for v in range(graph.n):
        if depth[v] < 0:
            assert parent[v] == _UNVISITED
        elif v == source:
            assert parent[v] == source
        else:
            assert parent[v] == min(u for u in G[v]
                                    if depth[u] == depth[v] - 1)


def _levels():
    snap = obs.metrics.snapshot()
    return (snap.get("direction.push_levels", 0),
            snap.get("direction.pull_levels", 0))


@pytest.mark.parametrize("direction", ["auto", "push", "pull"])
@pytest.mark.parametrize("budget", [None, "16KB"],
                         ids=["incore", "streamed"])
def test_level_counters_add_up_to_the_levels_run(store, direction, budget):
    kw = dict(memory_budget=budget) if budget else {}
    plan = compile_plan(bfs_algorithm(0), store, mode="sparse_only",
                        direction=direction, **kw)
    push0, pull0 = _levels()
    res = plan.run()
    push1, pull1 = _levels()
    stats = res.schedule_stats["direction"]
    assert push1 - push0 + pull1 - pull0 == res.iterations
    assert pull1 - pull0 == stats["pull_iterations"]
    if direction == "auto":
        assert 0 < stats["pull_iterations"] < res.iterations
