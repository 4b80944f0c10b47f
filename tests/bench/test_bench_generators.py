"""The benchmark's graph generators, its PageRank reference and its work
functions, at small sizes on the CPU."""
import json
import os

import networkx as nx
import numpy as np
import pytest

from bench.algorithms import pagerank
from bench.csr import symmetric_csr
from bench.generators import kron, urand

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))


def config(name, scale):
    with open(os.path.join(ROOT, "bench", "configs", f"{name}.json")) as f:
        return dict(json.load(f), scale=scale)


def traffic(name):
    with open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


def pairs(indptr, indices):
    src = np.repeat(np.arange(indptr.size - 1), np.diff(indptr))
    return src, indices.astype(np.int64)


@pytest.mark.parametrize("gen,name,kept", [(kron, "kron22", 0.5),
                                           (urand, "urand22", 0.97)])
@pytest.mark.parametrize("seed", [0, 2**31 + 11])
def test_generated_graph_is_simple_and_symmetric(gen, name, kept, seed):
    scale = 10
    indptr, indices = gen.generate(config(name, scale), seed)
    n = 1 << scale
    assert indptr.shape == (n + 1,) and indptr.dtype == np.int64
    assert indices.dtype == np.int32 and indptr[-1] == indices.size
    assert np.all(np.diff(indptr) >= 0)
    src, dst = pairs(indptr, indices)
    assert np.all((dst >= 0) & (dst < n))
    assert not np.any(src == dst), "self-loop"
    key = src * n + dst
    assert np.all(np.diff(key) > 0), "rows unsorted or duplicate arcs"
    assert np.array_equal(np.sort(dst * n + src), key), "not symmetric"
    # at most edge_factor * n edges, two arcs each; loops and
    # duplicates take some away, many in a small Kronecker graph
    assert kept * 32 * n < indices.size <= 32 * n


@pytest.mark.parametrize("gen,name", [(kron, "kron22"), (urand, "urand22")])
def test_seed_relabels_one_graph(gen, name):
    """The structure comes from the configuration's fixed seed; a run's
    seed only permutes the vertex ids: same degrees, same arc count."""
    cfg = config(name, 9)
    a = gen.generate(cfg, 2**31 + 3)
    b = gen.generate(cfg, 2**31 + 3)
    c = gen.generate(cfg, 2**31 + 4)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[1], c[1])
    assert a[1].size == c[1].size
    assert np.array_equal(np.sort(np.diff(a[0])), np.sort(np.diff(c[0])))
    other = gen.generate(dict(cfg, structure_seed=1), 2**31 + 3)
    assert not np.array_equal(np.sort(np.diff(a[0])),
                              np.sort(np.diff(other[0])))


def test_kron_is_skewed_and_urand_is_not():
    kd = np.diff(kron.generate(config("kron22", 12), 5)[0])
    ud = np.diff(urand.generate(config("urand22", 12), 5)[0])
    assert kd.max() > 20 * kd.mean()
    assert ud.max() < 3 * ud.mean()
    # Kronecker graphs leave many vertices isolated; urand almost none
    assert (kd == 0).mean() > 0.1 and (ud == 0).mean() < 0.01


def test_symmetric_csr_matches_networkx():
    rng = np.random.default_rng(1)
    src = rng.integers(0, 64, 500).astype(np.uint32)
    dst = rng.integers(0, 64, 500).astype(np.uint32)
    indptr, indices = symmetric_csr(src, dst, 6)
    g = nx.Graph()
    g.add_nodes_from(range(64))
    g.add_edges_from((int(u), int(v)) for u, v in zip(src, dst) if u != v)
    for u in range(64):
        assert indices[indptr[u]:indptr[u + 1]].tolist() == sorted(g[u])


def dense_pagerank(indptr, indices, iters, d=0.85):
    """Power iteration on the dense transition matrix, dangling mass
    spread uniformly: the textbook formula."""
    n = indptr.size - 1
    a = np.zeros((n, n))
    src, dst = pairs(indptr, indices)
    a[dst, src] = 1.0
    deg = a.sum(axis=0)
    p = np.where(deg > 0, a / np.maximum(deg, 1), 1.0 / n)
    r = np.full(n, 1.0 / n)
    for _ in range(iters):
        r = (1 - d) / n + d * p @ r
    return r


@pytest.mark.parametrize("iters", [1, 2, 5])
def test_pagerank_reference_matches_dense_power_iteration(iters):
    indptr, indices = kron.generate(config("kron22", 8), 7)
    t = dict(traffic("pr.incore"), max_iters=iters)
    got = pagerank.reference(indptr, indices, t)
    np.testing.assert_allclose(got, dense_pagerank(indptr, indices, iters),
                               rtol=1e-12, atol=0)


def test_pagerank_reference_converges_to_networkx():
    indptr, indices = urand.generate(config("urand22", 8), 2)
    g = nx.Graph()
    g.add_nodes_from(range(indptr.size - 1))
    g.add_edges_from(zip(*(x.tolist() for x in pairs(indptr, indices))))
    want = nx.pagerank(g, alpha=0.85, tol=1e-14, max_iter=1000)
    got = pagerank.reference(indptr, indices,
                             dict(traffic("pr.incore"), max_iters=200))
    np.testing.assert_allclose(got, [want[v] for v in range(got.size)],
                               rtol=1e-9)


def test_work_functions_by_hand():
    # a triangle 0-1-2 and an isolated vertex 3: 3 edges, 6 arcs
    indptr = np.array([0, 2, 4, 6, 6])
    t = traffic("pr.incore")
    assert pagerank.arcs_per_trial(4, 6, t) == 6 * t["max_iters"]
    # per arc 4 B index + 4 B contribution, per vertex 3 x 4 B
    assert pagerank.least_bytes_per_iteration(4, 6) == 6 * 8 + 4 * 12
    assert indptr[-1] == 6


def test_compare_reads_l1_and_worst_relative_error():
    want = np.array([0.5, 0.25, 0.25])
    got = np.array([0.5, 0.25 * 1.01, 0.25 - 0.0025], np.float32)
    c = pagerank.compare(got, want)
    assert c["rank_l1"] == pytest.approx(0.005, rel=1e-5)
    assert c["rank_max_rel"] == pytest.approx(0.01, rel=1e-4)
    bad = pagerank.compare(np.array([np.nan, 0.5, 0.5]), want)
    assert bad["rank_l1"] == float("inf")
