"""The BFS yardstick (``bench/algorithms/bfs.py``) and the cell
``urand22.bfs.incore``: the reference against networkx, GAP's check
deciding ``correct`` so that planted faults of the timed path and the
control come out false, and the cell's metric readers.  Small graphs,
on the CPU, with the harness's look for a chip skipped."""
import dataclasses
import types

import networkx as nx
import numpy as np
import pytest

from bench import harness
from bench.algorithms import bfs as yard
from bench.csr import symmetric_csr
from test_bench_harness import no_chip_check, small_root  # noqa: F401
from test_bench_scoped import fake_run, xspace

CELL = "urand22.bfs.incore"
SEED = 2**31 + 9


def metric(name):
    """The reader of one of the cell's metrics, loaded as the harness
    loads it."""
    cell = harness.load_cell(harness.ROOT, CELL)
    mods = {**cell.metrics, **cell.trace_metrics}
    return mods[name][1]


def random_csr(scale, edges, seed):
    rng = np.random.default_rng(seed)
    n = 1 << scale
    src = rng.integers(0, n, edges, dtype=np.uint32)
    dst = rng.integers(0, n, edges, dtype=np.uint32)
    return symmetric_csr(src, dst, scale)


def traffic(**kw):
    return {"source": 0, "max_iters": 10_000, **kw}


@pytest.mark.parametrize("scale, edges, seed, source", [
    (6, 40, 1, 0), (6, 100, 5, 63), (7, 300, 2, 3), (7, 90, 6, 1),
    (8, 200, 3, 9), (8, 2000, 4, 200)])
def test_reference_depths_are_networkx_shortest_paths(scale, edges, seed,
                                                       source):
    indptr, indices = random_csr(scale, edges, seed)
    n = indptr.shape[0] - 1
    G = nx.Graph()
    G.add_nodes_from(range(n))
    rows = np.repeat(np.arange(n), np.diff(indptr))
    G.add_edges_from(zip(rows.tolist(), indices.tolist()))
    got = yard.reference(indptr, indices, traffic(source=source))
    lengths = nx.single_source_shortest_path_length(G, source)
    want = np.array([lengths.get(v, -1) for v in range(n)])
    np.testing.assert_array_equal(got.depth, want)
    assert got.levels == want.max() + 1   # the last level finds nothing
    assert got.indptr is indptr and got.indices is indices
    # the least-parent tree passes GAP's check
    out = dict(dist=np.where(want < 0, 2**31 - 1, want),
               parent=yard.min_parents(got))
    assert yard.check(out, got.levels, got, {}) == dict(
        depth_mismatches=0, bad_parents=0, levels_off=0)


def test_reference_stops_at_max_iters():
    indptr, indices = random_csr(7, 300, 2)
    full = yard.reference(indptr, indices, traffic())
    cut = yard.reference(indptr, indices, traffic(max_iters=2))
    assert cut.levels == 2
    np.testing.assert_array_equal(cut.depth,
                                  np.where(full.depth <= 2, full.depth, -1))


def test_check_counts_each_broken_parent():
    # a path 0-1-2-3 and a lone vertex 4
    indptr = np.array([0, 1, 3, 5, 6, 6])
    indices = np.array([1, 0, 2, 1, 3, 2], np.int32)
    want = yard.reference(indptr, indices, traffic())
    none = 2**31 - 1
    good = dict(dist=np.array([0, 1, 2, 3, none]),
                parent=np.array([0, 0, 1, 2, none]))
    assert yard.check(good, 4, want, {}) == dict(
        depth_mismatches=0, bad_parents=0, levels_off=0)

    def bad(**kw):
        out = {k: v.copy() for k, v in good.items()}
        for k, (v, x) in kw.items():
            out[k][v] = x
        return yard.check(out, 4, want, {})

    assert bad(parent=(3, 1))["bad_parents"] == 1   # not a neighbour
    assert bad(parent=(2, 3))["bad_parents"] == 1   # a neighbour, below
    assert bad(parent=(0, 1))["bad_parents"] == 1   # the root's own
    assert bad(parent=(4, 3))["bad_parents"] == 1   # unreached, a parent
    assert bad(parent=(2, none))["bad_parents"] == 1
    assert bad(dist=(4, 4))["depth_mismatches"] == 1
    assert bad(dist=(3, none))["depth_mismatches"] == 1
    assert yard.check(good, 3, want, {})["levels_off"] == 1
    assert yard.check(dict(good, dist=good["dist"][:4]), 4, want,
                      {})["depth_mismatches"] == float("inf")


def run(root, seconds=0.3, seed=SEED, algorithm=yard):
    cell = harness.load_cell(str(root), CELL)
    cell = dataclasses.replace(cell, algorithm=algorithm)
    return harness.run_cell(cell, seed, seconds, False, 0.0, str(root))


def test_sound_run_is_correct(tmp_path, no_chip_check):  # noqa: F811
    res = run(small_root(tmp_path))
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert set(res["metrics"]) == {"teps", "setup_s"}   # no HBM on a CPU
    checks = res["checks"]
    assert set(checks) == {"depth_mismatches", "bad_parents", "levels_off",
                           "recoveries", "fallbacks"}
    assert checks["levels_off"]["value"] == 0
    for c in checks.values():
        assert c["value"] <= c["limit"]


def small_search(root):
    """The reference search of the cell's graph at the small root's
    scale and :data:`SEED`."""
    cell = harness.load_cell(str(root), CELL)
    indptr, indices = cell.generator.generate(cell.config, SEED)
    return yard.reference(indptr, indices, cell.traffic)


def _one_level_short(monkeypatch, root):
    levels = small_search(root).levels
    make = yard.make
    monkeypatch.setattr(yard, "make", lambda t: make(
        dict(t, max_iters=levels - 1)))


def _parent_not_a_neighbour(monkeypatch, root):
    make = yard.make

    def altered(t):
        alg = make(t)

        def finalize(store, state):
            out = alg.finalize(store, state)
            dist, parent = out["dist"].copy(), out["parent"].copy()
            g = store.graph
            v = int(np.flatnonzero(dist == 2)[0])
            nbrs = set(g.indices[g.indptr[v]:g.indptr[v + 1]].tolist())
            u = next(int(u) for u in np.flatnonzero(dist == 1)
                     if u not in nbrs)
            parent[v] = u
            return dict(dist=dist, parent=parent)
        return dataclasses.replace(alg, finalize=finalize)

    monkeypatch.setattr(yard, "make", altered)


def _pull_keeps_its_parents(monkeypatch, root):
    import importlib

    from repro.core import engine

    prog = importlib.import_module("repro.algorithms.bfs")

    def keep(ctx, state, it):
        return state

    monkeypatch.setattr(prog, "_kernel_sparse_pull", keep)
    monkeypatch.setattr(prog, "_kernel_dense_pull", keep)
    # fresh compiled steps, so the planted kernel is traced
    monkeypatch.setattr(engine, "_STEP_CACHE", {})


FAULTS = {"one_level_short": _one_level_short,
          "parent_not_a_neighbour": _parent_not_a_neighbour,
          "pull_keeps_its_parents": _pull_keeps_its_parents}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_planted_fault_is_not_correct(tmp_path, no_chip_check,  # noqa: F811
                                      monkeypatch, fault):
    root = small_root(tmp_path)
    FAULTS[fault](monkeypatch, root)
    res = run(root)
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


def test_pull_engages_from_the_second_level(tmp_path):
    """At scale 9 (512 vertices, 24 x the root's degree above 512) the
    controller pulls from the second level on, so the planted pull
    fault above is in the timed path."""
    from repro.core import Graph, build_block_store, compile_plan

    root = small_root(tmp_path)
    cell = harness.load_cell(str(root), CELL)
    indptr, indices = cell.generator.generate(cell.config, SEED)
    store = build_block_store(Graph(indptr=indptr, indices=indices,
                                    n=indptr.shape[0] - 1), 4)
    res = compile_plan(yard.make(cell.traffic), store,
                       **cell.traffic["executor"]).run()
    decisions = res.schedule_stats["direction"]["decisions"]
    assert decisions[:2] == ["push", "pull"]
    assert res.iterations == small_search(root).levels


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_control_fails_depth_mismatches(tmp_path, seed):
    cell = harness.load_cell(str(small_root(tmp_path, scale=11)), CELL)
    indptr, indices = cell.generator.generate(cell.config, seed)
    want = yard.reference(indptr, indices, cell.traffic)
    out = yard.control(indptr, indices, cell.traffic)
    run = harness.Run(cell=cell, n=indptr.shape[0] - 1,
                      m=int(indices.shape[0]),
                      trials=[harness.Trial(0.0, 0.0, want.levels, {})])
    checks, failed = harness.judge(cell, run, [out], want, fallbacks=0)
    assert not harness.is_correct(checks, failed)
    c = checks["depth_mismatches"]
    assert c["value"] > c["limit"]
    assert checks["levels_off"]["value"] == 0


def _trial(stats):
    return harness.Trial(0.0, 1.0, 5, stats)


def test_pull_level_share():
    cell = harness.load_cell(harness.ROOT, CELL)
    direction = dict(decisions=["push", "push", "pull", "pull", "push"],
                     pull_iterations=2)
    r = harness.Run(cell=cell, n=8, m=16,
                    trials=[_trial({}), _trial(dict(direction=direction))])
    assert metric("pull_level_share").read(r) == pytest.approx(40.0)
    r.trials.append(_trial({}))     # a plan without a direction block
    assert metric("pull_level_share").read(r) is None
    assert metric("pull_level_share").read(
        dataclasses.replace(r, trials=[])) is None


def test_bfs_roofline():
    cell = harness.load_cell(harness.ROOT, CELL)
    r = harness.Run(cell=cell, n=1000, m=30000,
                    trials=[_trial({}), _trial({})],
                    peaks=dict(hbm_bytes_per_s=1e9),
                    trace=types.SimpleNamespace(compute_s=0.5))
    # (4 x 30000 + 12 x 1000) B x 2 trials over 1e9 B/s x 0.5 s
    assert metric("bfs_roofline").read(r) == pytest.approx(
        100 * 264000 / 5e8)
    assert metric("bfs_roofline").read(
        dataclasses.replace(r, trace=None)) is None
    r.trace.compute_s = 0.0
    assert metric("bfs_roofline").read(r) is None


@pytest.mark.parametrize("name, want", [
    ("gather_s_per_iter.bfs", 10e-6), ("scatter_s_per_iter.bfs", 5e-6)])
def test_scope_readers(tmp_path, monkeypatch, name, want):
    run = fake_run(tmp_path, monkeypatch, xspace().SerializeToString())
    assert metric(name).read(run) == pytest.approx(want)
    assert metric(name).read(types.SimpleNamespace(trace=None)) is None
    run.trace.spans = {}
    assert metric(name).read(run) is None       # no level traced


@pytest.mark.parametrize("name", ["gather_s_per_iter.bfs",
                                  "scatter_s_per_iter.bfs"])
def test_scope_readers_read_nothing_without_the_scope(tmp_path, monkeypatch,
                                                      name):
    """A program whose BFS names no ``gather`` or ``scatter`` scope (only
    ``sparse`` and ``post``) gives no reading, not 0."""
    msg = xspace()
    plane = msg.planes[0]
    for meta in plane.event_metadata.values():
        for st in meta.stats:
            st.str_value = st.str_value.replace("/gather", "")
            st.ref_value = 0
    run = fake_run(tmp_path, monkeypatch, msg.SerializeToString())
    assert metric(name).read(run) is None
