#!/usr/bin/env python3
"""Drive the block graph engine's main path once on a TPU and check it.

    python chip_smoke.py              # one chip, every phase below
    python chip_smoke.py --chips 4    # the 4-chip streamed mesh path only

One chip, all phases in this one process:

1. device: require a TPU (never fall back to the CPU);
2. in-core: a Graph500 Kronecker graph at scale 22 (``--scale``), edge
   factor 16, blocked p=8, through PageRank, BFS and Shiloach-Vishkin
   CC; PageRank is capped at ``PR_ITERS`` iterations to fit the time
   limit;
3. streamed: the same graph under a memory budget of a fifth of its edge
   bytes (at least 4 waves), results equal to the in-core run;
4. serving: 8 personalised-PageRank queries through GraphServer, each
   equal to the same query run alone;
5. pallas: a small Kronecker graph whose blocks fit a 512-wide tile, so
   the Pallas kernels run natively (PageRank, pull BFS, triangles).

``--chips 4`` runs PageRank and BFS through the streamed executor over a
1-D mesh of four chips and compares them with the one-chip in-core run.

Every result is checked against a plain numpy reference written below,
independent of the engine.  Any failure, kernel fallback or recovery of
the retry ladder exits non-zero without the final line.  On success the
last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import importlib.metadata
import json
import os
import sys
import time
import traceback

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

KRON_SCALE = 22
EDGE_FACTOR = 16
KRON_P = 8
PALLAS_SCALE = 13
PALLAS_P = 32
STREAM_WAVES_MIN = 4
SERVE_QUERIES = 8
# PageRank iterations at scale 22: each one is a 1.3e8-arc scatter, so
# the smoke caps them to stay inside its time limit (the reference runs
# the same count)
PR_ITERS = 2
# PageRank is float32 scatter-adds against a float64 reference: the L1
# distance of the two rank vectors after the same number of iterations
PR_L1_TOL = 1e-4
PR_SUM_TOL = 1e-3
# a batched serving answer against the same query run alone
SERVE_L1_TOL = 1e-6
UNVISITED = 2**31 - 1


class SmokeError(RuntimeError):
    pass


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeError(what)


T0 = time.perf_counter()


def say(phase: str, **kv) -> None:
    print(f"[{phase}] t={time.perf_counter() - T0:.1f}s "
          + " ".join(f"{k}={v}" for k, v in kv.items()), flush=True)


# ----------------------------------------------------------------------
# numpy references: CSR (indptr, indices) of a symmetric graph in,
# float64 / int64 out; nothing here imports the engine
def _row_starts(indptr):
    deg = np.diff(indptr)
    nz = deg > 0
    return deg, nz, indptr[:-1][nz]


def ref_pagerank(indptr, indices, iters, seed=None, damping=0.85):
    """PageRank, or personalised to restart at vertex ``seed``."""
    n = indptr.shape[0] - 1
    deg, nz, starts = _row_starts(indptr)
    inv = 1.0 / np.maximum(deg, 1)
    if seed is None:
        tele = np.full(n, 1.0 / n)
    else:
        tele = np.zeros(n)
        tele[seed] = 1.0
    rank = tele.copy()
    for _ in range(iters):
        contrib = rank * inv
        acc = np.zeros(n)
        acc[nz] = np.add.reduceat(contrib[indices], starts)
        dangling = rank[deg == 0].sum()
        rank = (1 - damping) * tele + damping * (acc + dangling * tele)
    return rank


def ref_bfs(indptr, indices, root):
    """Levels, and the smallest neighbour one level up as parent."""
    n = indptr.shape[0] - 1
    dist = np.full(n, UNVISITED, np.int64)
    dist[root] = 0
    frontier = np.asarray([root], np.int64)
    level = 0
    while frontier.size:
        lo, hi = indptr[frontier], indptr[frontier + 1]
        cnt = hi - lo
        offs = np.repeat(lo - np.cumsum(cnt) + cnt, cnt)
        nbr = indices[offs + np.arange(cnt.sum())]
        nbr = np.unique(nbr[dist[nbr] == UNVISITED])
        level += 1
        dist[nbr] = level
        frontier = nbr.astype(np.int64)
    deg, nz, starts = _row_starts(indptr)
    row = np.repeat(np.arange(n), deg)
    up = (dist[indices] == dist[row] - 1) & (dist[row] != UNVISITED)
    cand = np.where(up, indices, UNVISITED)
    parent = np.full(n, UNVISITED, np.int64)
    parent[nz] = np.minimum.reduceat(cand, starts)
    parent[root] = root
    return dist, parent


def ref_components(indptr, indices):
    """Min-label propagation with pointer jumping: min vertex id per
    component."""
    n = indptr.shape[0] - 1
    _, nz, starts = _row_starts(indptr)
    lab = np.arange(n)
    while True:
        new = lab.copy()
        new[nz] = np.minimum(lab[nz], np.minimum.reduceat(lab[indices], starts))
        while True:
            jumped = new[new]
            if np.array_equal(jumped, new):
                break
            new = jumped
        if np.array_equal(new, lab):
            return lab
        lab = new


def canonical_labels(labels):
    """Relabel each class by its smallest member (CC up to relabelling)."""
    labels = np.asarray(labels)
    _, inv = np.unique(labels, return_inverse=True)
    first = np.full(inv.max() + 1, labels.shape[0])
    np.minimum.at(first, inv, np.arange(labels.shape[0]))
    return first[inv]


def ref_triangles(indptr, indices):
    """Triangles of a symmetric graph: orient by (degree, id), then test
    every 2-path u->v->w for the closing arc u->w."""
    n = indptr.shape[0] - 1
    deg = np.diff(indptr)
    rank = np.empty(n, np.int64)
    rank[np.lexsort((np.arange(n), deg))] = np.arange(n)
    src = rank[np.repeat(np.arange(n), deg)]
    dst = rank[indices]
    keep = src < dst
    key = np.sort(src[keep] * n + dst[keep])
    s, d = key // n, key % n
    optr = np.zeros(n + 1, np.int64)
    np.cumsum(np.bincount(s, minlength=n), out=optr[1:])
    cnt = optr[d + 1] - optr[d]
    offs = np.repeat(optr[d] - np.cumsum(cnt) + cnt, cnt)
    w = d[offs + np.arange(cnt.sum())]
    q = np.repeat(s, cnt) * n + w
    pos = np.minimum(np.searchsorted(key, q), key.size - 1)
    return int(np.count_nonzero(key[pos] == q))


# ----------------------------------------------------------------------
_PR_REFS: dict = {}


def check_pagerank(phase, ranks, indptr, indices, iters, seed=None):
    key = (id(indptr), iters, seed)
    if key not in _PR_REFS:
        _PR_REFS[key] = ref_pagerank(indptr, indices, iters, seed)
    want = _PR_REFS[key]
    got = np.asarray(ranks, np.float64)
    l1 = float(np.abs(got - want).sum())
    total = float(got.sum())
    say(phase, pagerank_l1_vs_numpy=l1, pagerank_sum=total, iterations=iters)
    check(np.isfinite(got).all(), f"{phase}: non-finite PageRank")
    check(l1 <= PR_L1_TOL, f"{phase}: PageRank L1 {l1} > {PR_L1_TOL}")
    check(abs(total - 1.0) <= PR_SUM_TOL, f"{phase}: PageRank sum {total}")


def check_bfs(phase, out, ref):
    dist, parent = ref
    check(np.array_equal(np.asarray(out["dist"], np.int64), dist),
          f"{phase}: BFS levels differ from numpy")
    check(np.array_equal(np.asarray(out["parent"], np.int64), parent),
          f"{phase}: BFS parents differ from numpy")
    reached = int((dist != UNVISITED).sum())
    say(phase, bfs_levels="exact", reached=reached,
        depth=int(dist[dist != UNVISITED].max()))


def check_components(phase, labels, ref):
    check(np.array_equal(canonical_labels(labels), ref),
          f"{phase}: CC labels differ from numpy")
    say(phase, cc_labels="exact", components=int(np.unique(ref).size))


def no_recovery(phase, res):
    check("resilience" not in res.schedule_stats,
          f"{phase}: a recovery fired: {res.schedule_stats.get('resilience')}")


def kron_algorithms(root):
    """(name, factory) of the scale-22 algorithms; factories take the
    algorithm's keyword options."""
    from repro.algorithms import bfs_algorithm, pagerank_algorithm, sv_algorithm

    return (("pagerank",
             lambda **kw: pagerank_algorithm(**{"max_iters": PR_ITERS, **kw})),
            ("bfs", lambda **kw: bfs_algorithm(root, **kw)),
            ("cc", lambda **kw: sv_algorithm(**kw)))


def warm_run(phase, store, make):
    """Compile the step with a one-iteration run (set-up), then time one
    full run of a fresh plan that reuses it; both end fully synced."""
    from repro.core import compile_plan

    t0 = time.perf_counter()
    warmup = compile_plan(make(max_iters=1), store)
    no_recovery(phase, warmup.run())
    del warmup
    gc.collect()
    t1 = time.perf_counter()
    plan = compile_plan(make(), store)
    t2 = time.perf_counter()
    res = plan.run()
    t3 = time.perf_counter()
    no_recovery(phase, res)
    say(phase, compile_and_one_iteration_s=t1 - t0, compile_plan_s=t2 - t1,
        warm_run_s=t3 - t2, iterations=res.iterations,
        dense_tasks=res.schedule_stats["dense_tasks"])
    return res


# ----------------------------------------------------------------------
def phase_device():
    import jax

    devs = jax.devices()
    d = devs[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "absent"
    say("device", platform=d.platform, kind=repr(d.device_kind),
        count=len(devs), jax=jax.__version__, libtpu=libtpu)
    check(d.platform == "tpu", f"no TPU: JAX reports platform {d.platform!r}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def build_kron(scale, seed):
    from repro.core import build_block_store, rmat

    t0 = time.perf_counter()
    g = rmat(scale, EDGE_FACTOR, seed=seed)
    t1 = time.perf_counter()
    store = build_block_store(g, KRON_P)
    t2 = time.perf_counter()
    edge_bytes = int(store.m) * (4 + 4 + 4 + 1 + 1)
    say("setup", graph=f"rmat({scale},{EDGE_FACTOR})", n=g.n, arcs=g.m,
        edge_bytes=edge_bytes, graph_build_s=t1 - t0, block_build_s=t2 - t1)
    return g, store


def kron_references(g, components=True):
    t0 = time.perf_counter()
    root = int(np.argmax(np.diff(g.indptr)))
    refs = dict(root=root, bfs=ref_bfs(g.indptr, g.indices, root))
    if components:
        refs["cc"] = ref_components(g.indptr, g.indices)
    say("reference", bfs_root=root, numpy_s=time.perf_counter() - t0)
    return refs


def phase_incore(g, store, refs):
    import jax

    out = {name: warm_run(f"in-core/{name}", store, make)
           for name, make in kron_algorithms(refs["root"])}
    check_pagerank("in-core/pagerank", out["pagerank"].result, g.indptr,
                   g.indices, out["pagerank"].iterations)
    check_bfs("in-core/bfs", out["bfs"].result, refs["bfs"])
    check_components("in-core/cc", out["cc"].result, refs["cc"])
    stats = jax.devices()[0].memory_stats() or {}
    say("in-core", peak_bytes_in_use=stats.get("peak_bytes_in_use",
                                                "not reported"))
    return {k: v.result for k, v in out.items()}


def phase_streamed(g, store, refs, incore):
    from repro.core import compile_plan

    edge_bytes = int(store.m) * (4 + 4 + 4 + 1 + 1)
    # a fifth of the edge bytes packs about 11 waves; the host lane may
    # take tasks off the device and merge waves, so keep a margin above 4
    budget = edge_bytes // 5
    for name, make in kron_algorithms(refs["root"]):
        phase = f"streamed/{name}"
        t0 = time.perf_counter()
        plan = compile_plan(make(), store, memory_budget=budget)
        t1 = time.perf_counter()
        res = plan.run()
        t2 = time.perf_counter()
        no_recovery(phase, res)
        st = res.schedule_stats["streaming"]
        het = res.schedule_stats["hetero"]
        say(phase, compile_plan_s=t1 - t0, run_s=t2 - t1,
            iterations=res.iterations, num_waves=st["num_waves"],
            budget_bytes=st["budget_bytes"],
            max_wave_bytes=max(st["bytes_per_wave"]),
            dense_tasks=res.schedule_stats["dense_tasks"],
            host_tasks_executed=het["host_tasks_executed"],
            phase_seconds=st["phase_seconds"])
        check(st["num_waves"] >= STREAM_WAVES_MIN,
              f"{phase}: {st['num_waves']} waves < {STREAM_WAVES_MIN}")
        check(all(b <= st["budget_bytes"] for b in st["bytes_per_wave"]),
              f"{phase}: a wave staged more than the budget")
        if name == "pagerank":
            check_pagerank(phase, res.result, g.indptr, g.indices,
                           res.iterations)
        elif name == "bfs":
            check(all(np.array_equal(res.result[k], incore["bfs"][k])
                      for k in ("dist", "parent")),
                  f"{phase}: differs from the in-core run")
            check_bfs(phase, res.result, refs["bfs"])
        else:
            check(np.array_equal(res.result, incore["cc"]),
                  f"{phase}: differs from the in-core run")
            check_components(phase, res.result, refs["cc"])
        del plan, res
        gc.collect()


def pallas_native() -> bool:
    from repro.kernels import ops

    return ops._interpret() is False


def _step_hlo(plan, state, direction="push"):
    """StableHLO of the plan's own compiled step, for the custom-call
    check (the step is jitted once per direction)."""
    import jax.numpy as jnp

    step = plan._steps[direction]._jit
    return step.lower(plan.context, state, jnp.int32(0), True).as_text()


def phase_pallas(seed):
    from repro.algorithms import (bfs_algorithm, orient_dag,
                                  pagerank_algorithm, tc_algorithm)
    from repro.core import build_block_store, compile_plan, rmat

    check(pallas_native(), "Pallas would run in interpret mode")
    g = rmat(PALLAS_SCALE, EDGE_FACTOR, seed=seed + 1)
    store = build_block_store(g, PALLAS_P)
    dag_store = build_block_store(orient_dag(g), PALLAS_P)
    root = int(np.argmax(np.diff(g.indptr)))
    cases = (
        ("pagerank", lambda: pagerank_algorithm(), store, None),
        ("bfs-pull", lambda: bfs_algorithm(root), store, "pull"),
        ("tc", lambda: tc_algorithm(), dag_store, None),
    )
    results = {}
    for name, make, st, direction in cases:
        phase = f"pallas/{name}"
        by_backend = {}
        for backend in ("pallas", "xla"):
            plan = compile_plan(make(), st, backend=backend,
                                direction=direction)
            res = plan.run()
            no_recovery(phase, res)
            by_backend[backend] = res
            if backend == "pallas":
                check(plan.backend == "pallas", f"{phase}: backend fell back")
                dense = res.schedule_stats["dense_tasks"]
                check(dense > 0, f"{phase}: no dense tasks, kernel not run")
                hlo = _step_hlo(plan, plan.alg.init_state(st),
                                direction or "push")
                check("tpu_custom_call" in hlo,
                      f"{phase}: no Mosaic kernel in the compiled step")
                say(phase, dense_tasks=dense, tile_dim=plan.schedule.tile_dim,
                    tpu_custom_call=True, run_s=res.seconds)
        results[name] = by_backend
    pr = results["pagerank"]
    check_pagerank("pallas/pagerank", pr["pallas"].result, g.indptr, g.indices,
                   pr["pallas"].iterations)
    check_pagerank("pallas/pagerank-xla", pr["xla"].result, g.indptr,
                   g.indices, pr["xla"].iterations)
    diff = float(np.abs(pr["pallas"].result - pr["xla"].result).sum())
    say("pallas/pagerank", l1_pallas_vs_xla=diff)
    check(diff <= PR_L1_TOL, f"pallas/pagerank: pallas vs xla L1 {diff}")
    bfs = results["bfs-pull"]
    ref = ref_bfs(g.indptr, g.indices, root)
    for backend in ("pallas", "xla"):
        check_bfs(f"pallas/bfs-pull-{backend}", bfs[backend].result, ref)
    tri = ref_triangles(g.indptr, g.indices)
    got = {b: int(results["tc"][b].result) for b in ("pallas", "xla")}
    say("pallas/tc", triangles=got["pallas"], xla=got["xla"], numpy=tri)
    check(got["pallas"] == tri and got["xla"] == tri,
          f"pallas/tc: {got} != numpy {tri}")


def phase_serving(g, store, seed):
    from repro.algorithms import pagerank_algorithm
    from repro.serve import GraphServer, Query

    rng = np.random.default_rng(seed)
    deg = np.diff(g.indptr)
    seeds = rng.choice(np.flatnonzero(deg > 0), SERVE_QUERIES, replace=False)
    server = GraphServer()
    server.register_graph("kron", store)
    t0 = time.perf_counter()
    params = [dict(seeds=[int(s)], max_iters=PR_ITERS) for s in seeds]
    uids = [server.submit(Query("kron", "pagerank", p)) for p in params]
    done = server.drain()
    t1 = time.perf_counter()
    worst_solo, bit_identical = 0.0, True
    for uid, s, p in zip(uids, seeds, params):
        q = done[uid]
        check(q.status == "done", f"serving: query {uid} {q.status} {q.reason}")
        no_recovery("serving", q)
        plan = server.plan_for("kron", "pagerank", p)
        solo = plan.run(state=pagerank_algorithm(**p).init_state(store))
        no_recovery("serving", solo)
        l1 = float(np.abs(q.result - solo.result).sum())
        worst_solo = max(worst_solo, l1)
        bit_identical &= bool(np.array_equal(q.result, solo.result))
        check(l1 <= SERVE_L1_TOL, f"serving: query {uid} vs solo L1 {l1}")
        if uid == uids[0]:
            check_pagerank(f"serving/q{uid}", q.result, g.indptr, g.indices,
                           solo.iterations, int(s))
    st = server.stats()
    say("serving", queries=SERVE_QUERIES, drain_s=t1 - t0,
        batches=st.get("batches"), worst_l1_vs_solo=worst_solo,
        bit_identical_to_solo=bit_identical)


def phase_mesh(g, store, refs):
    import jax
    from jax.sharding import Mesh

    from repro.core import compile_plan

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = Mesh(np.array(devs[:4]), ("blocks",))
    edge_bytes = int(store.m) * (4 + 4 + 4 + 1 + 1)
    budget = edge_bytes // 16
    for name, make in kron_algorithms(refs["root"])[:2]:
        one = compile_plan(make(), store).run()
        no_recovery(f"mesh/{name}", one)
        plan = compile_plan(make(), store, memory_budget=budget, mesh=mesh)
        t0 = time.perf_counter()
        res = plan.run()
        t1 = time.perf_counter()
        no_recovery(f"mesh/{name}", res)
        st = res.schedule_stats["streaming"]
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devs[:4]]
        say(f"mesh/{name}", run_s=t1 - t0, num_waves=st["num_waves"],
            mesh_devices=st["mesh_devices"], budget_bytes=st["budget_bytes"],
            max_per_device_bytes=max(st["per_device_bytes"]),
            peak_bytes_in_use=peaks)
        check(st["mesh_devices"] == 4, f"mesh/{name}: not on 4 devices")
        check(all(b <= st["budget_bytes"] for b in st["per_device_bytes"]),
              f"mesh/{name}: a device slab exceeds the budget")
        check(all(p > 0 for p in peaks), f"mesh/{name}: a device held nothing")
        if name == "pagerank":
            diff = float(np.abs(res.result - one.result).sum())
            say("mesh/pagerank", l1_vs_one_chip=diff)
            check(diff <= PR_L1_TOL, f"mesh/pagerank: L1 vs one chip {diff}")
            check_pagerank("mesh/pagerank", res.result, g.indptr, g.indices,
                           res.iterations)
        else:
            check(all(np.array_equal(res.result[k], one.result[k])
                      for k in ("dist", "parent")),
                  "mesh/bfs: differs from the one-chip run")
            check_bfs("mesh/bfs", res.result, refs["bfs"])
        del plan, res, one
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scale", type=int, default=KRON_SCALE,
                    help="Kronecker scale of the main graph")
    args = ap.parse_args(argv)
    try:
        device = phase_device()
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.core.compilecache import use_persistent_cache

        say("setup", compile_cache=use_persistent_cache(ROOT))
        g, store = build_kron(args.scale, args.seed)
        refs = kron_references(g, components=args.chips == 1)
        if args.chips == 4:
            phase_mesh(g, store, refs)
        else:
            incore = phase_incore(g, store, refs)
            phase_streamed(g, store, refs, incore)
            phase_serving(g, store, args.seed)
            phase_pallas(args.seed)
    except Exception as e:  # noqa: BLE001 — any failure fails the smoke
        traceback.print_exc()
        print(f"FAILED: {type(e).__name__}: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
