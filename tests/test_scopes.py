"""The program names its own layers: ``jax.named_scope`` on every
compiled step (``sparse``, ``dense``, ``post``, ``fold``, PageRank's
``gather`` and ``scatter``), and ``repro.obs`` spans over the streamed
plan's main-thread waits (``stage_wait``, ``host_wait``) and its set-up
(``calibrate``, ``split_refresh``, ``plan_waves``, ``host_lane_build``).
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro import obs
from repro.algorithms import pagerank_algorithm
from repro.core import build_block_store, compile_plan, rmat


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable()
    yield
    obs.disable()


@pytest.fixture(scope="module")
def store():
    return build_block_store(rmat(8, 8, seed=3), 4)


def _op_names(compiled) -> set[str]:
    return set(re.findall(r'op_name="([^"]*)"', compiled.as_text()))


def _streamed(store, **kw):
    kw = dict(mode="sparse_only", share=False, memory_budget="16KB",
              rebalance_threshold=None, **kw)
    return compile_plan(pagerank_algorithm(max_iters=kw.pop("iters", 2),
                                           tol=0.0), store, **kw)


def _wave_args(plan):
    slab = plan._slabs[0]
    bufs = plan._put_slab(plan._assemble_runtime(slab, wave=0), wave=0)
    return slab, bufs


def _incore_names(store):
    alg = pagerank_algorithm(max_iters=2, tol=0.0)
    plan = compile_plan(alg, store, mode="sparse_only", share=False)
    b = next(iter(plan._bindings.values()))
    return _op_names(plan._step._jit.lower(
        b.context, alg.init_state(store), jnp.int32(0),
        b.run_dense).compile())


def _streamed_names(store):
    plan = _streamed(store, pipeline_depth=0)
    state = plan.alg.init_state(store)
    slab, bufs = _wave_args(plan)
    names = _op_names(plan._step._jit.lower(
        plan._wave_context(bufs), state, state, jnp.int32(0),
        slab.run_dense).compile())
    return names | _op_names(plan._post._jit.lower(
        plan._resident, state, jnp.int32(0)).compile())


def _mesh_names(store):
    mesh = Mesh(np.array(jax.devices()[:1]), ("blocks",))
    plan = _streamed(store, pipeline_depth=0, mesh=mesh)
    state = plan._put_replicated(plan.alg.init_state(store))
    slab, (slab_bufs, ex_leaves, ex_aux) = _wave_args(plan)
    return _op_names(plan._mesh_step._jit.lower(
        plan._resident, slab_bufs, ex_leaves, state, state, jnp.int32(0),
        slab.run_dense, ex_aux).compile())


@pytest.mark.parametrize("names, scopes", [
    (_incore_names, ("sparse/gather/", "sparse/scatter/", "/post/")),
    (_streamed_names, ("sparse/gather/", "sparse/scatter/", "/fold/",
                       "/post/")),
    (_mesh_names, ("sparse/gather/", "sparse/scatter/", "/fold/")),
], ids=["incore", "streamed", "mesh"])
def test_compiled_steps_carry_kernel_scopes(store, names, scopes):
    got = names(store)
    for scope in scopes:
        assert any(scope in n for n in got), (scope, sorted(got))
    # the scatter-add itself, which XLA sorts, sits in the scatter scope
    assert any(n.endswith("sparse/scatter/scatter-add") for n in got)


def _traced(store, **kw):
    with obs.tracing() as tr:
        res = _streamed(store, **kw).run()
    by: dict = {}
    for ev in tr.events():
        by.setdefault(ev.name, []).append(ev)
    want = _streamed(store, **kw).run()
    np.testing.assert_allclose(res.result, want.result, rtol=1e-6,
                               atol=1e-9)
    return res, by


def test_traced_streamed_run_names_its_main_thread_waits(store):
    """A fixed host split with pipelined staging: iteration 0 waits on
    the host lane, then calibrates; later iterations wait on the
    staging worker once per wave and on the host lane once."""
    res, by = _traced(store, iters=3, pipeline_depth=2, host_fraction=0.3)
    waves = res.schedule_stats["streaming"]["num_waves"]
    for name in ("stage_wait", "host_wait", "calibrate", "split_refresh"):
        assert {(ev.lane, ev.parent) for ev in by[name]} == {
            ("main", "iteration")}, name
    assert len(by["stage_wait"]) == 2 * waves
    assert len(by["host_wait"]) == 3
    assert all(ev.args["units"] > 0 for ev in by["host_wait"])
    assert [(ev.args["it"], ev.args["waves"]) for ev in by["calibrate"]] \
        == [(0, waves)]
    # a fixed split is never refreshed
    assert [ev.args["applied"] for ev in by["split_refresh"]] == [False]


def test_traced_streamed_run_names_its_set_up(store, monkeypatch):
    """The ``"auto"`` split, with the noise floor lowered, probes the
    host after the first calibration: the refresh re-packs the waves
    and builds the host lane inside its span, and iteration 1 calibrates
    the new waves after waiting on the host lane."""
    monkeypatch.setenv("REPRO_HETERO_NOISE_FLOOR_S", "0.00001")
    res, by = _traced(store, iters=2, pipeline_depth=2,
                      host_fraction="auto")
    assert res.schedule_stats["hetero"]["refreshes"] == 1
    assert [(ev.args["it"], ev.parent) for ev in by["calibrate"]] == [
        (0, "iteration"), (1, "iteration")]
    refresh = by["split_refresh"]
    assert [(ev.args["it"], ev.args["applied"], ev.lane) for ev in refresh] \
        == [(0, True, "main"), (1, False, "main")]
    initial = [ev for ev in by["plan_waves"] if ev.args["initial"]]
    repack = [ev for ev in by["plan_waves"] if not ev.args["initial"]]
    assert [ev.parent for ev in initial] == [None]   # in compile_plan
    assert [ev.parent for ev in repack] == ["split_refresh"]
    assert [ev.parent for ev in by["host_lane_build"]] == ["plan_waves"]
    assert [(ev.parent, ev.lane) for ev in by["host_wait"]] == [
        ("iteration", "main")]
    assert "stage_wait" not in by       # no pipelined iteration ran


def test_untraced_runs_record_no_span(store, monkeypatch):
    recorded = []
    monkeypatch.setattr(obs.Tracer, "record",
                        lambda self, *a, **k: recorded.append(a))
    monkeypatch.setenv("REPRO_HETERO_NOISE_FLOOR_S", "0.00001")
    _streamed(store, iters=3, pipeline_depth=2, host_fraction="auto").run()
    compile_plan(pagerank_algorithm(max_iters=2, tol=0.0), store,
                 mode="sparse_only", share=False).run()
    assert recorded == [] and obs.tracer() is None
    span = obs.span("split_refresh")
    with span as sp:
        sp.set(applied=True)            # the shared no-op takes attributes
    assert span is obs.span("other")
