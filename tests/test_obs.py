"""Unified telemetry layer: tracer, metrics registry, exporters, and
their integration with the executors.

Four layers of coverage:

* tracer units — no-op when disabled, span attributes and nesting,
  ring-buffer bounds, concurrent recording from many threads;
* metrics units — counter/gauge/histogram semantics, the registry's
  create-on-first-use contract, and the bounded histogram's
  within-one-bucket percentile accuracy against exact order statistics;
* exporter units — Chrome-trace structure, per-device lane expansion,
  validation teeth, and the run-report schema's byte-compatibility
  promise;
* integration — the exact per-wave span tree of a ≥4-wave streamed run
  (synchronous pipeline for determinism), spans from the background
  staging worker under ``pipeline_depth=2``, collective spans appearing
  only under a mesh, the serving path's bounded latency percentiles,
  and an 8-device subprocess whose exported timeline carries one lane
  per device plus the staging lane.
"""
import json
import os
import subprocess
import sys
import textwrap
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import build_block_store, compile_plan, rmat
from repro.core.stream import StreamingPlan
from repro.algorithms import pagerank_algorithm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled."""
    obs.disable()
    yield
    obs.disable()


# ---------------------------------------------------------------- tracer
def test_disabled_tracer_is_noop():
    assert not obs.enabled()
    assert obs.tracer() is None
    s1 = obs.span("anything", wave=1)
    s2 = obs.span("other")
    assert s1 is s2                     # the shared no-op singleton
    with s1:
        pass
    assert obs.add_span("x", 0.1) is None
    assert obs.instant("x") is None
    with pytest.raises(RuntimeError):
        obs.export.chrome_trace()       # nothing to export


def test_span_records_name_lane_args_and_duration():
    with obs.tracing() as tr:
        with obs.span("work", lane="staging", wave=3, bytes=128):
            pass
        (ev,) = tr.events()
    assert ev.name == "work"
    assert ev.lane == "staging"
    assert ev.args == dict(wave=3, bytes=128)
    assert ev.dur_ns >= 0
    assert ev.end_ns == ev.start_ns + ev.dur_ns


def test_span_nesting_tracks_depth_and_parent():
    with obs.tracing() as tr:
        with obs.span("outer"):
            with obs.span("inner"):
                with obs.span("leaf"):
                    pass
        by_name = {ev.name: ev for ev in tr.events()}
    assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
    assert by_name["inner"].depth == 1 and by_name["inner"].parent == "outer"
    assert by_name["leaf"].depth == 2 and by_name["leaf"].parent == "inner"
    # inner spans close first: recorded leaf-outward
    assert [ev.name for ev in tr.events()] == ["leaf", "inner", "outer"]


def test_default_lane_derives_from_thread():
    with obs.tracing() as tr:
        with obs.span("main_side"):
            pass
        t = threading.Thread(target=lambda: tr.record(
            "worker_side", 0, 1), name="bg-worker")
        t.start()
        t.join()
        lanes = {ev.name: ev.lane for ev in tr.events()}
    assert lanes == dict(main_side="main", worker_side="bg-worker")


def test_ring_buffer_bounds_and_dropped_count():
    with obs.tracing(capacity=8) as tr:
        for i in range(20):
            obs.instant("e", i=i)
        assert len(tr) == 8
        assert tr.dropped == 12
        # the retained spans are the most recent, oldest first
        assert [ev.args["i"] for ev in tr.events()] == list(range(12, 20))
        tr.clear()
        assert len(tr) == 0 and tr.dropped == 0


def test_tracer_thread_safety():
    """N threads hammering one tracer: every span lands, none tear."""
    threads, per = 8, 500
    with obs.tracing(capacity=threads * per) as tr:
        def work(tid):
            for i in range(per):
                with obs.span("t", tid=tid, i=i):
                    pass

        ts = [threading.Thread(target=work, args=(k,)) for k in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        evs = tr.events()
        assert tr.dropped == 0
    assert len(evs) == threads * per
    for k in range(threads):
        mine = [ev.args["i"] for ev in evs if ev.args["tid"] == k]
        assert sorted(mine) == list(range(per))


def test_tracing_context_restores_previous_state():
    outer = obs.enable(capacity=16)
    try:
        with obs.tracing() as inner:
            assert obs.tracer() is inner
            assert inner is not outer
        assert obs.tracer() is outer
    finally:
        obs.disable()


@pytest.mark.parametrize("default", [False, True])
def test_profiler_bridge_keys_the_compile_cache_on_metadata(default):
    """While spans reach the profiler, a step from JAX's persistent cache
    carries the names its own code gave; without the bridge the key, and
    the cache hits, stay as JAX's default."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, default)
    try:
        obs.enable()
        assert getattr(jax.config, flag) is default
        obs.disable()
        obs.enable(jax_annotations=True)
        assert getattr(jax.config, flag) is True
        with obs.tracing(jax_annotations=False):
            assert getattr(jax.config, flag) is default
        assert getattr(jax.config, flag) is True
        obs.enable(jax_annotations=True)        # idempotent
        obs.disable()
        assert getattr(jax.config, flag) is default
        with obs.tracing(jax_annotations=True):
            assert getattr(jax.config, flag) is True
        assert getattr(jax.config, flag) is default
    finally:
        obs.disable()
        jax.config.update(flag, before)


# --------------------------------------------------------------- metrics
def test_counter_and_gauge_semantics():
    reg = obs.MetricsRegistry()
    c = reg.counter("c")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("g")
    g.set(5)
    g.set(2)
    assert g.value == 2 and g.high_water == 5
    g.set_max(1)                        # ratchet never lowers
    assert g.value == 2
    assert reg.counter("c") is c        # create-on-first-use returns same
    with pytest.raises(TypeError):
        reg.gauge("c")                  # name registered as another type


def test_histogram_percentiles_within_one_bucket():
    """The fixed-bucket estimate lands in the same bucket as the exact
    order statistic, so |estimate - exact| <= that bucket's width."""
    rng = np.random.default_rng(7)
    values = rng.uniform(1e-4, 2.0, size=500)
    h = obs.Histogram("lat")
    for v in values:
        h.observe(v)
    edges = np.asarray(h.edges)
    for q in (50, 95, 99):
        exact = float(np.percentile(values, q, method="inverted_cdf"))
        est = h.percentile(q)
        b = int(np.searchsorted(edges, exact, side="right"))
        lo = edges[b - 1] if b > 0 else h.min
        hi = edges[b] if b < len(edges) else h.max
        assert abs(est - exact) <= hi - lo
    assert h.percentile(50) <= h.percentile(95) <= h.percentile(99)
    assert h.min <= h.percentile(0) and h.percentile(100) <= h.max


def test_histogram_memory_constant_in_observations():
    h = obs.Histogram("lat")
    buckets = len(h._counts)
    for v in np.linspace(1e-5, 10.0, 10_000):
        h.observe(v)
    assert len(h._counts) == buckets    # no per-observation storage
    assert h.count == 10_000
    snap = h.snapshot()
    assert set(snap) == {"count", "sum", "min", "max", "p50", "p95", "p99"}


def test_registry_snapshot_flat_dict():
    reg = obs.MetricsRegistry()
    reg.counter("a.b").inc(3)
    reg.gauge("a.g").set(1.5)
    reg.histogram("a.h").observe(0.25)
    snap = reg.snapshot()
    assert snap["a.b"] == 3
    assert snap["a.g"] == 1.5
    assert snap["a.h"]["count"] == 1


# --------------------------------------------------------------- export
def test_chrome_trace_structure_and_device_lane_expansion():
    with obs.tracing() as tr:
        with obs.span("compute", lane="device", wave=0, devices=3):
            pass
        with obs.span("assemble", lane="staging", wave=0):
            pass
        obj = obs.export.chrome_trace()
        info = obs.export.validate_chrome_trace(
            json.dumps(obj),
            require_lanes=("staging", "device/0", "device/1", "device/2"),
            require_phases=("compute", "assemble"))
    # the device-lane span is mirrored onto every device's track
    assert info["span_counts"]["compute"] == 3
    assert info["span_counts"]["assemble"] == 1
    assert tr.events()                  # buffer untouched by export


def test_validate_chrome_trace_teeth():
    with pytest.raises(ValueError, match="traceEvents"):
        obs.export.validate_chrome_trace({})
    bad_ts = dict(traceEvents=[
        dict(ph="X", pid=1, tid=1, name="a", ts=100.0, dur=1.0, args={}),
        dict(ph="X", pid=1, tid=1, name="b", ts=50.0, dur=1.0, args={}),
    ])
    with pytest.raises(ValueError, match="monotonic"):
        obs.export.validate_chrome_trace(bad_ts)
    neg = dict(traceEvents=[
        dict(ph="X", pid=1, tid=1, name="a", ts=1.0, dur=-2.0, args={}),
    ])
    with pytest.raises(ValueError, match="dur"):
        obs.export.validate_chrome_trace(neg)
    with pytest.raises(ValueError, match="lane"):
        obs.export.validate_chrome_trace(
            dict(traceEvents=[]), require_lanes=("staging",))


def test_run_report_schema_and_byte_compat():
    payload = dict(checks=dict(ok=True), passed=True, floors=dict(x=0.5))
    rep = obs.export.run_report("unit_test", dict(payload),
                                include_metrics=False)
    assert rep["schema"] == obs.export.RUN_REPORT_SCHEMA
    assert rep["schema_version"] == obs.export.RUN_REPORT_VERSION
    assert rep["report"] == "unit_test"
    for k, v in payload.items():        # gate fields stay at top level
        assert rep[k] == v
    with_metrics = obs.export.run_report("unit_test", dict(payload))
    assert isinstance(with_metrics["metrics"], dict)
    with pytest.raises(ValueError, match="collide"):
        obs.export.run_report("x", dict(schema="boom"))


# ----------------------------------------------------------- integration
@pytest.fixture(scope="module")
def graph():
    return rmat(8, 8, seed=3)


def _streamed_plan(graph, depth):
    return compile_plan(pagerank_algorithm(max_iters=3, tol=0.0),
                        build_block_store(graph, 4), mode="sparse_only",
                        share=False, memory_budget="16KB",
                        pipeline_depth=depth, rebalance_threshold=None)


def test_streamed_span_tree_exact(graph):
    """Synchronous (pipeline_depth=0) streamed run: the span tree is
    exactly predictable.  The calibration iteration assembles and steps
    every wave twice (warm-up + timed); later iterations once."""
    plan = _streamed_plan(graph, depth=0)
    assert isinstance(plan, StreamingPlan)
    with obs.tracing() as tr:
        res = plan.run()
        events = tr.events()
    W = res.schedule_stats["streaming"]["num_waves"]
    I = res.iterations
    assert W >= 4 and I == 3
    counts = {}
    for ev in events:
        counts[ev.name] = counts.get(ev.name, 0) + 1
    expect = 2 * W + (I - 1) * W
    assert counts["iteration"] == I
    assert counts["assemble"] == expect
    assert counts["device_put"] == expect
    assert counts["compute"] == expect
    assert "collective" not in counts   # no mesh, no collective spans
    # phase spans nest under the calibration pass of the first
    # iteration, and under their iteration after it, on the main thread
    assert counts["calibrate"] == counts["split_refresh"] == 1
    parents: dict = {}
    for ev in events:
        if ev.name in ("device_put", "compute", "assemble"):
            parents[ev.parent] = parents.get(ev.parent, 0) + 1
        if ev.name in ("calibrate", "split_refresh"):
            assert ev.parent == "iteration" and ev.args["it"] == 0
    assert parents == {"calibrate": 3 * 2 * W,
                       "iteration": 3 * (I - 1) * W}
    lanes = {ev.name: ev.lane for ev in events}
    assert lanes["assemble"] == "staging"
    assert lanes["device_put"] == "device"
    assert lanes["compute"] == "device"
    assert lanes["iteration"] == "main"
    # per-wave attribution: every wave index shows up in each phase
    for name in ("assemble", "device_put", "compute"):
        waves = {ev.args["wave"] for ev in events if ev.name == name}
        assert waves == set(range(W))


def test_pipelined_run_records_worker_spans(graph):
    """With the background worker on (pipeline_depth=2), assemble spans
    recorded from the staging thread and main-thread spans interleave
    into one buffer without loss."""
    plan = _streamed_plan(graph, depth=2)
    with obs.tracing() as tr:
        res = plan.run()
        events = tr.events()
        assert tr.dropped == 0
    W = res.schedule_stats["streaming"]["num_waves"]
    asm = [ev for ev in events if ev.name == "assemble"]
    # calibration (2W, inline) + overlapped iterations (W each, from the
    # worker); speculative assembly may prefetch part of a never-run
    # epoch, so >= rather than ==
    assert len(asm) >= 2 * W + (res.iterations - 1) * W
    assert {ev.lane for ev in asm} == {"staging"}
    # the traced run is still bit-identical to an untraced one
    want = _streamed_plan(graph, depth=2).run()
    np.testing.assert_allclose(np.asarray(res.result),
                               np.asarray(want.result),
                               rtol=1e-6, atol=1e-9)


def test_collective_spans_only_on_mesh(graph):
    """A 1-device mesh still runs the shard_map step: collective spans
    appear; the plain streamed run records none."""
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("blocks",))
    plan = compile_plan(pagerank_algorithm(max_iters=2, tol=0.0),
                        build_block_store(graph, 4), mode="sparse_only",
                        share=False, memory_budget="16KB", mesh=mesh,
                        pipeline_depth=0, rebalance_threshold=None)
    with obs.tracing() as tr:
        plan.run()
        names = {ev.name for ev in tr.events()}
    assert "collective" in names
    collect = [ev for ev in tr.events() if ev.name == "collective"]
    assert {ev.lane for ev in collect} == {"device"}
    assert all(ev.args["devices"] == 1 for ev in collect)


def test_streamed_trace_exports_valid_chrome_json(graph, tmp_path):
    plan = _streamed_plan(graph, depth=0)
    path = tmp_path / "run.perfetto.json"
    with obs.tracing():
        plan.run()
        obj = obs.export.write_chrome_trace(str(path))
    on_disk = json.loads(path.read_text())
    assert on_disk == json.loads(json.dumps(obj))
    info = obs.export.validate_chrome_trace(
        on_disk, require_lanes=("main", "staging", "device/0"),
        require_phases=("assemble", "device_put", "compute", "iteration"))
    assert info["events"] > 0


def test_serving_stats_bounded_latency():
    """The serving latency block keeps its field names and ordering
    invariant while holding constant memory in the query count."""
    from repro.serve.stats import ServingStats

    st = ServingStats()
    assert st.latency_percentiles() == dict(p50=None, p95=None, p99=None)
    rng = np.random.default_rng(11)
    lats = rng.lognormal(mean=-4.0, sigma=1.0, size=2000)
    for v in lats:
        st.record_latency(v)
    snap = st.snapshot()
    lat = snap["latency_s"]
    assert set(lat) == {"p50", "p95", "p99"}
    assert lat["p50"] <= lat["p95"] <= lat["p99"]
    # accuracy: within one bucket of the exact percentile
    edges = np.asarray(st._latency.edges)
    for q in (50, 95, 99):
        exact = float(np.percentile(lats, q, method="inverted_cdf"))
        b = int(np.searchsorted(edges, exact, side="right"))
        lo = edges[b - 1] if b > 0 else lats.min()
        hi = edges[b] if b < len(edges) else lats.max()
        assert abs(lat[f"p{q}"] - exact) <= hi - lo
    # memory: fixed bucket counts, not a 2000-entry list
    assert len(st._latency._counts) == len(st._latency.edges) + 1
    assert st.completed == 2000


def test_engine_run_is_spanned(graph):
    plan = compile_plan(pagerank_algorithm(max_iters=2, tol=0.0),
                        build_block_store(graph, 4), mode="sparse_only",
                        share=False)
    with obs.tracing() as tr:
        plan.run()
        counts = {}
        for ev in tr.events():
            counts[ev.name] = counts.get(ev.name, 0) + 1
    assert counts["iteration"] == 2
    assert counts["compute"] == 2


def test_metrics_publishing_from_streamed_run(graph):
    obs.REGISTRY.reset()
    try:
        plan = _streamed_plan(graph, depth=0)
        res = plan.run()
        snap = obs.metrics.snapshot()
        st = res.schedule_stats["streaming"]
        assert snap["stream.runs"] == 1
        assert snap["stream.iterations"] == res.iterations
        assert snap["stream.bytes_staged"] == st["bytes_staged_total"]
        assert snap["stream.waves"] == st["num_waves"]
        assert snap["stream.budget_bytes"] == st["budget_bytes"]
        assert 0 < snap["stream.budget_high_water_bytes"] <= st["budget_bytes"]
        assert snap["stream.run_seconds"]["count"] == 1
        for phase in ("assemble", "device_put", "compute"):
            assert snap[f"stream.phase_seconds.{phase}"] >= 0
    finally:
        obs.REGISTRY.reset()


# ------------------------------------- 8-device subprocess composition
def _run_py(code: str, devices: int = 8, timeout: int = 500):
    env = dict(
        os.environ,
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
        PYTHONPATH=os.path.join(REPO, "src"),
        JAX_PLATFORMS="cpu",
    )
    return subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )


@pytest.mark.slow
@pytest.mark.subprocess
def test_mesh_streamed_trace_has_one_lane_per_device():
    """Acceptance: an 8-device mesh streamed run exports a valid trace
    with one lane per device plus the staging lane, carrying per-wave
    assemble / device_put / compute / collective spans."""
    r = _run_py("""
        import json
        import numpy as np, jax
        from jax.sharding import Mesh
        from repro import obs
        from repro.core import build_block_store, compile_plan, rmat
        from repro.algorithms import pagerank_algorithm

        assert len(jax.devices()) == 8, jax.devices()
        mesh = Mesh(np.array(jax.devices()), ("blocks",))
        g = rmat(10, 16, seed=5)
        with obs.tracing() as tr:
            plan = compile_plan(pagerank_algorithm(max_iters=3, tol=0.0),
                                build_block_store(g, 8), mode="sparse_only",
                                share=False, memory_budget="12KB", mesh=mesh,
                                rebalance_threshold=None)
            res = plan.run()
            obj = obs.export.chrome_trace()
        waves = res.schedule_stats["streaming"]["num_waves"]
        lanes = ["main", "staging"] + [f"device/{i}" for i in range(8)]
        info = obs.export.validate_chrome_trace(
            obj, require_lanes=lanes,
            require_phases=("assemble", "device_put", "compute",
                            "collective", "iteration"))
        per_wave = {
            name: sorted({ev.args["wave"] for ev in tr.events()
                          if ev.name == name})
            for name in ("assemble", "device_put", "compute", "collective")
        }
        print(json.dumps(dict(
            waves=waves, lanes=info["lanes"],
            span_counts=info["span_counts"], per_wave=per_wave,
        )))
    """)
    assert r.returncode == 0, r.stderr[-4000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["waves"] >= 4
    for lane in ["main", "staging"] + [f"device/{i}" for i in range(8)]:
        assert lane in out["lanes"]
    # every wave index appears in every phase, collective included
    W = out["waves"]
    for name in ("assemble", "device_put", "compute", "collective"):
        assert out["per_wave"][name] == list(range(W)), name
    # a device-lane span is mirrored onto all 8 device tracks
    assert out["span_counts"]["collective"] % 8 == 0


# ---------------------------------------------------------------------
# metric-catalog conformance: docs/observability.md lists exactly the
# metric names the source publishes — both directions.

def test_metric_catalog_matches_source():
    import re
    from pathlib import Path

    from repro.core.stream import PHASES

    root = Path(__file__).resolve().parents[1]
    doc = (root / "docs" / "observability.md").read_text()
    start = doc.index("The metric catalog")
    table = doc[start:]
    table = table[:table.index("\n\n", table.index("| ---"))]
    doc_names = set(re.findall(r"\| `([a-z_]+(?:\.[a-z_]+)+)` \|", table))
    assert doc_names, "catalog table not found in docs/observability.md"

    published: set = set()
    for path in (root / "src" / "repro").rglob("*.py"):
        src = path.read_text()
        published |= set(re.findall(
            r'(?:counter|gauge|histogram)\(\s*"([a-z_]+(?:\.[a-z_]+)+)"',
            src))
        # the per-phase counters publish through one f-string
        if 'f"stream.phase_seconds.{' in src:
            published |= {f"stream.phase_seconds.{p}" for p in PHASES}

    missing_from_docs = sorted(published - doc_names)
    stale_in_docs = sorted(doc_names - published)
    assert not missing_from_docs, (
        f"published metrics absent from the docs catalog: "
        f"{missing_from_docs}")
    assert not stale_in_docs, (
        f"docs catalog names nothing in src publishes: {stale_in_docs}")

    # and a live streamed + served run publishes only cataloged names
    from repro.core import build_block_store, compile_plan, rmat
    from repro.algorithms import sv_algorithm
    from repro.serve import GraphServer, Query

    obs.REGISTRY.reset()
    store = build_block_store(rmat(8, 8, seed=3), 4)
    compile_plan(sv_algorithm(), store, mode="sparse_only", share=False,
                 memory_budget="16KB", host_fraction=0.3).run()
    srv = GraphServer(max_batch=4)
    srv.register_graph("g", build_block_store(rmat(8, 8, seed=3), 4))
    srv.submit(Query("g", "pagerank", dict(seeds=[1])))
    srv.drain()
    live = set(obs.metrics.snapshot())
    assert live <= doc_names, sorted(live - doc_names)
