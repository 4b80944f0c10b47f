"""The program's own names in a traced run (``bench/scoped.py``): the
``tf_op`` of each device operation read from the XSpace with a small
``google.protobuf`` decoder, device seconds per kernel scope, chip idle
inside the main thread's spans, set-up spans, and the metrics that read
them; on traces built by hand and on traces recorded on the chip."""
import gzip
import importlib
import pathlib
import types

import pytest
from jax.profiler import ProfileData

from bench import harness, scoped
from bench import trace as tr
from bench.algorithms import pagerank
from bench.metrics import device_idle, iter_gap_ms, sparse_roofline
from bench.peaks import peaks_for
from repro import obs

US = 1000  # ns
DATA = pathlib.Path(__file__).parent / "data"


def metric(name):
    """The reader of a per-layer metric, loaded as the harness loads it."""
    for cell in ("kron22.pr.incore", "urand22.pr.stream"):
        mods = harness.load_cell(harness.ROOT, cell).trace_metrics
        if name in mods:
            return mods[name][1]
    raise KeyError(name)


def xspace():
    """Window 0..100 us.  Chip 0 runs a gather 10..30, a scatter 30..40
    (its ``tf_op`` by reference), a fold 50..55, a post 70..75, an
    unscoped copy 80..82 and an operation after the window.  The main
    thread (the line with ``bench_window``) runs one iteration 5..95
    holding stage_wait 40..50, compute 50..56 and host_wait 60..70,
    among runtime events; the staging thread assembles 35..60."""
    msg = scoped._xspace_type()()
    dev = msg.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "jit(step)/sparse/scatter/scatter-add:"
    ops = [("%fusion.3", "jit(step)/sparse/gather/jit(_where)/select_n:",
            10, 20), ("%fusion", 2, 30, 10),
           ("%add_fusion", "jit(step)/fold/add:", 50, 5),
           ("%fusion.2", "jit(step)/post/mul:", 70, 5),
           ("%copy-done", None, 80, 2), ("%fusion.4", "jit(step)/post/mul:",
                                         120, 10)]
    line = dev.lines.add(name=tr.OPS_LINE, timestamp_ns=0)
    for mid, (name, op, start, dur) in enumerate(ops, 1):
        meta = dev.event_metadata[mid]
        meta.name = name
        if isinstance(op, str):
            meta.stats.add(metadata_id=1, str_value=op)
        elif op is not None:
            meta.stats.add(metadata_id=1, ref_value=op)
        line.events.add(metadata_id=mid, offset_ps=start * US * 1000,
                        duration_ps=dur * US * 1000)
    host = msg.planes.add(name="/host:CPU")
    lines = {
        "python3": [("bench_window", 0, 100), ("iteration", 5, 90),
                    ("stage_wait", 40, 10), ("PjitFunction", 41, 2),
                    ("compute", 50, 6), ("host_wait", 60, 10)],
        "repro-staging/7": [("assemble", 35, 25), ("stage_wait", 0, 100)],
    }
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    for mid, name in enumerate(names, 1):
        host.event_metadata[mid].name = name
    for lname, evs in lines.items():
        line = host.lines.add(name=lname, timestamp_ns=1000)
        for name, start, dur in evs:
            line.events.add(metadata_id=names.index(name) + 1,
                            offset_ps=(start * US - 1000) * 1000,
                            duration_ps=dur * US * 1000)
    return msg


@pytest.fixture
def built():
    return scoped.from_xspace(scoped.decode(xspace().SerializeToString()))


@pytest.mark.parametrize("default", [False, True])
def test_loading_the_cell_leaves_the_compile_cache_key_alone(default):
    """Loading a cell's metrics, which imports this reader, changes no
    JAX setting: an untraced run keys its compile cache as the program
    does (the program keys it on metadata only while it is traced)."""
    import jax

    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, default)
    try:
        for cell in ("kron22.pr.incore", "urand22.pr.stream"):
            mods = harness.load_cell(harness.ROOT, cell).trace_metrics
            assert any(m.__dict__.get("scoped") is scoped
                       for _, m in mods.values()), cell
            importlib.reload(scoped)
            assert getattr(jax.config, flag) is default
    finally:
        jax.config.update(flag, before)


def test_scope_of_an_operation_path():
    assert scoped.scope_of("jit(step)/sparse/gather/jit(_where)/select_n") \
        == "gather"
    assert scoped.scope_of("jit(step)/sparse/add") == "sparse"
    # the last element names the operation, never a scope
    assert scoped.scope_of("jit(step)/gather") == scoped.UNSCOPED
    assert scoped.scope_of("") == scoped.UNSCOPED


def test_device_seconds_per_scope(built):
    assert built.scope_s == {
        "gather": pytest.approx(20e-6), "scatter": pytest.approx(10e-6),
        "fold": pytest.approx(5e-6), "post": pytest.approx(5e-6),
        scoped.UNSCOPED: pytest.approx(2e-6)}
    assert built.scoped
    assert (built.lo, built.hi) == (0, 100 * US)
    assert tr.length(built.busy) == pytest.approx(42 * US)
    # only the program's spans on the main thread's line
    assert [e.name for e in built.main] == [
        "iteration", "stage_wait", "compute", "host_wait"]


def test_idle_inside_main_thread_spans(built):
    # idle 40..50 in stage_wait, 60..70 in host_wait; the staging
    # thread's spans do not count
    assert built.idle_within("stage_wait") == pytest.approx(10e-6)
    assert built.idle_within("host_wait") == pytest.approx(10e-6)
    split = built.idle_split("iteration")
    # 5..10, 56..60, 75..80 and 82..95 are the iteration's own; the
    # compute span holds 55..56 of its idle
    assert split == {"iteration": pytest.approx(27e-6),
                     "stage_wait": pytest.approx(10e-6),
                     "compute": pytest.approx(1e-6),
                     "host_wait": pytest.approx(10e-6)}
    assert sum(split.values()) == pytest.approx(
        built.idle_within("iteration"))


def test_flatten_names_each_piece_by_its_innermost_span():
    ev = tr.Event
    got = scoped.flatten([ev("it", 0, 10), ev("a", 2, 4), ev("b", 4, 6),
                          ev("c", 5, 12), ev("it", 20, 30)])
    # c opens inside b and is cut where b ends
    assert got == [(0, 2, "it"), (2, 4, "a"), (4, 5, "b"), (5, 6, "c"),
                   (6, 10, "it"), (20, 30, "it")]


def test_refuses_a_trace_without_one_window():
    msg = xspace()
    msg.planes[1].lines[0].events[0].metadata_id = 2    # no bench_window
    with pytest.raises(ValueError, match="one bench_window"):
        scoped.from_xspace(msg)


def fake_run(tmp_path, monkeypatch, data: bytes, iterations=2,
             compute_s=42e-6):
    where = tmp_path / ".bench_trace" / "plugins" / "profile" / "1"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(data)
    monkeypatch.setattr(scoped, "ROOT", str(tmp_path))
    spans = {"iteration": [tr.Event("iteration", 0, 1)] * iterations}
    return types.SimpleNamespace(trace=types.SimpleNamespace(
        spans=spans, compute_s=compute_s))


@pytest.mark.parametrize("name, want", [
    ("gather_s_per_iter", 10e-6), ("scatter_s_per_iter", 5e-6),
    ("gather_s_per_iter.stream", 10e-6), ("scatter_s_per_iter.stream", 5e-6),
    ("stage_wait_idle_s", 5e-6), ("host_wait_idle_s", 5e-6),
])
def test_device_trace_metrics(tmp_path, monkeypatch, name, want):
    run = fake_run(tmp_path, monkeypatch, xspace().SerializeToString())
    assert metric(name).read(run) == pytest.approx(want)
    assert metric(name).read(types.SimpleNamespace(trace=None)) is None
    run.trace.spans = {}
    assert metric(name).read(run) is None       # no iteration traced


@pytest.mark.parametrize("name", ["scope_coverage", "scope_coverage.stream"])
def test_scope_coverage(tmp_path, monkeypatch, name):
    # 40 of the 42 us of device time lie in a scope; the copy does not
    run = fake_run(tmp_path, monkeypatch, xspace().SerializeToString())
    assert metric(name).read(run) == pytest.approx(100 * 40 / 42)
    assert metric(name).read(types.SimpleNamespace(trace=None)) is None
    run.trace.compute_s = 0.0
    assert metric(name).read(run) is None


@pytest.mark.parametrize("span", ["stage_wait", "host_wait"])
def test_idle_readers_need_the_program_span_not_a_scope(
        tmp_path, monkeypatch, span):
    """A wait the window never saw reads 0 where the program recorded it
    before the window, and nothing where it never did."""
    msg = xspace()
    main = next(line for line in msg.planes[1].lines
                if line.name == "python3")
    names = {k: v.name for k, v in msg.planes[1].event_metadata.items()}
    keep = [e for e in main.events if names[e.metadata_id] != span]
    del main.events[:]
    main.events.extend(keep)
    for p in msg.planes[0].event_metadata.values():
        del p.stats[:]                          # no scope either
    run = fake_run(tmp_path, monkeypatch, msg.SerializeToString())
    reader = metric(f"{span}_idle_s")
    assert reader.read(run) is None
    with obs.tracing() as t:
        t.record(span, 1, 1)
        assert reader.read(run) == 0.0


def recorded_kron22() -> bytes:
    return gzip.decompress(
        (DATA / "kron22.pr.incore.xplane.pb.gz").read_bytes())


def test_tf_op_of_the_recorded_chip_trace(tmp_path, monkeypatch):
    """kron22.pr.incore traced on one TPU v5 lite before the program
    named its kernels: each operation carries the path of the operation
    XLA made it from, and nothing reads as a scope."""
    data = recorded_kron22()
    msg = scoped.decode(data)
    plane, = [p for p in msg.planes if p.name == "/device:TPU:0"]
    ops = scoped.tf_ops(plane)
    label = {tr.parse_op(m.name)[0]: ops[k]
             for k, m in plane.event_metadata.items()
             if m.name.startswith("%")}
    assert label["%fusion (fusion kCustom)"] == "jit(step)/gather"
    assert label["%fusion.1 (fusion kCustom)"] == "jit(step)/scatter-add"
    assert label["%sort (sort)"] == "jit(step)/scatter-add"
    assert label["%fusion.2 (fusion kLoop)"] == "jit(step)/mul"
    s = scoped.from_xspace(msg)
    assert list(s.scope_s) == [scoped.UNSCOPED] and not s.scoped
    run = fake_run(tmp_path, monkeypatch, data, iterations=4)
    for name in ("gather_s_per_iter", "scatter_s_per_iter",
                 "stage_wait_idle_s", "host_wait_idle_s"):
        assert metric(name).read(run) is None


def test_existing_metrics_read_as_before_on_the_recorded_trace():
    """The readers of the accepted benchmark read what they read before
    the scoped reader was added; the decoder finds the same operations
    as JAX's ``ProfileData``."""
    data = recorded_kron22()
    summary = tr.summarize(tr.from_profile(
        ProfileData.from_serialized_xspace(data), {"iteration", "compute"}),
        devices=1)
    run = types.SimpleNamespace(
        trace=summary, n=4194304, m=128309778, peaks=peaks_for("TPU v5 lite"),
        cell=types.SimpleNamespace(algorithm=pagerank, traffic={}))
    assert device_idle.read(run) == pytest.approx(0.18525860855803655,
                                                  rel=1e-12)
    assert iter_gap_ms.read(run) == pytest.approx(2.60308025, rel=1e-12)
    assert sparse_roofline.read(run) == pytest.approx(0.03202292505909758,
                                                      rel=1e-12)
    s = scoped.from_xspace(scoped.decode(data))
    assert tr.length(s.busy) * 1e-9 == pytest.approx(summary.busy_s,
                                                     rel=1e-8)
    assert sum(s.scope_s.values()) == pytest.approx(
        sum(summary.op_s.values()), rel=1e-8)
    # the two readers round picosecond offsets apart by nanoseconds
    assert s.idle_within("iteration") == pytest.approx(
        summary.idle_within("iteration")[0], abs=1e-7)


@pytest.fixture
def program_spans():
    """The program's spans: two calibrations and a split refresh before
    the window (which opens at t=100 s), and a calibration and a split
    refresh inside it, over three timed trials."""
    with obs.tracing() as t:
        t.record("calibrate", 10e9, 2e9)
        t.record("split_refresh", 12e9, 7e9)
        t.record("calibrate", 40e9, 3e9)
        t.record("iteration", 50e9, 1e9)
        t.record("calibrate", 120e9, 1e9)
        t.record("split_refresh", 121e9, 1e9)
        yield types.SimpleNamespace(window_start=100.0, trials=[1, 2, 3])


def test_calibrate_s(program_spans):
    assert metric("calibrate_s").read(program_spans) == pytest.approx(5.0)


def test_split_refresh_s(program_spans):
    assert metric("split_refresh_s").read(program_spans) == \
        pytest.approx(7.0)
    run = types.SimpleNamespace(window_start=100.0, trials=[1])
    obs.disable()
    assert metric("split_refresh_s").read(run) is None   # tracing off
    with obs.tracing() as t:
        t.record("calibrate", 10e9, 1e9)        # no split refresh
        assert metric("split_refresh_s").read(run) is None


def test_calibrations_per_trial(program_spans):
    assert metric("calibrations_per_trial").read(program_spans) == \
        pytest.approx(1 / 3)


@pytest.mark.parametrize("name", ["calibrate_s", "calibrations_per_trial"])
def test_program_span_metrics_read_nothing_without_calibrate(name):
    run = types.SimpleNamespace(window_start=100.0, trials=[1])
    assert metric(name).read(run) is None       # tracing off
    with obs.tracing() as t:
        t.record("iteration", 10e9, 1e9)        # a program without it
        assert metric(name).read(run) is None


def test_scoped_chip_trace(tmp_path, monkeypatch):
    """kron22.pr.incore cut to Kronecker scale 12 (4,096 vertices, 96,712
    arcs, blocked p=8, so some tasks take the dense path), a window of 2
    trials of 2 iterations traced on one TPU v5 lite with the scoped
    program.  The run reported busy_s 0.006088197 of window_s 0.018700139,
    gather_s_per_iter 0.0006436703125 and scatter_s_per_iter
    0.0008263181445."""
    data = gzip.decompress(
        (DATA / "kron12.pr.incore.xplane.pb.gz").read_bytes())
    summary = tr.summarize(tr.from_profile(
        ProfileData.from_serialized_xspace(data), harness.PROGRAM_SPANS),
        devices=1)
    assert summary.busy_s == pytest.approx(0.006088197, rel=1e-9)
    s = scoped.from_xspace(scoped.decode(data))
    assert {"gather", "scatter", "dense", "post"} <= set(s.scope_s)
    assert s.scope_s["gather"] > 0 and s.scope_s["scatter"] > 0
    named = sum(v for k, v in s.scope_s.items() if k != scoped.UNSCOPED)
    assert named >= 0.95 * summary.compute_s
    split = s.idle_split("iteration")
    assert sum(split.values()) == pytest.approx(s.idle_within("iteration"))
    run = fake_run(tmp_path, monkeypatch, data, iterations=4)
    assert metric("gather_s_per_iter").read(run) == pytest.approx(
        0.0006436703125, rel=1e-6)
    assert metric("scatter_s_per_iter").read(run) == pytest.approx(
        0.0008263181445, rel=1e-6)


def test_report_of_the_traced_window(built):
    """``bench/report.py`` gives the scope seconds per iteration and the
    idle inside iterations split by the main thread's innermost span."""
    from bench import report

    summary = types.SimpleNamespace(
        spans={"iteration": [tr.Event("iteration", 0, 1)] * 2},
        compute_s=42e-6)
    got = report.device_report(built, summary)
    assert got["iterations"] == 2
    assert got["scope_s_per_iter"]["gather"] == pytest.approx(10e-6)
    assert got["scope_coverage"] == pytest.approx(100 * 40 / 42)
    idle = got["idle"]
    assert idle["within_iterations_s"] == pytest.approx(48e-6)
    assert sum(idle["by_innermost_s"].values()) == pytest.approx(48e-6)


def test_report_of_the_warm_trials():
    """``bench/report.py`` names each warm trial's wall by the main
    thread's spans: the first trial calibrates and refreshes the split,
    then waits on the host lane and the staging queue; the second waits
    on the staging queue; the window's trial is left out."""
    from bench import report

    with obs.tracing() as t:
        def rec(name, start, dur, **kw):
            t.record(name, start * 1e9, dur * 1e9, **kw)

        rec("plan_waves", 0, 5, initial=True)
        rec("host_lane_build", 1, 2, parent="plan_waves")
        rec("iteration", 10, 10, it=0)
        rec("calibrate", 11, 4, parent="iteration", it=0)
        rec("assemble", 11, 1, parent="calibrate")
        rec("assemble", 12, 30)                 # the staging thread's
        rec("split_refresh", 15, 3, parent="iteration", it=0, applied=True)
        rec("plan_waves", 15, 2, parent="split_refresh", initial=False)
        rec("iteration", 21, 9, it=1)
        rec("host_wait", 21, 4, parent="iteration")
        rec("stage_wait", 25, 1, parent="iteration")
        rec("iteration", 40, 5, it=0)
        rec("stage_wait", 41, 2, parent="iteration")
        rec("iteration", 50, 5, it=0)
        got = report.warm_report(t.events(), warm=2)
    assert [s["name"] for s in got["before_trials"]] == [
        "plan_waves", "host_lane_build"]
    first, second = got["warm_trials"]
    assert first["wall_s"] == pytest.approx(20)
    assert first["by_innermost_s"] == pytest.approx(dict(
        iteration=7, assemble=1, calibrate=3, plan_waves=2,
        split_refresh=1, host_wait=4, stage_wait=1, **{tr.NO_SPAN: 1}))
    assert first["covered"] == pytest.approx(60)
    assert [(s["name"], s["s"]) for s in first["setup_spans"]] == [
        ("calibrate", 4), ("split_refresh", 3), ("plan_waves", 2)]
    assert first["setup_spans"][1]["applied"] is True
    assert [i["it"] for i in first["iterations"]] == [0, 1]
    assert second["wall_s"] == pytest.approx(5)
    assert second["covered"] == pytest.approx(40)
