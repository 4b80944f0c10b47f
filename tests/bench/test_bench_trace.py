"""The reduction from a profiler trace to the benchmark's device numbers,
on traces built by hand, and the table of peaks."""
import gzip
import json
import pathlib
import types

import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import trace as tr
from bench.algorithms import pagerank
from bench.metrics import (device_idle, iter_gap_ms,  # noqa: F401
                           sparse_roofline)
from bench.peaks import peaks_for

US = 1000  # ns
DATA = pathlib.Path(__file__).parent / "data"


def xspace(planes):
    """Text proto of an XSpace: ``planes`` maps a plane name to
    ``{line name: [(event name, start_us, duration_us), ...]}``."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({ev[0] for evs in lines.values() for ev in evs})
        meta = {n: i for i, n in enumerate(names, 1)}
        body = [f'id: {pid}', f'name: "{pname}"']
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            events = " ".join(
                f"events {{ metadata_id: {meta[n]} offset_ps: {s * US * 1000} "
                f"duration_ps: {d * US * 1000} }}" for n, s, d in evs)
            body.append(f'lines {{ id: {lid} name: "{lname}" '
                        f'timestamp_ns: 0 {events} }}')
        body += [f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
                 for n, i in meta.items()]
        out.append("planes {\n" + "\n".join(body) + "\n}")
    return ProfileData.from_text_proto("\n".join(out))


SCATTER = "%scatter.1 = f32[64]{0} scatter(f32[64]{0} %p, s32[9]{0} %i)"
FUSION = ("%fusion.2 = (s32[9]{0:T(1024)}, f32[9]{0}) fusion(s32[9]{0} %a), "
          "kind=kCustom, calls=%fused_computation")
INFEED = "%infeed.3 = (f32[64]{0}, token[]) infeed(token[] %t)"
# window 0..100 us; device busy 10..30 (two overlapping ops), 50..60
# (a transfer) and 70..75, and an op outside the window
PLANES = {
    "/device:TPU:0": {
        "XLA Ops": [(SCATTER, 10, 15), (FUSION, 20, 10), (INFEED, 50, 10),
                    (SCATTER, 70, 5), (FUSION, 120, 10)],
        "XLA Modules": [("jit_step", 10, 70)],
    },
    "/host:CPU": {
        "python": [("bench_window", 0, 100), ("iteration", 5, 40),
                   ("compute", 5, 10), ("iteration", 45, 40),
                   ("assemble", 60, 20), ("unrelated", 0, 100)],
        "worker": [("host_compute", 32, 4)],
    },
}


@pytest.fixture
def summary():
    names = {"iteration", "compute", "assemble", "host_compute"}
    return tr.summarize(tr.from_profile(xspace(PLANES), names), devices=1)


def test_busy_is_the_union_of_operations_inside_the_window(summary):
    assert summary.window_s == pytest.approx(100e-6)
    # 10..30 once despite the overlap, 50..60, 70..75; not 120..130
    assert summary.busy_s == pytest.approx(35e-6)
    assert summary.idle_share == pytest.approx(0.65)


def test_time_per_operation_and_transfers(summary):
    assert summary.op_s == {
        "%scatter.1 (scatter)": pytest.approx(20e-6),
        "%fusion.2 (fusion kCustom)": pytest.approx(10e-6),
        "%infeed.3 (infeed)": pytest.approx(10e-6)}
    assert summary.compute_s == pytest.approx(25e-6)
    assert tr.top(summary.op_s, 1) == [["%scatter.1 (scatter)",
                                        pytest.approx(20e-6)]]


def test_idle_gaps_go_to_the_innermost_open_span(summary):
    got = summary.idle_by_span
    # 0..10: window only (unnamed spans are not kept) -> (none)
    assert got[tr.NO_SPAN] == pytest.approx(10e-6 + 15e-6)
    # 30..50: middle 40 lies in iteration 5..45 and host_compute ended
    assert got["iteration"] == pytest.approx(20e-6)
    # 60..70: middle 65 in assemble 60..80; 75..100: middle 87.5, none
    assert got["assemble"] == pytest.approx(10e-6)
    assert sum(got.values()) == pytest.approx(65e-6)


def test_idle_inside_iteration_spans(summary):
    idle, count = summary.idle_within("iteration")
    # 5..45 is busy 10..30 -> idle 20; 45..85 busy 50..60, 70..75 -> 25
    assert count == 2 and idle == pytest.approx(45e-6)


def fake_run(summary, n=1000, m=30000):
    cell = types.SimpleNamespace(algorithm=pagerank, traffic={})
    return types.SimpleNamespace(trace=summary, n=n, m=m, cell=cell,
                                 peaks=peaks_for("TPU v5 lite"))


def test_metrics_read_from_the_summary(summary):
    run = fake_run(summary)
    assert device_idle.read(run) == pytest.approx(65.0)
    assert iter_gap_ms.read(run) == pytest.approx(45e-6 / 2 * 1e3)
    want = 100 * (8 * 30000 + 12 * 1000) * 2 / (819e9 * 25e-6)
    assert sparse_roofline.read(run) == pytest.approx(want)
    assert device_idle.read(fake_run(None)) is None
    assert sparse_roofline.read(fake_run(None)) is None


def test_summarize_refuses_a_trace_it_cannot_read():
    names = {"iteration"}
    two = dict(PLANES, **{"/host:CPU": {"python": [("bench_window", 0, 10),
                                                   ("bench_window", 20, 10)]}})
    with pytest.raises(ValueError, match="one bench_window"):
        tr.summarize(tr.from_profile(xspace(two), names), devices=1)
    with pytest.raises(ValueError, match="chips \\[1\\]"):
        tr.summarize(tr.from_profile(xspace(PLANES), names), devices=2)


def test_operation_labels():
    assert tr.parse_op(SCATTER) == ("%scatter.1 (scatter)", "scatter")
    assert tr.parse_op(FUSION) == ("%fusion.2 (fusion kCustom)", "fusion")
    assert tr.parse_op("%copy-done.1 = f32[8]{0:T(1024)S(1)} copy-done("
                       "(f32[8]{0}, u32[]{:S(2)}) %copy-start.1)") == (
        "%copy-done.1 (copy-done)", "copy-done")
    assert tr.parse_op("jit_step") == ("jit_step", "")


def test_trace_recorded_on_the_chip():
    """A 2-trial window of kron22.pr.incore traced on one TPU v5 lite:
    the run reported busy_s 16.423061492 of window_s 16.453543097."""
    data = gzip.decompress(
        (DATA / "kron22.pr.incore.xplane.pb.gz").read_bytes())
    trace = tr.from_profile(ProfileData.from_serialized_xspace(data),
                            {"iteration", "compute"})
    s = tr.summarize(trace, devices=1)
    assert s.window_s == pytest.approx(16.453543097, rel=1e-9)
    assert s.busy_s == pytest.approx(16.423061492, rel=1e-9)
    assert len(s.spans["iteration"]) == 4 and len(s.spans["bench_trial"]) == 2
    (label, secs), = tr.top(s.op_s, 1)
    assert label == "%fusion (fusion kCustom)" and 9 < secs < 11
    assert s.compute_s == pytest.approx(s.busy_s)
    assert sum(s.idle_by_span.values()) == pytest.approx(
        s.window_s - s.busy_s)
    run = fake_run(s, n=4194304, m=128309778)
    assert 0 < sparse_roofline.read(run) < 0.1
    assert 0 < iter_gap_ms.read(run) < 10


def test_merge_and_gaps():
    assert tr.merge([(5, 6), (0, 2), (1, 3)]) == [(0, 3), (5, 6)]
    assert tr.gaps([(0, 3), (5, 6)], 1, 10) == [(3, 5), (6, 10)]
    assert tr.length(tr.clip([(0, 3), (5, 6)], 2, 5.5)) == pytest.approx(1.5)


def test_peaks_known_and_unknown(tmp_path):
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")
    table = tmp_path / "peaks.json"
    table.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        peaks_for("TPU v5 lite", str(table))
    assert np.isfinite(p["hbm_bytes"])
