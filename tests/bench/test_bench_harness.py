"""The harness: driven by data, refusing the CPU, and deciding
``correct`` so that the lower-precision control and planted faults of
the timed path come out false.  Small graphs, on the CPU, with the
harness's look for a chip skipped."""
import dataclasses
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench.algorithms import pagerank

ROOT = harness.ROOT
CELLS = ("kron22.pr.incore", "urand22.pr.stream")


def small_root(tmp_path, scale=9, p=4):
    """A copy of the benchmark whose configurations are cut to ``scale``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    for f in (root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(f.read_text())
        f.write_text(json.dumps(dict(cfg, scale=scale, p=p)))
    return root


@pytest.fixture
def no_chip_check(monkeypatch):
    """Skip the look for a TPU and leave JAX's compile cache alone."""
    monkeypatch.setattr(harness, "require_chips", lambda chips: dict(
        platform="cpu", kind="cpu", count=1))
    monkeypatch.setattr(harness, "use_compile_cache", lambda root: "")


def run(root, workload, seconds=0.3, seed=2**31 + 9):
    cell = harness.load_cell(str(root), workload)
    return harness.run_cell(cell, seed, seconds, False, 0.0, str(root))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, no_chip_check, workload):
    res = run(small_root(tmp_path), workload)
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    teps = "teps.stream" if "stream" in workload else "teps"
    assert set(res["metrics"]) == {teps, "setup_s"}  # no HBM on a CPU
    assert list(res)[-1] == "checks"
    for c in res["checks"].values():
        assert c["value"] <= c["limit"]


def test_streamed_run_states_its_memory_bound(tmp_path, no_chip_check,
                                              capsys):
    run(small_root(tmp_path), CELLS[1])
    err = capsys.readouterr().err
    bound = int(err.split("stream_bound_bytes=")[1].split()[0])
    resident = int(err.split("resident_device_bytes=")[1].split()[0])
    assert bound > resident > 0


def test_stream_peak_vs_bound_reads_streamed_runs_only():
    mod = harness.load_module(ROOT, "metrics", "stream_peak_vs_bound")
    cell = harness.load_cell(ROOT, CELLS[1])
    streamed = harness.Run(cell=cell, n=4, m=8, peak_bytes=3000,
                           stream_bound_bytes=2000)
    assert mod.read(streamed) == 150.0
    assert mod.read(dataclasses.replace(streamed, stream_bound_bytes=0)) \
        is None
    assert mod.read(dataclasses.replace(streamed, peak_bytes=0)) is None


def test_compile_counter_names_what_it_traced():
    import logging

    import jax

    counter = harness.CompileCounter()
    try:
        jax.jit(lambda x: x * 3 + 1)(np.float32(2.0))
    finally:
        counter.close()
    assert counter.compiles >= 1
    assert any("never seen function" in r for r in counter.retraced)
    log = logging.getLogger("jax._src.compiler")
    assert log.propagate and not log.handlers


def test_new_cell_from_new_files_only(tmp_path, no_chip_check, capsys):
    root = small_root(tmp_path)
    bench = root / "bench"
    cfg = json.loads((bench / "configs" / "urand22.json").read_text())
    (bench / "configs" / "tiny_urand.json").write_text(
        json.dumps(dict(cfg, scale=8, p=2)))
    mix = json.loads((bench / "traffic" / "pr.incore.json").read_text())
    (bench / "traffic" / "pr.three.json").write_text(
        json.dumps(dict(mix, max_iters=3)))
    (bench / "metrics" / "trials_run.py").write_text(
        "def read(run):\n    return len(run.trials)\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append(dict(spec["configs"][1], name="tiny_urand",
                                file="bench/configs/tiny_urand.json"))
    spec["workloads"].append(dict(name="tiny_urand.pr.three",
                                  config="tiny_urand", traffic="pr.three",
                                  chips=1, why="new cell from data"))
    teps = next(m for m in spec["end_to_end"] if m["name"] == "teps")
    teps["workloads"].append("tiny_urand.pr.three")
    spec["end_to_end"].append(dict(name="trials_run", unit="trials",
                                   better="higher", bound=0.01,
                                   source="host_clock",
                                   workloads=["tiny_urand.pr.three"]))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))

    cell = harness.load_cell(str(root), "tiny_urand.pr.three")
    assert cell.traffic["max_iters"] == 3 and cell.config["scale"] == 8
    assert list(cell.metrics) == ["teps", "setup_s", "trials_run"]
    # the metric is listed for the new cell only
    assert "trials_run" not in harness.load_cell(str(root), CELLS[0]).metrics
    rc = harness.main(["--workload", "tiny_urand.pr.three", "--seed", "4",
                       "--seconds", "0.2", "--trace", "0"], root=str(root))
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is True
    assert res["metrics"]["trials_run"] == dict(
        value=float(res["attempted"]), unit="trials")


def _run_script(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "JAX_COMPILATION_CACHE_DIR")}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_the_cpu():
    r = _run_script(ROOT)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_run_fails_with_the_benchmark_files_alone(tmp_path):
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    r = _run_script(tmp_path)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


# --- faults planted in the timed path: each must read not correct ------
def _unchanged_state(monkeypatch):
    from repro.core import engine, stream

    monkeypatch.setattr(engine._CompiledStep, "__call__",
                        lambda self, ctx, state, it, dense: state)
    monkeypatch.setattr(stream._PostStep, "__call__",
                        lambda self, ctx, state, it: dict(
                            state, acc=state["acc"] * 0))


def _half_the_edges(monkeypatch):
    import importlib

    import jax.numpy as jnp

    from repro.core import engine, stream

    prog = importlib.import_module("repro.algorithms.pagerank")

    def scatter(ctx, rank, acc):
        keep = ctx.sparse_edge_mask & (jnp.arange(ctx.src.shape[0]) % 2 == 0)
        vals = jnp.where(keep, (rank * ctx.extras["inv_deg"])[ctx.src], 0.0)
        return acc.at[ctx.dst].add(vals)

    monkeypatch.setattr(prog, "_scatter_sparse", scatter)
    # fresh compiled steps, so the planted kernel is traced
    monkeypatch.setattr(engine, "_STEP_CACHE", {})
    for name in ("_STREAM_STEP_CACHE", "_POST_STEP_CACHE"):
        if hasattr(stream, name):
            monkeypatch.setattr(stream, name, {})


def _altered_answer(monkeypatch):
    make = pagerank.make

    def altered(traffic):
        alg = make(traffic)

        def finalize(store, state):
            r = np.array(state["rank"])
            r[int(np.argmax(r))] *= 1.01
            return r
        return dataclasses.replace(alg, finalize=finalize)

    monkeypatch.setattr(pagerank, "make", altered)


FAULTS = {"unchanged_state": _unchanged_state,
          "half_the_edges": _half_the_edges,
          "altered_answer": _altered_answer}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", CELLS)
def test_planted_fault_is_not_correct(tmp_path, no_chip_check, monkeypatch,
                                      workload, fault):
    FAULTS[fault](monkeypatch)
    root = small_root(tmp_path)
    cell = harness.load_cell(str(root), workload)
    cell = dataclasses.replace(cell, algorithm=pagerank)
    res = harness.run_cell(cell, 77, 0.3, False, 0.0, str(root))
    assert res["correct"] is False
    assert res["failed"] == res["attempted"]


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_bfloat16_control_fails_a_limit(tmp_path, workload, seed):
    from bench import control

    cell = harness.load_cell(str(small_root(tmp_path, scale=11)), workload)
    got = control.readings(cell, seed)
    assert got["correct"] is False
    checks = got["checks"]
    assert checks["rank_l1"]["value"] > checks["rank_l1"]["limit"] or \
        checks["rank_max_rel"]["value"] > checks["rank_max_rel"]["limit"]
