"""Run one cell of ``BENCHMARK.json`` once and print its result.

A cell names a configuration and a traffic mix.  Everything that
belongs to one of them, or to one metric, is found by name:

* ``bench/configs/<config>.json``: the deployment graph: its generator
  (``bench/generators/<generator>.py``), that generator's parameters,
  and the blocking ``p``;
* ``bench/traffic/<traffic>.json``: the algorithm
  (``bench/algorithms/<algorithm>.py``), its parameters, the executor
  options, the warm-up trials and the limits of the comparison;
* ``bench/metrics/<metric>.py``: a ``read(run)`` that takes one metric
  from the :class:`Run` record, or returns None where it finds nothing.

A run: require the chips, generate the graph from the seed, block it
and compile the plan through the program's normal path, run the warm
trials (all of that is set-up), run trials back to back for the
window, read the device's memory peak, compare every timed trial's
output with the plain reference, and print one JSON line.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
#: spans of the program (``repro.obs``) that gaps are attributed to
PROGRAM_SPANS = {"iteration", "compute", "assemble", "device_put",
                 "host_compute", "collective", "checkpoint", "prepare"}


class NoChip(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    generator: object
    algorithm: object
    metrics: dict           # name -> (unit, module), in BENCHMARK.json order
    trace_metrics: dict


@dataclass
class Trial:
    start: float
    end: float
    iterations: int
    stats: dict


@dataclass
class Run:
    """What one run measured; the metric modules read it."""
    cell: Cell
    n: int
    m: int
    generate_s: float = 0.0
    blocking_s: float = 0.0
    compile_s: float = 0.0
    setup_s: float = 0.0
    window_start: float = 0.0
    trials: list = field(default_factory=list)
    warm_stats: dict = field(default_factory=dict)  # last warm trial's
    peak_bytes: int = 0
    #: the device bytes a streamed plan promises to stay within, by its
    #: own two public numbers; 0 for an in-core plan
    stream_bound_bytes: int = 0
    peaks: dict = field(default_factory=dict)
    trace: object = None        # bench.trace.Summary of a traced run

    @property
    def iterations(self) -> int:
        return sum(t.iterations for t in self.trials)


def load_module(root: str, kind: str, name: str):
    path = os.path.join(root, "bench", kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} module {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(root: str, kind: str, name: str) -> dict:
    with open(os.path.join(root, "bench", kind, f"{name}.json")) as f:
        return json.load(f)


def load_cell(root: str, workload: str) -> Cell:
    """Resolve ``workload`` from ``<root>/BENCHMARK.json`` and the files
    its names point at."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    config = load_json(root, "configs", w["config"])
    traffic = load_json(root, "traffic", w["traffic"])

    def metrics_of(group: str) -> dict:
        return {m["name"]: (m["unit"], load_module(root, "metrics", m["name"]))
                for m in spec[group]
                if workload in m.get("workloads", [workload])}

    return Cell(
        name=workload, chips=int(w["chips"]), config=config, traffic=traffic,
        generator=load_module(root, "generators", config["generator"]),
        algorithm=load_module(root, "algorithms", traffic["algorithm"]),
        metrics=metrics_of("end_to_end"), trace_metrics=metrics_of("per_layer"))


def require_chips(chips: int) -> dict:
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {d.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return dict(platform=d.platform, kind=d.device_kind, count=len(devs))


def use_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else the fixed ``<checkout>/.jax_cache``; every program
    is cached, so a second run of a cell compiles nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX's backend compilations (cache hits included) and its
    persistent-cache misses, through ``jax.monitoring``, and keeps the
    name of each program that missed and why JAX traced it anew, from
    JAX's own log records, so that set-up can say what compiled."""

    def __init__(self) -> None:
        import logging

        import jax
        import jax.monitoring as mon

        self.compiles = 0
        self.misses = 0
        self.missed: list[str] = []
        self.retraced: list[str] = []

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event, **kw):
            if event == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        counter = self

        class Keep(logging.Handler):
            def emit(self, record):
                msg = record.getMessage()
                if msg.startswith("PERSISTENT COMPILATION CACHE MISS"):
                    counter.missed.append(msg.split("'")[1])
                elif msg.startswith("TRACING CACHE MISS"):
                    counter.retraced.append(" ".join(msg.split()))

        self._explain = jax.config.jax_explain_cache_misses
        jax.config.update("jax_explain_cache_misses", True)
        self._handler = Keep(logging.DEBUG)
        self._loggers = []
        for name in ("jax._src.compiler", "jax._src.interpreters.partial_eval"):
            log = logging.getLogger(name)
            self._loggers.append((log, log.level, log.propagate))
            log.setLevel(logging.DEBUG)
            log.propagate = False
            log.addHandler(self._handler)
        self._listeners = (on_duration, on_event)
        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def summary(self) -> str:
        from collections import Counter

        names = Counter(self.missed).most_common()
        return (f"missed={names} retraced={len(self.retraced)} "
                f"first_retraces={self.retraced[:4]}")

    def close(self) -> None:
        import jax
        import jax.monitoring as mon

        on_duration, on_event = self._listeners
        mon.unregister_event_duration_listener(on_duration)
        mon.unregister_event_listener(on_event)
        for log, level, propagate in self._loggers:
            log.removeHandler(self._handler)
            log.setLevel(level)
            log.propagate = propagate
        jax.config.update("jax_explain_cache_misses", self._explain)


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def executor_options(traffic: dict, m: int) -> dict:
    opts = dict(traffic.get("executor", {}))
    frac = traffic.get("memory_budget_edge_fraction")
    if frac is not None:
        opts["memory_budget"] = int(m * traffic["edge_bytes_per_arc"] * frac)
    return opts


def run_trials(plan, run: Run, seconds: float, annotate) -> list:
    """Trials back to back until ``seconds`` have passed; the trial
    running at the deadline finishes and counts.  Returns each trial's
    output."""
    outputs = []
    t0 = run.window_start
    while True:
        start = time.perf_counter()
        with annotate("bench_trial"):
            res = plan.run()
        end = time.perf_counter()
        run.trials.append(Trial(start, end, res.iterations,
                                res.schedule_stats))
        outputs.append(res.result)
        if end - t0 >= seconds:
            return outputs


def judge(cell: Cell, run: Run, outputs: list, want: np.ndarray,
          fallbacks: int) -> tuple[dict, int]:
    """Every number compared, with its limit, over every timed trial;
    and how many trials failed one."""
    limits = cell.traffic["limits"]
    worst = {k: 0.0 for k in limits}
    failed = 0
    for trial, out in zip(run.trials, outputs):
        got = cell.algorithm.check(out, trial.iterations, want,
                                   cell.traffic)
        got["recoveries"] = int("resilience" in trial.stats)
        if any(got[k] > limits[k] for k in limits):
            failed += 1
        for k in limits:
            worst[k] = max(worst[k], got[k])
    worst["fallbacks"] = fallbacks
    checks = {k: dict(value=v, limit=limits.get(k, 0))
              for k, v in worst.items()}
    return checks, failed


def is_correct(checks: dict, failed: int) -> bool:
    return failed == 0 and all(c["value"] <= c["limit"]
                               for c in checks.values())


def read_metrics(mods: dict, run: Run) -> dict:
    out = {}
    for name, (unit, mod) in mods.items():
        v = mod.read(run)
        if v is not None:
            out[name] = dict(value=float(v), unit=unit)
    return out


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t0: float, root: str = ROOT) -> dict:
    """One run of ``cell``; returns the result object."""
    from bench.peaks import peaks_for

    device = require_chips(cell.chips)
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else {}
    cache = use_compile_cache(root)
    say(f"[setup] cell={cell.name} seed={seed} device={device} cache={cache}")
    counter = CompileCounter()
    try:
        return _measure(cell, seed, seconds, traced, t0, root, device, peaks,
                        counter)
    finally:
        counter.close()


def _measure(cell: Cell, seed: int, seconds: float, traced: bool, t0: float,
             root: str, device: dict, peaks: dict,
             counter: CompileCounter) -> dict:
    import contextlib

    import jax

    from repro import obs
    from repro.core import Graph, build_block_store, compile_plan

    t = time.perf_counter()
    indptr, indices = cell.generator.generate(cell.config, seed)
    n, m = indptr.shape[0] - 1, int(indices.shape[0])
    run = Run(cell=cell, n=n, m=m, generate_s=time.perf_counter() - t,
              peaks=peaks)
    say(f"[setup] generator={cell.config['generator']} n={n} arcs={m} "
        f"generate_s={run.generate_s}")

    t = time.perf_counter()
    store = build_block_store(Graph(indptr=indptr, indices=indices, n=n,
                                    name=cell.name),
                              int(cell.config["p"]))
    run.blocking_s = time.perf_counter() - t

    if traced:
        obs.enable(jax_annotations=True)
        annotate = jax.profiler.TraceAnnotation
    else:
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    opts = executor_options(cell.traffic, m)
    backend = opts.get("backend", "xla")
    t = time.perf_counter()
    plan = compile_plan(cell.algorithm.make(cell.traffic), store, **opts)
    steps = [time.perf_counter() - t]
    for _ in range(int(cell.traffic.get("warm_trials", 1))):
        run.warm_stats = plan.run().schedule_stats
        steps.append(time.perf_counter() - t - sum(steps))
    run.compile_s = time.perf_counter() - t
    say(f"[setup] blocking_s={run.blocking_s} compile_s={run.compile_s} "
        f"compile_plan_and_warm_trials_s={steps} options={opts} "
        f"compiles={counter.compiles} cache_misses={counter.misses}")
    say(f"[setup] {counter.summary()}")

    trace_dir = os.path.join(root, ".bench_trace")
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    compiles_before = counter.compiles
    run.window_start = time.perf_counter()
    run.setup_s = run.window_start - t0
    with annotate("bench_window"):
        outputs = run_trials(plan, run, seconds, annotate)
    window_compiles = counter.compiles - compiles_before
    if traced:
        jax.profiler.stop_trace()
    run.peak_bytes = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in jax.devices()[:cell.chips])
    say(f"[window] trials={len(run.trials)} "
        f"seconds={run.trials[-1].end - run.window_start} "
        f"iterations={run.iterations} window_compiles={window_compiles} "
        f"peak_bytes={run.peak_bytes} "
        f"trial_s={[round(t.end - t.start, 4) for t in run.trials]}")
    st = run.trials[-1].stats
    if "streaming" in st:
        # the plan held hot (resident arrays and two worst-case staged
        # waves, state excluded) plus the resident set with the state
        run.stream_bound_bytes = int(plan.resident_device_bytes
                                     + st["streaming"]["resident_bytes"])
        say(f"[window] stream_bound_bytes={run.stream_bound_bytes} "
            f"resident_device_bytes={plan.resident_device_bytes} "
            f"resident_bytes={st['streaming']['resident_bytes']}")
        say(f"[window] waves={st['streaming']['num_waves']} "
            f"budget={st['streaming']['budget_bytes']} "
            f"wave_bytes={st['streaming']['bytes_per_wave']} "
            f"host_tasks={st['hetero']['host_tasks']} "
            f"phase_s={st['streaming']['phase_seconds']} "
            f"planning_s={st['streaming']['planning_phase_seconds']}")

    close = getattr(plan, "close", None)
    if close is not None:
        close()
    fallbacks = int(plan.backend != backend)
    del plan, store
    gc.collect()

    t = time.perf_counter()
    want = cell.algorithm.reference(indptr, indices, cell.traffic)
    checks, failed = judge(cell, run, outputs, want, fallbacks)
    say(f"[check] reference_s={time.perf_counter() - t}")

    result = dict(
        correct=is_correct(checks, failed),
        attempted=len(run.trials), failed=failed)
    device = dict(device, memory_peak_bytes=int(run.peak_bytes))
    if traced:
        from bench import trace as tr

        path = _xplane(trace_dir)
        summary = tr.summarize(tr.load(path, PROGRAM_SPANS), cell.chips)
        run.trace = summary
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["metrics"] = read_metrics(cell.trace_metrics, run)
        result["breakdown"] = dict(device_ops=tr.top(summary.op_s),
                                   idle_gaps=tr.top(summary.idle_by_span))
    else:
        result["metrics"] = read_metrics(cell.metrics, run)
    result["device"] = device
    result["window_compiles"] = window_compiles
    result["checks"] = checks
    return result


def _xplane(trace_dir: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(trace_dir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {found}")
    return found[0]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, t0: float | None = None, root: str = ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    try:
        cell = load_cell(root, args.workload)
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                          t0, root)
    except NoChip as e:
        say(f"FAILED: {e}")
        return 1
    for name, c in result["checks"].items():
        say(f"{name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
