"""Run one cell traced, as ``bench/run.py --trace 1`` does, and report
what the program names inside it.

    python3 bench/report.py --workload <cell> --seed <n> --seconds <s> [--out <file>]

The harness's result line goes to standard output as usual; the report,
one JSON object, to ``--out`` where given, else after it:

* ``scope_s_per_iter``: chip 0's device seconds per kernel scope per
  traced iteration; ``scope_coverage``: the share of chip 0's compute
  time that a scope names, in percent;
* ``idle``: chip 0's idle seconds inside the window's ``iteration``
  spans, whole and split by the main thread's innermost span;
* ``before_trials``: the set-up spans the plan recorded before its
  first iteration;
* ``warm_trials``: each warm trial (the iterations before the window; a
  trial starts where ``it`` returns to 0): its wall from its first
  iteration's start to its last one's end, its seconds by the main
  thread's innermost span, its set-up spans with their attributes, and
  the share of its wall inside a set-up or wait span (``covered``).
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    # the checkout's root, not this directory, so `bench` imports as a
    # package
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from bench import harness, scoped  # noqa: E402
from bench import trace as tr  # noqa: E402

#: the program's set-up and wait spans on the main thread
SETUP = ("calibrate", "split_refresh", "plan_waves", "host_lane_build",
         "stage_wait", "host_wait")


def device_report(s: scoped.Scoped, summary: tr.Summary) -> dict:
    """Scope seconds and the idle split of one traced window."""
    n = len(summary.spans.get("iteration", []))
    named = sum(v for k, v in s.scope_s.items() if k != scoped.UNSCOPED)
    return dict(
        iterations=n,
        scope_s_per_iter={k: v / n for k, v in s.scope_s.items()} if n
        else {},
        scope_coverage=(100.0 * named / summary.compute_s
                        if summary.compute_s > 0 else None),
        idle=dict(within_iterations_s=s.idle_within("iteration"),
                  by_innermost_s=s.idle_split("iteration")))


def main_thread(events) -> list:
    """The ``repro.obs`` spans of the main thread: its own names, and the
    waves it assembled itself inside a synchronous calibration."""
    return sorted((e for e in events if e.name in scoped.MAIN_SPANS
                   or (e.name == "assemble" and e.parent is not None)),
                  key=lambda e: e.start_ns)


def _span(e) -> dict:
    return dict(name=e.name, parent=e.parent, s=e.dur_ns * 1e-9, **e.args)


def warm_report(events, warm: int) -> dict:
    """The set-up spans before the first iteration, and the first
    ``warm`` trials by the main thread's spans."""
    main = main_thread(events)
    trials: list[list] = []
    for e in main:
        if e.name == "iteration":
            if e.args.get("it") == 0 or not trials:
                trials.append([])
            trials[-1].append(e)
    first = trials[0][0].start_ns if trials else float("inf")
    out = dict(before_trials=[_span(e) for e in main
                              if e.name in SETUP and e.end_ns <= first],
               warm_trials=[])
    for trial in trials[:warm]:
        lo, hi = trial[0].start_ns, max(e.end_ns for e in trial)
        inside = [e for e in main if e.start_ns >= lo and e.end_ns <= hi]
        by: dict[str, float] = defaultdict(float)
        for a, b, name in scoped.flatten(
                [tr.Event(e.name, e.start_ns, e.end_ns) for e in inside]):
            by[name] += (b - a) * 1e-9
        wall = (hi - lo) * 1e-9
        by[tr.NO_SPAN] = wall - sum(by.values())
        covered = 1e-9 * tr.length(tr.merge(
            (e.start_ns, e.end_ns) for e in inside if e.name in SETUP))
        out["warm_trials"].append(dict(
            wall_s=wall,
            iterations=[dict(it=e.args.get("it"), s=e.dur_ns * 1e-9)
                        for e in trial],
            by_innermost_s=dict(by),
            setup_spans=[_span(e) for e in inside
                         if e.name in SETUP[:4]],
            covered=100.0 * covered / wall if wall > 0 else None))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    rc = harness.main(["--workload", args.workload, "--seed", str(args.seed),
                       "--seconds", str(args.seconds), "--trace", "1"],
                      t0=T0)
    if rc:
        return rc
    from repro import obs

    cell = harness.load_cell(ROOT, args.workload)
    path = scoped.trace_file()
    with open(path, "rb") as f:
        s = scoped.from_xspace(scoped.decode(f.read()))
    summary = tr.summarize(tr.load(path, harness.PROGRAM_SPANS), cell.chips)
    report = dict(workload=args.workload, seed=args.seed,
                  **device_report(s, summary),
                  **warm_report(obs.tracer().events(),
                                int(cell.traffic.get("warm_trials", 1))))
    text = json.dumps(report, default=str)
    if args.out:
        with open(args.out, "w") as f:
            f.write(text + "\n")
    else:
        print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
