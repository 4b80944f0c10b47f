"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV.  A section that raises prints
a ``<section>/ERROR`` row, the rest still run, and the exit code is
non-zero.  Sections:
  table1    — 5 algorithms × graph-class suite (paper Table 1)
  sched     — scheduling-mode ablation + cut-off sweep (paper §5.2–5.4)
  profile   — performance profiles (paper Fig. 3)
  oversub   — device-memory oversubscription claim (paper §1/§4.4)
  lm        — LM-substrate roofline cells from the dry-run (assignment)

Usage: PYTHONPATH=src python -m benchmarks.run [--scale small|bench]
                                               [--backend reference|xla|pallas]
"""
from __future__ import annotations

import argparse
import os
import sys


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", default="small", choices=["small", "bench"])
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument(
        "--backend", default="xla", choices=["reference", "xla", "pallas"],
        help="kernel backend for the graph sections (plan registry)",
    )
    ap.add_argument(
        "--only", default=None,
        help="comma list of sections (table1,sched,profile,oversub,lm)",
    )
    args = ap.parse_args(argv)

    from repro.core.compilecache import use_persistent_cache

    use_persistent_cache(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

    from . import lm_roofline, oversub, perf_profile, sched_ablation, table1_graphs

    sections = {
        "table1": table1_graphs.run,
        "sched": sched_ablation.run,
        "profile": perf_profile.run,
        "oversub": oversub.run,
        "lm": lm_roofline.run,
    }
    # the LM section predates the graph-plan API and takes no backend
    graph_sections = {"table1", "sched", "profile", "oversub"}
    chosen = args.only.split(",") if args.only else list(sections)

    print("name,us_per_call,derived")
    failed = []
    for sec in chosen:
        kw = dict(scale=args.scale, repeats=args.repeats)
        if sec in graph_sections:
            kw["backend"] = args.backend
        try:
            for row in sections[sec](**kw):
                print(row)
        except Exception as e:  # noqa: BLE001 — report, continue suite
            print(f"{sec}/ERROR,0.0,{type(e).__name__}: {e}", file=sys.stdout)
            failed.append(sec)
    sys.stdout.flush()
    if failed:
        sys.exit(f"benchmark sections failed: {','.join(failed)}")


if __name__ == "__main__":
    main()
