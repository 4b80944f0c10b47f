"""Readings of the lower-precision control at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

For each seed: the cell's graph, the float64 reference, and the same
reference computed one precision down (the algorithm module's
``control``) on the default JAX device, put in the program's place as
one timed trial and judged by the harness's own :func:`judge`; prints
the numbers compared, their limits and ``correct``, one JSON line per
seed.  These readings are the upper ends of the limits in the cell's
traffic file (see PERF.md).  The benchmark's own runs never run this.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if __name__ == "__main__":
    sys.path[0] = ROOT
    sys.path.insert(1, os.path.join(ROOT, "src"))

from bench.harness import Run, Trial, is_correct, judge, load_cell  # noqa: E402


def readings(cell, seed: int) -> dict:
    """The control in the program's place for one seed: ``checks`` as a
    run prints them, and ``correct``."""
    alg = cell.algorithm
    indptr, indices = cell.generator.generate(cell.config, seed)
    want = alg.reference(indptr, indices, cell.traffic)
    ranks = alg.control(indptr, indices, cell.traffic)
    run = Run(cell=cell, n=indptr.shape[0] - 1, m=int(indices.shape[0]),
              trials=[Trial(0.0, 0.0, int(cell.traffic["max_iters"]), {})])
    checks, failed = judge(cell, run, [ranks], want, fallbacks=0)
    return dict(correct=is_correct(checks, failed), checks=checks)


def main(argv=None) -> int:
    import argparse

    import jax

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(ROOT, args.workload)
    for seed in args.seeds:
        t = time.perf_counter()
        got = readings(cell, seed)
        print(json.dumps(dict(workload=cell.name, seed=seed,
                              device=jax.devices()[0].device_kind, **got,
                              seconds=time.perf_counter() - t)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
