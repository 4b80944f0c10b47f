"""Share of the HBM roofline that the traced window's iterations
reached, in percent: the least bytes the algorithm must move per
iteration, times the iterations traced, over the peak HBM bandwidth
times the chip's compute time (the busy union of every operation that
is not a host/device transfer).  The program names no kernel yet, so
the compute time is the whole step's: the sparse scatter, the dense
tiles when any run, and the per-iteration post."""


def read(run):
    tr = run.trace
    if tr is None or tr.compute_s <= 0:
        return None
    iters = len(tr.spans.get("iteration", []))
    if not iters:
        return None
    alg = run.cell.algorithm
    moved = alg.least_bytes_per_iteration(run.n, run.m) * iters
    return 100.0 * moved / (run.peaks["hbm_bytes_per_s"] * tr.compute_s)
