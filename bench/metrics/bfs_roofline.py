"""Share of the HBM roofline that the traced window's searches reached,
in percent: the least bytes one search must move (the algorithm's
``least_bytes_per_trial``) times the timed trials, every one of them
traced, over the peak HBM bandwidth times the chip's compute time (the
busy union of every operation that is not a host/device transfer)."""


def read(run):
    tr = run.trace
    if tr is None or tr.compute_s <= 0 or not run.trials:
        return None
    alg = run.cell.algorithm
    moved = alg.least_bytes_per_trial(run.n, run.m) * len(run.trials)
    return 100.0 * moved / (run.peaks["hbm_bytes_per_s"] * tr.compute_s)
