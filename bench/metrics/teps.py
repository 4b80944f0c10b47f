"""Arcs traversed per second over the window: the arcs every trial in
the window must traverse, by the algorithm's work function, over the
time from the window's start to the end of its last trial."""


def read(run):
    work = run.cell.algorithm.arcs_per_trial(run.n, run.m, run.cell.traffic)
    return work * len(run.trials) / (run.trials[-1].end - run.window_start)
