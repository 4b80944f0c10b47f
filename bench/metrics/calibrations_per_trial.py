"""``calibrate`` spans that started inside the window, per timed trial:
synchronous re-timing iterations after a rebalance or a split refresh."""
from bench import scoped


def read(run):
    got = scoped.split_at_window(run, "calibrate")
    if got is None or not run.trials:
        return None
    return len(got[1]) / len(run.trials)
