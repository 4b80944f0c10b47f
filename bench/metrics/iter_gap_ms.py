"""Milliseconds per iteration that the chip stood idle inside the
program's ``iteration`` spans: the host loop's dispatch, convergence
check and round-trip between one step and the next."""


def read(run):
    if run.trace is None:
        return None
    idle_s, count = run.trace.idle_within("iteration")
    return 1e3 * idle_s / count if count else None
