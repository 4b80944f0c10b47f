"""Host seconds of the streamed plan's ``calibrate`` spans before the
window: the synchronous iterations that warm every wave shape and time
each phase, in the warm trials."""
from bench import scoped


def read(run):
    got = scoped.split_at_window(run, "calibrate")
    if got is None:
        return None
    return 1e-9 * sum(e.dur_ns for e in got[0])
