"""Host seconds of the streamed plan's ``split_refresh`` spans before the
window: re-balancing the ``"auto"`` host/device split after each
calibration, with the re-packing of every wave (``plan_waves``) and the
rebuild of the host lane (``host_lane_build``) where a new split was
applied, in the warm trials."""
from bench import scoped


def read(run):
    got = scoped.split_at_window(run, "split_refresh")
    if got is None:
        return None
    return 1e-9 * sum(e.dur_ns for e in got[0])
