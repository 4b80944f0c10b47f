"""The share of chip 0's compute time (``Summary.compute_s``) that the
program named with a kernel scope (``sparse``, ``dense``, ``post``,
``fold``, ``gather``, ``scatter``), in percent: how much of the device
time the per-scope metrics account for."""
from bench import scoped


def read(run):
    return scoped.scope_coverage(run)
