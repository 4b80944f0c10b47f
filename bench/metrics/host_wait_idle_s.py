"""Seconds per iteration that chip 0 stood idle while the main thread
waited in ``host_wait`` for the host lane's units to finish."""
from bench import scoped


def read(run):
    return scoped.idle_per_iteration(run, "host_wait")
