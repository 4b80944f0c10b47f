"""Seconds per iteration that chip 0 stood idle while the main thread
waited in ``stage_wait`` for the staging worker's next wave slab."""
from bench import scoped


def read(run):
    return scoped.idle_per_iteration(run, "stage_wait")
