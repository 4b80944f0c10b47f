"""Device seconds per traced BFS level in the program's ``scatter``
scope on chip 0: the min-scatter of the parent offers, with the sort
XLA makes of it, in the push and pull steps alike.  Nothing to read
where the program names no ``scatter`` scope."""
from bench import scoped


def read(run):
    s = scoped.for_run(run)
    if s is None or "scatter" not in s.scope_s:
        return None
    return scoped.scope_per_iteration(run, "scatter")
