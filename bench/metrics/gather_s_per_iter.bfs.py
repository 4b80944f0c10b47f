"""Device seconds per traced BFS level in the program's ``gather`` scope
on chip 0: the frontier and unvisited flags read at every arc's two
ends, and the masks, in the push and pull steps alike.  Nothing to
read where the program names no ``gather`` scope."""
from bench import scoped


def read(run):
    s = scoped.for_run(run)
    if s is None or "gather" not in s.scope_s:
        return None
    return scoped.scope_per_iteration(run, "gather")
