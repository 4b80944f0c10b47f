"""Device seconds per iteration of the program's ``gather`` scope on
chip 0: PageRank's ``rank / degree`` read at every arc's source and its
mask, over the ``iteration`` spans traced."""
from bench import scoped


def read(run):
    return scoped.scope_per_iteration(run, "gather")
