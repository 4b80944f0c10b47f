"""Device seconds per iteration of the program's ``scatter`` scope on
chip 0: PageRank's scatter-add into the accumulator, with the sort of
its indices that XLA makes of it, over the ``iteration`` spans traced."""
from bench import scoped


def read(run):
    return scoped.scope_per_iteration(run, "scatter")
