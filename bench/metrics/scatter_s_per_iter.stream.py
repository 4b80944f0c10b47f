"""``scatter_s_per_iter`` of a streamed cell, a metric of its own because the
streamed cells report ``teps.stream``: see ``scatter_s_per_iter.py``."""
from bench.metrics.scatter_s_per_iter import read  # noqa: F401
