"""``gather_s_per_iter`` of a streamed cell, a metric of its own because the
streamed cells report ``teps.stream``: see ``gather_s_per_iter.py``."""
from bench.metrics.gather_s_per_iter import read  # noqa: F401
