"""``teps`` of a streamed cell, a metric of its own because the streamed
cells report ``teps.stream``, not ``teps``: see ``teps.py``."""
from bench.metrics.teps import read  # noqa: F401
