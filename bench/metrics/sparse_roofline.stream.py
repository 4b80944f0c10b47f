"""``sparse_roofline`` of a streamed cell, a metric of its own because the streamed
cells report ``teps.stream``, not ``teps``: see ``sparse_roofline.py``."""
from bench.metrics.sparse_roofline import read  # noqa: F401
