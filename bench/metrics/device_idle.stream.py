"""``device_idle`` of a streamed cell, a metric of its own because the streamed
cells report ``teps.stream``, not ``teps``: see ``device_idle.py``."""
from bench.metrics.device_idle import read  # noqa: F401
