"""``scope_coverage`` of a streamed cell, a metric of its own because the
streamed cells report ``teps.stream``: see ``scope_coverage.py``."""
from bench.metrics.scope_coverage import read  # noqa: F401
