"""1 - union of device operation intervals over the traced window."""
from benchmarks.metrics.lib import idle_pct as read  # noqa: F401
