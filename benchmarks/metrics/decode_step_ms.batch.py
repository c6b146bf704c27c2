"""Device time of the decode-window program per token step (trace)."""
from benchmarks.metrics.lib import decode_step_ms as read  # noqa: F401
