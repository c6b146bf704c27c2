"""Programs built or loaded INSIDE the measured window: has to read 0."""
from benchmarks.metrics.lib import compiles_in_window as read  # noqa: F401
