"""Per engine step of the traced segment, the device's idle time at the
dispatch seam itself: each gap between two of the engine's own programs less
the host time between the wait that covered the first and the launch of the
second (``benchmarks/trace/seam.py``): wake-up plus launch latency, with no
clock offset in it. What a dispatch ahead removes outright."""
from benchmarks.trace import seam


def read(obs):
    return seam.per_step_ms(obs, "seam_s")
