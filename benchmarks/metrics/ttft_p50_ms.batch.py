"""Median time to first token in the saturated loop: recorded, not judged."""
from benchmarks.metrics.lib import ms_p


def read(obs):
    return ms_p(obs.get("ttft_s"), 50)
