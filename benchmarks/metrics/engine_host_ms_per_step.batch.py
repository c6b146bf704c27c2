"""The engine's ``host_s`` span (a step minus its dispatch spans) per step."""
from benchmarks.metrics.lib import timing_per_step_ms


def read(obs):
    return timing_per_step_ms(obs, "host_s")
