"""Median of the NON-ZERO gaps between deliveries in the saturated loop (the
median of all per-token samples is 0 under a fused window): recorded, not
judged."""
from benchmarks.metrics.lib import ms_p


def read(obs):
    return ms_p([g for g in obs.get("gaps_s", ()) if g > 0], 50)
