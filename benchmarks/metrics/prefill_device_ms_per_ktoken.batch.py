"""Device time of the programs launched inside the traced segment's
``orion/prefill/run`` spans, per 1000 real prompt positions
(``prefill_tokens``) of that segment. The prefill programs are told from the
others by the host span that launched them, not by their names."""
from benchmarks.trace import host_spans


def read(obs):
    got = host_spans.for_obs(obs)
    if got is None:
        return None
    tokens = obs["trace"]["timing"].get("prefill_tokens")
    seconds = got["run_module_s"].get("orion/prefill/run")
    if not tokens or not seconds:
        return None
    return 1e3 * seconds / (tokens / 1000.0)
