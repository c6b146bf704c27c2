"""The scheduler's own host time per engine step: ``reap_s`` (expiry sweep
and the reaping of finished slots) and ``admit_s`` (slot and page claims,
prefix match, grouping into bursts; the prefill spans nest inside
``orion/admit`` and are not part of it)."""


def read(obs):
    t = obs["timing"]
    if "admit_s" not in t or not obs.get("steps"):
        return None
    return 1e3 * (t["reap_s"] + t["admit_s"]) / obs["steps"]
