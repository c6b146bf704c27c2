"""Useful (slot x token step) work over slots x steps run."""


def read(obs):
    t = obs["timing"]
    run = obs["slots"] * t["windows"] * obs["decode_window"]
    return 100.0 * (t["slot_steps"] - t["wasted_steps"]) / run if run else None
