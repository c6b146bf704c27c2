"""The engine's ``prefill_s`` span as a share of the window."""


def read(obs):
    return 100.0 * obs["timing"]["prefill_s"] / obs["window_s"]
