"""Host clock over the window's synchronised steps, per step."""


def read(obs):
    return 1e3 * obs["window_s"] / obs["steps"] if obs.get("steps") else None
