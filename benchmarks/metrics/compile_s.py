"""Seconds JAX spent building or loading programs during set-up (its own
monitoring event, persistent-cache loads included)."""


def read(obs):
    return obs.get("compile_s")
