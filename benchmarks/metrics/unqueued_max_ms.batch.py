"""The longest single interval of the 40 s window in which nothing was queued
on the device (the executor's ``unqueued_max_s``): a stall of the host, by
its size."""


def read(obs):
    t = obs["timing"]
    return 1e3 * t["unqueued_max_s"] if "unqueued_max_s" in t else None
