"""Per engine step of the traced segment, the device's idle time while
nothing was queued and the host was inside ``orion/step``: from a wait's
return to the next launch's start (fetch, emission, reap, admit, build,
uploads), on the host's clock alone (``benchmarks/trace/seam.py``). What a
dispatch ahead hides under a running program."""
from benchmarks.trace import seam


def read(obs):
    return seam.per_step_ms(obs, "host_in_step_s")
