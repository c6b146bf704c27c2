"""Per engine step of the traced segment, the device's idle time while
nothing was queued and the host was OUTSIDE ``orion/step``: the benchmark's
own front end between two steps (``bench.observe``, ``bench.generate``, the
edges of ``bench.engine_step``), on the host's clock alone
(``benchmarks/trace/seam.py``)."""
from benchmarks.trace import seam


def read(obs):
    return seam.per_step_ms(obs, "host_outside_s")
