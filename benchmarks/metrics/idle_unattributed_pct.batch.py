"""Share of the traced segment's device idle time that no phase of the
engine accounts for: gaps whose innermost host span is one of the
benchmark's own (``bench.generate``, ``bench.observe``, the edge of
``bench.engine_step``), none at all, or the part of ``orion/step`` that no
child span covers. Says whether the engine's spans are enough."""
from benchmarks.trace import host_spans


def read(obs):
    got = host_spans.for_obs(obs)
    if got is None or not got["idle_s"]:
        return None
    left = host_spans.unattributed(got["idle_by_span"])
    return 100.0 * sum(left.values()) / got["idle_s"]
