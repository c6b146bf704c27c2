"""Per engine step, the host time with no dispatch program in flight: the
``orion/step`` spans' total less every ``orion/*/run`` leaf. The engine's own
estimate of device idle time, to be read beside ``device_idle_pct.batch``
(which the trace measures): it still holds the launch latency inside the run
spans' complement, and not the idle time outside ``step()``."""
from benchmarks.trace.host_spans import engine_gap_s


def read(obs):
    gap = engine_gap_s(obs["timing"])
    if gap is None or not obs.get("steps"):
        return None
    return 1e3 * gap / obs["steps"]
