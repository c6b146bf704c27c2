"""Per engine step of the 40 s window, the host time with nothing queued on
the device, as the executor itself counts it (``unqueued_s``: from the return
of the wait that covered the newest launch to the start of the next launch).
Read from an UNTRACED window: to be compared with
``idle_engine_host_ms_per_step.batch`` + ``idle_frontend_ms_per_step.batch``
of the traced segment."""


def read(obs):
    t = obs["timing"]
    if "unqueued_s" not in t or not obs.get("steps"):
        return None
    return 1e3 * t["unqueued_s"] / obs["steps"]
