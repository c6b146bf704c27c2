"""Device time of the decode-window program's block selection (the scope
``attention/kernel/select``: the gather of a slot's compressed keys, the
scores, the group sum, the pooling, the forced blocks and the top-k of every
sparse layer), per token step (the denominator of ``decode_step_ms.batch``),
from the instructions' scope paths in the trace. A program without the scope
reads nothing."""
from benchmarks.metrics import kda
from benchmarks.metrics.lib import decode_program


def read(obs):
    got = decode_program(obs)
    seconds = kda.scope_seconds(
        obs, "orion_decode_window", "attention/kernel/select")
    if got is None or not seconds:
        return None
    return 1e3 * seconds / (got[1] * obs["decode_window"])
