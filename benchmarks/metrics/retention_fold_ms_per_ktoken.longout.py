"""Device time of the fold kernel (``retention_fold.N`` in the traced
segment) per 1000 output tokens the decode windows of that segment kept
(``slot_steps`` less ``wasted_steps``): what moving a tail's chunk into the
state costs a generated token. A program without the ``folds`` counter reads
nothing; one that has it and folded nothing in the segment reads 0."""
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr:
        return None
    seconds = op_seconds(obs, r"^retention_fold\.")
    t = tr["timing"]
    tokens = t.get("slot_steps", 0) - t.get("wasted_steps", 0)
    if "folds" not in t or tokens <= 0:
        return None
    return 1e3 * (seconds or 0.0) / (tokens / 1000.0)
