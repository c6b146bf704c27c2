"""The retention prefill kernel against the COMPUTE roofline: the operations
named ``retention_prefill.N`` in the traced segment against
``prefill_retention_units`` (the engine's count, by host arithmetic on prompt
lengths: a real position of index t is min(2 (t + 1), D) units a layer, the
cheaper of the two exact forms for its query, which no chunking can
undercut) x 2 x head size x query heads over the chip's bf16 peak. A program
without the counter reads nothing; a segment of one that has it in which no
prompt was admitted (about one traced segment in two hundred of this cell)
reads 0."""
from benchmarks.metrics import retention
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    units = tr["timing"].get("prefill_retention_units")
    seconds = op_seconds(obs, r"^retention_prefill\.")
    if units is None:
        return None
    if not units or not seconds:
        return 0.0
    least = (retention.prefill_flops(obs["config"], units)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
