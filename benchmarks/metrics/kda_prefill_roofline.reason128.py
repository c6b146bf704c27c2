"""A KDA layer's prefill against the COMPUTE roofline: the prefill programs'
operations under the scope ``kda/chunk`` (the XLA chunked form; a kernel
named ``kda_prefill.N`` where a later program has one) against
``prefill_kda_token_layers`` (the engine's count: real prompt positions x
KDA layers) x the recurrence's own operations a position
(``kda.prefill_flops``: the cheaper of the two exact forms, which no
chunking can undercut) over the chip's bf16 peak. A program without the
counter reads nothing, and so does a segment in which no prompt was
admitted: a share of a roofline is never 0."""
from benchmarks.metrics import kda
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    tokens = tr["timing"].get("prefill_kda_token_layers")
    seconds = (op_seconds(obs, r"^kda_prefill\.")
               or kda.scope_seconds(obs, "orion_prefill", "kda/chunk"))
    if not tokens or not seconds:
        return None
    least = (kda.prefill_flops(obs["config"], tokens)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
