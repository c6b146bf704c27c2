"""A lightning layer's prefill against the COMPUTE roofline: the prefill
programs' operations under the scope ``lightning/chunk`` (the XLA chunked
form; a kernel named ``lightning_prefill.N`` where a later program has one)
against ``prefill_lightning_token_layers`` (the engine's count: real prompt
positions x lightning layers) x the recurrence's own operations a position
(``sala.lightning_prefill_flops``, which no chunking can undercut) over the
chip's bf16 peak. A program without the counter reads nothing, and so does a
segment in which no prompt was admitted."""
from benchmarks.metrics import kda, sala
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    tokens = tr["timing"].get("prefill_lightning_token_layers")
    seconds = (op_seconds(obs, r"^lightning_prefill\.")
               or kda.scope_seconds(obs, "orion_prefill", "lightning/chunk"))
    if not tokens or not seconds:
        return None
    least = (sala.lightning_prefill_flops(obs["config"], tokens)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
