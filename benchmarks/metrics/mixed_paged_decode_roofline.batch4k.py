"""The paged decode attention kernel against the MEMORY roofline in a model
that mixes window and full layers: the least time is the K and V each layer
kind has to read (``decode_kv_token_layers``: per token step and live slot, a
full layer its context, a window layer ``min(context, window)``, summed over
the layers) over the published bandwidth; the kernel's time is that of the
operations named ``paged_decode.N`` in the traced segment. A program without
the counter reads nothing."""
from benchmarks.metrics import held_share
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    token_layers = tr["timing"].get("decode_kv_token_layers")
    seconds = op_seconds(obs, r"^paged_decode\.")
    if not token_layers or not seconds:
        return None
    least = (held_share.kv_bytes(obs["config"], token_layers)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
