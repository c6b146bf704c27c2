"""The retention decode kernel against the MEMORY roofline: a token step reads
each live slot's state row and its tail in every layer. The least time is
the bytes of the engine's counters ``decode_state_slot_layers`` (per token
step, live slot and layer) and ``decode_tail_token_layers`` (the tail
positions read, the new one among them), at the model's own sizes
(``retention.py``), over the published bandwidth; the kernel's time is that
of the operations named ``retention_decode.N`` in the traced segment. A
program without the counters or the kernel reads nothing."""
from benchmarks.metrics import retention
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    rows = tr["timing"].get("decode_state_slot_layers")
    tail = tr["timing"].get("decode_tail_token_layers")
    seconds = op_seconds(obs, r"^retention_decode\.")
    if not rows or not seconds:
        return None
    least = (retention.decode_bytes(obs["config"], rows, tail or 0)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
