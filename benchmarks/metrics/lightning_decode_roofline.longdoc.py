"""The lightning decode kernel against the MEMORY roofline: a token step
reads and writes each live slot's state row in every lightning layer. The
least time is the engine's counter ``decode_lightning_slot_layers`` x 2 x the
MODEL's row (``sala.state_row_bytes``: 2,097,152 B) over the published
bandwidth; the kernel's time is that of the operations named
``lightning_decode.N`` in the traced segment. A program without the counter
or the kernel reads nothing."""
from benchmarks.metrics import sala
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    rows = tr["timing"].get("decode_lightning_slot_layers")
    seconds = op_seconds(obs, r"^lightning_decode\.")
    if not rows or not seconds:
        return None
    least = (sala.lightning_decode_bytes(obs["config"], rows)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
