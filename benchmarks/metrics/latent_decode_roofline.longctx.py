"""The latent paged decode kernel against the MEMORY roofline (at one query a
sequence the absorbed form does 38 operations a byte against the chip's 240):
a token step reads each live slot's cached rows once in every layer. The
least time is the engine's counter ``decode_latent_token_layers`` (per token
step, the live slots' contexts summed over the layers) x the MODEL's row
(``latent.latent_row_bytes``: 1152 B, whatever the pool pads it to) over the
published bandwidth; the kernel's time is that of the operations named
``latent_paged_decode.N`` in the traced segment. A program without the
counter or the kernel reads nothing."""
from benchmarks.metrics import latent
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    rows = tr["timing"].get("decode_latent_token_layers")
    seconds = op_seconds(obs, r"^latent_paged_decode\.")
    if not rows or not seconds:
        return None
    least = (latent.decode_bytes(obs["config"], rows)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
