"""The paged decode attention kernel against the MEMORY roofline in a model
whose full layers keep pages and whose window layers keep a ring, over both
kinds: the least time is the K and V a step has to read (the engine's
``decode_kv_token_layers_full`` / ``_ring``: per token step and live slot a
full layer its context, a window layer ``min(context, 128)`` positions, at 4
and 8 K/V heads of 192 + 128 numbers: ``mimo.decode_kv_bytes``) over the
published bandwidth; the kernel's time is that of the operations named
``paged_decode.N`` in the traced segment (one kernel, with the sink's term and
the packed keys, walks both kinds of leaves). A program without the counters
reads nothing."""
from benchmarks.metrics import mimo
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    full = tr["timing"].get("decode_kv_token_layers_full")
    ring = tr["timing"].get("decode_kv_token_layers_ring")
    seconds = op_seconds(obs, r"^paged_decode\.")
    if not full or not ring or not seconds:
        return None
    least = (mimo.decode_kv_bytes(obs["config"], full, ring)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
