"""The sparse layers' decode kernel against the MEMORY roofline: a token step
reads, for each live slot, sparse layer and K/V head, the K and V of the keys
its selection leaves visible. The least time is the engine's counter
``decode_sparse_visible_keys`` x one key's K and V numbers
(``sala.key_bytes``: 512 B) over the published bandwidth; the kernel's time
is that of the operations named ``sparse_paged_decode.N`` in the traced
segment. A program without the counter or the kernel reads nothing."""
from benchmarks.metrics import sala
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    keys = tr["timing"].get("decode_sparse_visible_keys")
    seconds = op_seconds(obs, r"^sparse_paged_decode\.")
    if not keys or not seconds:
        return None
    least = (sala.sparse_decode_bytes(obs["config"], keys)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
