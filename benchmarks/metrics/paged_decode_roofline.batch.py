"""The paged decode attention kernel against the MEMORY roofline: the bound
is bytes, not operations (one query position a slot reads its whole
context). The least time is the K and V of every context position the
kernel had to read (``decode_kv_tokens``: per token step, the live slots'
context lengths, the sliding window at most) in every layer, over the
published bandwidth; the kernel's time is that of the operations named
``paged_decode.N`` in the traced segment. ``kv_bytes`` multiplies by
``num_hidden_layers``, so this reader is for a model whose EVERY layer keeps
K and V over the same span; a model of mixed layers lists itself under
``mixed_paged_decode_roofline.batch4k``, which counts (position, layer)
pairs."""
from benchmarks.metrics.lib import op_seconds


def kv_bytes(hf: dict, kv_tokens: int, itemsize: int = 2) -> int:
    """K and V (2) x kv heads x head size x bytes, per position and layer."""
    heads = hf["num_attention_heads"]
    head_dim = hf.get("head_dim") or hf["hidden_size"] // heads
    return (kv_tokens * hf["num_hidden_layers"] * 2
            * hf["num_key_value_heads"] * head_dim * itemsize)


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    kv_tokens = tr["timing"].get("decode_kv_tokens")
    seconds = op_seconds(obs, r"^paged_decode\.")
    if not kv_tokens or not seconds:
        return None
    least = kv_bytes(obs["config"], kv_tokens) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
