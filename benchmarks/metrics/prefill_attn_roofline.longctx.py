"""The flash forward kernel of prefill against the COMPUTE roofline: the
expanded causal attention over 20 heads rebuilt from the latent rows. The
least time is the engine's counter ``prefill_attn_pairs`` (S (S + 1) / 2 a
real prompt and layer: padding and the blocks past a row's end count against
the kernel) x heads x (key + value size) x 2 over the chip's bf16 peak; the
kernel's time is that of the custom calls whose first output is [rows,
heads, bucket, key size], found by SHAPE as ``flash_attn_roofline.train``
finds its own (a serving program runs the flash forward in prefill only). A
program without the counter reads nothing."""
from benchmarks.metrics import latent
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr, hf = obs.get("trace"), obs["config"]
    if not tr or not obs.get("peaks") or "qk_nope_head_dim" not in hf:
        return None
    pairs = tr["timing"].get("prefill_attn_pairs")
    N = hf["num_attention_heads"]
    H = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    seconds = op_seconds(obs, rf"_custom-call_bf16_\d+_{N}_\d+_{H}_$")
    if not pairs or not seconds:
        return None
    least = latent.prefill_attn_flops(hf, pairs) / obs["peaks"]["bf16_flops"]
    return 100.0 * least / seconds
