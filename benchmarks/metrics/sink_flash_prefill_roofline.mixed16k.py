"""The flash forward kernel of prefill against the COMPUTE roofline, over
window layers (a sink, 128 positions) and full layers together, at keys 192
and values 128 wide. The least time is the engine's counter
``prefill_attn_pairs`` (the pairs each layer's own mask keeps of a real
prompt: padding, and the part of a 1024-wide block that a 128-wide window
drops, count against the kernel) x 64 heads x (192 + 128) x 2
(``mimo.prefill_attn_flops``) over the chip's bf16 peak; the kernel's time
is that of the custom calls whose first output is [rows, heads, bucket,
value size], found by SHAPE as ``prefill_attn_roofline.longctx`` finds its
own (a serving program runs the flash forward in prefill only). A program
without the counter, or a configuration of one K/V shape, reads nothing."""
from benchmarks.metrics import mimo
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr, hf = obs.get("trace"), obs["config"]
    if (not tr or not obs.get("peaks")
            or "swa_num_key_value_heads" not in hf):
        return None
    pairs = tr["timing"].get("prefill_attn_pairs")
    N, Hv = hf["num_attention_heads"], hf["v_head_dim"]
    seconds = op_seconds(obs, rf"_custom-call_bf16_\d+_{N}_\d+_{Hv}_$")
    if not pairs or not seconds:
        return None
    least = mimo.prefill_attn_flops(hf, pairs) / obs["peaks"]["bf16_flops"]
    return 100.0 * least / seconds
