"""The flash attention kernels (forward, the forward that remat runs again,
and backward) against the compute roofline: the matmul operations the masked,
windowed attention needs, forward and backward, over the published peak, over
the kernels' device time. The kernels are found by SHAPE (a custom call whose
first output is [sequences, q or kv heads, seq_len, head_dim]): the program
names them ``attention.N`` on one chip and ``shard_map.N`` under a mesh."""
from benchmarks.metrics import flops
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr, hf = obs.get("trace"), obs["config"]
    if not tr or not obs.get("peaks"):
        return None
    N, K = hf["num_attention_heads"], hf["num_key_value_heads"]
    H = hf.get("head_dim") or hf["hidden_size"] // N
    batch = obs["tokens_per_step"] // obs["seq_len"] // obs["chips"]
    seconds = op_seconds(
        obs, rf"_custom-call_bf16_{batch}_({N}|{K})_{obs['seq_len']}_{H}_$")
    if not seconds:
        return None
    need = tr["steps"] * flops.flash_fwd_bwd_flops(hf, obs["seq_len"], batch)
    return 100.0 * (need / obs["peaks"]["bf16_flops"]) / seconds
