"""The expert feed-forward at decode against the memory roofline: every token
step reads all experts' three matrices in every layer (64 picks a step hit
all eight), so the least time is those bytes over the published bandwidth.
The operations are found by SHAPE, [experts, slots, d_ff] and
[slots, experts, 1, d_model], not by their numbered names."""
from benchmarks.metrics import flops
from benchmarks.metrics.lib import decode_program, op_seconds


def read(obs):
    hf, got = obs["config"], decode_program(obs)
    if got is None or not obs.get("peaks"):
        return None
    E, B = hf["num_local_experts"], obs["slots"]
    F, D = hf["intermediate_size"], hf["hidden_size"]
    seconds = op_seconds(obs, rf"_fusion_bf16_({E}_{B}_{F}|{B}_{E}_1_{D})_$")
    if not seconds:
        return None
    steps = got[1] * obs["decode_window"]
    least = steps * flops.expert_weight_bytes(hf) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
