"""The routed experts' feed-forward at decode against the MEMORY roofline:
every token step reads the three matrices of all 64 routed experts in every
sparse layer (32 slots x 4 picks hit nearly every one), so the least time is
those bytes (``latent.routed_expert_bytes``) over the published bandwidth.
The operations are found by SHAPE, as ``moe_ffn_roofline.batch`` finds
Mixtral's: [experts, slots, expert width] (in and gate) and [slots, experts,
1, hidden] (out). The shared expert is on neither side. A program that has
no such operation reads nothing."""
from benchmarks.metrics import latent
from benchmarks.metrics.lib import decode_program, op_seconds


def patterns(hf: dict, slots: int) -> str:
    E, D = hf["n_routed_experts"], hf["hidden_size"]
    Fe = hf["moe_intermediate_size"]
    return rf"_fusion_bf16_({E}_{slots}_{Fe}|{slots}_{E}_1_{D})_$"


def read(obs):
    hf, got = obs["config"], decode_program(obs)
    if got is None or not obs.get("peaks") or "n_routed_experts" not in hf:
        return None
    seconds = op_seconds(obs, patterns(hf, obs["slots"]))
    if not seconds:
        return None
    steps = got[1] * obs["decode_window"]
    least = (steps * latent.routed_expert_bytes(hf)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
