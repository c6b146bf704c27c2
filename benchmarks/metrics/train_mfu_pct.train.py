"""Model FLOP/s utilisation: the benchmark's own count (causal mask and
window applied, recompute and the embedding lookup excluded) times tokens per
second over chips times the published peak."""
from benchmarks.metrics import flops


def read(obs):
    if not obs.get("steps") or not obs.get("peaks"):
        return None
    per_token = flops.train_flops_per_token(obs["config"], obs["seq_len"])
    rate = obs["steps"] * obs["tokens_per_step"] / obs["window_s"]
    return 100.0 * per_token * rate / (obs["chips"] * obs["peaks"]["bf16_flops"])
