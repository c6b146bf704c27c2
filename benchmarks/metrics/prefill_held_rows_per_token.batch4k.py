"""Routed rows on HELD experts per real prompt position and sparse layer
(``prefill_held_expert_rows`` / ``prefill_tokens`` / sparse layers; engine
counters, the first counted by the prefill program itself). The model needs
top-k x held / router width (10 x 128 / 256 = 5), a little more where padded
rows route one position each. Exact counts, so a CPU run prints it too; a
program without the counter reads nothing."""
from benchmarks.metrics import held_share


def read(obs):
    t = obs["timing"]
    rows, tokens = t.get("prefill_held_expert_rows"), t.get("prefill_tokens")
    if not rows or not tokens:
        return None
    return rows / tokens / held_share.sparse_layers(obs["config"])
