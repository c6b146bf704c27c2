"""The grouped expert matmuls of prefill against the COMPUTE roofline, where
the chip holds a share of the experts: the operations named ``gmm.N`` in the
traced segment against ``prefill_held_expert_rows`` (the prefill program's
own count of routed rows on HELD experts, already summed over the sparse
layers) x 6 D F over the chip's bf16 peak. Tile rounding (up to 256 rows a
held expert and matmul) counts against the kernel. A program without the
counter or the kernel reads nothing."""
from benchmarks.metrics import held_share
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    rows = tr["timing"].get("prefill_held_expert_rows")
    seconds = op_seconds(obs, r"^gmm\.")
    if not rows or not seconds:
        return None
    least = (held_share.held_expert_matmul_flops(obs["config"], rows)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
