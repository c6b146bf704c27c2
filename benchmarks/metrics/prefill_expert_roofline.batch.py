"""The grouped expert matmuls of prefill against the COMPUTE roofline: the
operations named ``gmm.N`` in the traced segment (the grouped matmul over the
routed (token, expert) rows, every layer's) against ``prefill_expert_rows``
(the engine's counter of rows ONE expert layer computed) x 6 D F x layers
over the chip's bf16 peak. The counter holds routed assignments, not the
kernel's tile rounding, so the share is of the work the model needs. Over
100 %: the counter and the traced segment do not cover the same dispatches.
A program without the counter or the kernel reads nothing."""
from benchmarks.metrics import flops
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    rows = tr["timing"].get("prefill_expert_rows")
    seconds = op_seconds(obs, r"^gmm\.")
    if not rows or not seconds:
        return None
    least = (flops.expert_matmul_flops(obs["config"], rows)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
