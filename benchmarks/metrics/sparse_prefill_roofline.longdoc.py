"""The sparse layers' prefill attention against the COMPUTE roofline: the
operations named ``sparse_paged_prefill.N`` (every position of a chunk a
virtual slot of the paged kernel, 16 query rows a selection) against
``prefill_sparse_visible_pairs`` (the engine's count: over real prompt
positions, sparse layers and query heads, the keys a selection leaves
visible) x ``4 x head`` operations a pair (``sala.sparse_prefill_flops``)
over the chip's bf16 peak. A program without the counter or the kernel reads
nothing, and so does a segment in which no prompt was admitted."""
from benchmarks.metrics import sala
from benchmarks.metrics.lib import op_seconds


def read(obs):
    tr = obs.get("trace")
    if not tr or not obs.get("peaks"):
        return None
    pairs = tr["timing"].get("prefill_sparse_visible_pairs")
    seconds = op_seconds(obs, r"^sparse_paged_prefill\.")
    if not pairs or not seconds:
        return None
    least = (sala.sparse_prefill_flops(obs["config"], pairs)
             / obs["peaks"]["bf16_flops"])
    return 100.0 * least / seconds
