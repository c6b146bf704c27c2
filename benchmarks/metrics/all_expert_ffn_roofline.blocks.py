"""The expert feed-forward of the block program against the MEMORY roofline,
where the chip holds all 128 experts of its layers: a forward's 128 slots x 4
positions x 8 picks reach every expert (32 rows each on average), so the
least time of a forward is the three matrices of every expert in every layer
(``sdar.expert_bytes``: 6 x 128 x 4,718,592 x 2 B) over the published
bandwidth, times the forwards (runs x (``denoising_steps`` + 1)). The time is
the block program's under the part ``mlp_moe/experts``, by SCOPE. A program
without the block program, or another kind of configuration, reads
nothing."""
from benchmarks.metrics import sdar
from benchmarks.trace import scopes


def read(obs):
    found = sdar.block_program(obs)
    if found is None or not obs.get("peaks"):
        return None
    got, runs = found
    seconds = scopes.seconds(got, sdar.PROGRAM, ("mlp_moe/experts",))
    if not seconds:
        return None
    hf = obs["config"]
    least = (runs * sdar.forwards_a_block(hf) * sdar.expert_bytes(hf)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
