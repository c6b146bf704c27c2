"""The flash forward of prefill under the block mask against the COMPUTE
roofline: the least time is the engine's ``prefill_attn_pairs`` (the pairs
the block mask keeps of a prompt's whole blocks, n (n + 4) / 2 a layer: the
pairs kept, not the tiles visited) x 32 heads x 2 x 128 x 2
(``sdar.prefill_attn_flops``) over the chip's bf16 peak; the time is the
prefill programs' under the part ``attention/kernel``, by SCOPE. A program
without named programs or the counter, or another kind of configuration,
reads nothing."""
from benchmarks.metrics import sdar
from benchmarks.trace import scopes


def read(obs):
    hf, got = obs["config"], scopes.for_obs(obs)
    prog = "orion_prefill"
    if (got is None or "generation" not in hf or not obs.get("peaks")
            or not got["module_n"].get(prog)):
        return None
    pairs = obs["trace"]["timing"].get("prefill_attn_pairs")
    seconds = scopes.seconds(got, prog, ("attention/kernel",))
    if not pairs or not seconds:
        return None
    least = sdar.prefill_attn_flops(hf, pairs) / obs["peaks"]["bf16_flops"]
    return 100.0 * least / seconds
