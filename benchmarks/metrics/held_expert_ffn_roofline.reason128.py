"""The expert feed-forward at decode against the MEMORY roofline, where the
chip holds a share of the experts: every token step reads the three matrices
of all held routed experts in every sparse layer (128 slots x 8 picks over
512 hit nearly every one of the 128 held), so the least time is those bytes
(``kda.held_expert_bytes``: 6 x 128 x 5,898,240 x 2 B) over the published
bandwidth. The time is the decode-window program's under the part
``mlp_moe/experts``, by SCOPE (``benchmarks/trace/scopes.py``) and not by
shape. The shared expert has a part of its own and is on neither side. A
program without named programs and parts reads nothing."""
from benchmarks.metrics import kda
from benchmarks.trace import scopes


def read(obs):
    hf, got = obs["config"], scopes.for_obs(obs)
    prog = "orion_decode_window"
    if (got is None or not got["module_n"].get(prog) or not obs.get("peaks")
            or "layer_group_size" not in hf):
        return None
    seconds = scopes.seconds(got, prog, ("mlp_moe/experts",))
    if not seconds:
        return None
    steps = got["module_n"][prog] * obs["decode_window"]
    least = (steps * kda.held_expert_bytes(hf)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
