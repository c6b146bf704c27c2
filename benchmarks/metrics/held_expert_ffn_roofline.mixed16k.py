"""The expert feed-forward at decode against the MEMORY roofline, where the
chip holds 16 of 256 experts: every token step reads the three matrices of
all held routed experts in every sparse layer (64 slots x 8 picks over 256
hit each of the 16 held twice on average), so the least time is those bytes
(``mimo.held_expert_bytes``: 10 x 16 x 25,165,824 x 2 B) over the published
bandwidth. The time is the decode-window program's under the part
``mlp_moe/experts``, by SCOPE (``benchmarks/trace/scopes.py``) and not by
shape, as ``held_expert_ffn_roofline.reason128`` reads its own. A program
without named programs and parts, or another configuration, reads nothing."""
from benchmarks.metrics import mimo
from benchmarks.trace import scopes


def read(obs):
    hf, got = obs["config"], scopes.for_obs(obs)
    prog = "orion_decode_window"
    if (got is None or not got["module_n"].get(prog) or not obs.get("peaks")
            or "moe_layer_freq" not in hf):
        return None
    seconds = scopes.seconds(got, prog, ("mlp_moe/experts",))
    if not seconds:
        return None
    steps = got["module_n"][prog] * obs["decode_window"]
    least = (steps * mimo.held_expert_bytes(hf)
             / obs["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / seconds
