"""Tokens emitted per (live slot x forward) of the block programs in the
window: the engine's ``tokens_committed`` over ``block_slot_forwards``. A
whole block yields ``block_length`` tokens for ``denoising_steps`` + 1
forwards (4 / 3 here); a prompt's tail in a first block, a block cut at
``max_new_tokens`` or an EOS, and nothing else, lower it. Exact counts, so a
CPU run prints it too. A program without the counters reads nothing."""


def read(obs):
    t = obs["timing"]
    if not t.get("block_slot_forwards"):
        return None
    return t["tokens_committed"] / t["block_slot_forwards"]
