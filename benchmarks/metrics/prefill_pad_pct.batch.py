"""Padding as a share of the positions the prefill dispatches computed:
each burst is a ``[rows -> power of two] x [largest bucket]`` block, and the
engine counts the real prompt positions in it (prefix-cached ones excluded)
and the rest. Exact counts, so a CPU run prints it too."""


def read(obs):
    t = obs["timing"]
    done = t.get("prefill_tokens", 0) + t.get("prefill_pad_tokens", 0)
    return 100.0 * t["prefill_pad_tokens"] / done if done else None
