"""What the cache holds for a live cached token, all layers together: the
engine's counters ``kda_live_state_bytes`` (summed at each decode window:
the live slots' state and convolution rows over the KDA layers, whatever
their length) + ``latent_live_page_bytes`` (their pages of the latent leaf,
the ones provisioned for the window ahead among them) over
``latent_live_tokens``. The number that sets the batch: 14.7 MB a slot and
1.28 KB a token come to about 6 KB a token at this mix's lengths, where 8
layers of K and V would be 524 KB. Engine counters, host arithmetic on the
slots' lengths; a program without them reads nothing."""


def read(obs):
    t = obs["timing"]
    tokens, state = t.get("latent_live_tokens"), t.get("kda_live_state_bytes")
    if not tokens or state is None:
        return None
    return (state + t["latent_live_page_bytes"]) / tokens
