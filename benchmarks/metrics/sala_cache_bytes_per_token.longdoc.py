"""What the cache holds for a live cached token, all layers together: the
engine's counters ``sala_live_page_bytes`` (summed at each decode window: the
pages the live slots hold of K, V and the compressed keys in the sparse
layers, the ones provisioned for the window ahead among them) +
``lightning_live_state_bytes`` (their state rows over the lightning layers,
whatever their length) over ``sala_live_tokens``. 2,112 B a cached position
and 12.6 MB a slot: about 2.7 KB a token at this mix's lengths, where 8
layers of K and V of 2 heads would be 8.2 KB. Engine counters, host
arithmetic on the slots' lengths; a program without them reads nothing."""


def read(obs):
    t = obs["timing"]
    tokens, state = (t.get("sala_live_tokens"),
                     t.get("lightning_live_state_bytes"))
    if not tokens or state is None:
        return None
    return (state + t["sala_live_page_bytes"]) / tokens
