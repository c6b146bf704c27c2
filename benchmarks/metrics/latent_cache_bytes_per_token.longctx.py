"""Pool bytes the live slots' pages hold, a cached token and layer: the
engine's counters ``latent_live_page_bytes`` (summed at each decode window:
the pages of the live slots x the leaf's page over all layers, the pages
provisioned for the window ahead among them) over ``latent_live_tokens`` x
layers. The model's row is 1152 B; this reads that x the page rounding and
the layout's padding, and a later layout or allocator change moves it.
Engine counters, host arithmetic on the slots' lengths; a program without
them reads nothing."""


def read(obs):
    t = obs["timing"]
    tokens = t.get("latent_live_tokens")
    if not tokens:
        return None
    return t["latent_live_page_bytes"] / (
        tokens * obs["config"]["num_hidden_layers"])
