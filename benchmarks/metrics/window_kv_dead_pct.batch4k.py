"""Of the pool's live pages x layers (sampled at every decode window over the
live slots), the share lying wholly behind a window layer's window
(``kv_dead_window_page_layers`` / ``kv_live_page_layers``, engine counters,
host arithmetic on the slots' lengths): what a page allocator that knows
window layers from full ones would free. Exact counts; a program without the
counters reads nothing."""


def read(obs):
    t = obs["timing"]
    live = t.get("kv_live_page_layers")
    if not live:
        return None
    return 100.0 * t.get("kv_dead_window_page_layers", 0) / live
