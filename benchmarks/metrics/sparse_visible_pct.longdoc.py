"""Of the keys in context, the share a sparse layer's query attends at
decode: the engine's counters ``decode_sparse_visible_keys`` over
``decode_sparse_context_keys`` (both over live slots, sparse layers, K/V heads
and token steps of the 40 s window; host arithmetic on lengths: a selection
takes min(causal blocks, 64) pages whatever it picks). Near 100 the cell no
longer measures the mechanism. A program without the counters reads
nothing."""


def read(obs):
    t = obs["timing"]
    seen = t.get("decode_sparse_visible_keys")
    if not seen or not t.get("decode_sparse_context_keys"):
        return None
    return 100.0 * seen / t["decode_sparse_context_keys"]
