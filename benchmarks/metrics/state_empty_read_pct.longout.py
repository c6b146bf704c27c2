"""Of the state rows the decode kernel read (``decode_state_slot_layers``:
per token step, live slot and layer), the share that held nothing yet
(``decode_state_empty_slot_layers``: the slot has folded no chunk and its
prefill completed none): bytes a kernel that knew could skip. Engine
counters, host arithmetic on the slots' lengths; a program without them
reads nothing."""


def read(obs):
    t = obs["timing"]
    rows = t.get("decode_state_slot_layers")
    if not rows:
        return None
    return 100.0 * t.get("decode_state_empty_slot_layers", 0) / rows
