"""What the cache holds for a live cached position, both kinds of leaves
together, counted from the leaves' shapes and the live lengths: the engine's
counters ``kv_full_bytes_live`` (summed at each decode window: the live
slots' positions x their K and V in the full layers) + ``kv_window_bytes_held``
(the positions a window layer still holds of them, a ring's reach of 192 at
most, x their K and V in the window layers) over ``kv_full_positions_live``.
The number that sets the batch: 5120 B a position in two full layers and 8.85
MB of rings a slot come to about 7.6 KB at this mix's lengths, where eleven
layers that kept every position would be 56 KB. What the pool ALLOCATES for
the same slots (whole pages, out to the end of a prompt's bucket) is the
counter ``kv_full_page_bytes_held`` beside it, which no metric reads yet
(PERF.md section 7). Engine counters, host arithmetic on the slots' lengths
and the leaves' shapes; a program without them reads nothing."""


def read(obs):
    t = obs["timing"]
    live, full = t.get("kv_full_positions_live"), t.get("kv_full_bytes_live")
    if not live or full is None:
        return None
    return (full + t["kv_window_bytes_held"]) / live
