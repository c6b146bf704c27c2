"""Bytes and operations of a model of block-sparse and lightning layers
(MiniCPM-SALA), from the configuration file's own keys: what the ``.longdoc``
metrics set the kernels' times against. Counted on the MODEL's sizes and the
engine's counters (a selected key is one head's K and V numbers whatever the
kernel copies of a page; a slot's state in a layer is heads x head x head
float32 numbers whatever the kernel's blocks; a prompt position is the
recurrence's own operations whatever the chunk), so that a share cannot pass
100 % whatever a later layout or kernel does."""

from __future__ import annotations


def key_bytes(hf: dict, itemsize: int = 2) -> int:
    """One visible key of one K/V head: its K and its V numbers (bfloat16:
    512 B at a head of 128)."""
    return 2 * hf["head_dim"] * itemsize


def sparse_decode_bytes(hf: dict, visible_keys: int) -> int:
    """What the sparse decode kernel has to read for the engine's counter
    ``decode_sparse_visible_keys`` (per token step: over live slots, sparse
    layers and K/V heads, the keys of the selected pages at or before the
    new position)."""
    return visible_keys * key_bytes(hf)


def sparse_prefill_flops(hf: dict, visible_pairs: int) -> int:
    """``prefill_sparse_visible_pairs`` (over real prompt positions, sparse
    layers and QUERY heads, the keys a selection leaves visible) x the score
    and the value product of a pair: 2 x 2 x head."""
    return visible_pairs * 4 * hf["head_dim"]


def state_row_bytes(hf: dict) -> int:
    """One slot's state in one lightning layer: heads x d x d float32."""
    return hf["lightning_nh"] * hf["lightning_head_dim"] ** 2 * 4


def lightning_decode_bytes(hf: dict, slot_layers: int) -> int:
    """``decode_lightning_slot_layers`` x a state row read AND written."""
    return slot_layers * 2 * state_row_bytes(hf)


def lightning_prefill_flops(hf: dict, token_layers: int) -> int:
    """``prefill_lightning_token_layers`` x the recurrence's own operations a
    position over all heads: decay the state (d d), write the rank-one
    product and read it along q (2 d d each)."""
    return (token_layers * 5 * hf["lightning_nh"]
            * hf["lightning_head_dim"] ** 2)
