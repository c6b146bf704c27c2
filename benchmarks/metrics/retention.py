"""Bytes and operations of a power-retention layer, from the configuration
file's own keys: what the ``.longout`` rooflines set the kernels' times
against. Counted on the MODEL's sizes (the symmetric square of a head, D =
H (H + 1) / 2 entries), not on the layout or the chunk the program chose, so
that a share cannot pass 100 % whatever a later kernel does."""

from __future__ import annotations


def state_entries(hf: dict) -> int:
    """D: the symmetric square of a head vector."""
    H = hf["head_dim"]
    return H * (H + 1) // 2


def state_row_bytes(hf: dict, itemsize: int = 2) -> int:
    """One slot's state in one layer: ``S`` [K, D, H] as stored and the
    normaliser ``z`` [K, D] in float32."""
    K, H, D = hf["num_key_value_heads"], hf["head_dim"], state_entries(hf)
    return K * D * H * itemsize + K * D * 4


def tail_token_bytes(hf: dict, itemsize: int = 2) -> int:
    """One tail position in one layer: K and V of every K/V head and the
    float32 cumulative log-gate of each."""
    K, H = hf["num_key_value_heads"], hf["head_dim"]
    return 2 * K * H * itemsize + K * 4


def decode_bytes(hf: dict, state_slot_layers: int,
                 tail_token_layers: int) -> int:
    """What the decode kernel has to read for the engine's two counters."""
    return (state_slot_layers * state_row_bytes(hf)
            + tail_token_layers * tail_token_bytes(hf))


def prefill_flops(hf: dict, units: int) -> int:
    """``units``: the engine's ``prefill_retention_units``, in which a real
    prompt position of index t counts min(2 (t + 1), D) a layer, the cheaper
    of attending its t + 1 predecessors (a score and a weighted value each)
    and of reading a state (D products for the numerator's row of phi; the
    normaliser is a 128th of that and is left out). A unit is H
    multiply-adds of every query head."""
    return units * 2 * hf["head_dim"] * hf["num_attention_heads"]
