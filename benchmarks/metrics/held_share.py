"""Bytes and operations that the ALGORITHM needs in a configuration whose
chip holds a SHARE of the experts under layers of two attention kinds
(``flops.py``'s counterpart for it; tested against the cut table of the
configuration file). ``num_experts`` is the experts HELD; only the layers
whose ``mlp_layer_types`` entry is sparse have any; a sliding layer reads its
window of a context at most."""

from __future__ import annotations


def sparse_layers(hf: dict) -> int:
    """How many of the layers run have experts."""
    return hf["mlp_layer_types"][:hf["num_hidden_layers"]].count("sparse")


def held_expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of every held routed expert in every sparse layer:
    what one decode step has to read of them. The shared expert (1 / 128 of
    it) is not in: at decode XLA fuses its matmuls with their neighbours
    into [slots, width] operations that a trace cannot tell apart, so the
    share that reads this leaves it out of the time and of the bytes."""
    routed = (hf["num_experts"] * 3 * hf["hidden_size"]
              * hf["moe_intermediate_size"])
    return sparse_layers(hf) * routed * itemsize


def held_expert_matmul_flops(hf: dict, rows: int) -> float:
    """The three matmuls of a gated expert (2 D F each) over ``rows`` (token,
    held expert) rows, the rows already summed over the sparse layers."""
    return rows * 3 * 2 * hf["hidden_size"] * hf["moe_intermediate_size"]


def kv_bytes(hf: dict, token_layers: int, itemsize: int = 2) -> int:
    """K and V of ``token_layers`` (position, layer) pairs."""
    return (token_layers * 2 * hf["num_key_value_heads"] * hf["head_dim"]
            * itemsize)
