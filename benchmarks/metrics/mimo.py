"""Bytes and operations of a model whose window layers differ from its full
layers in their K/V heads and keep a ring a slot (keys wider than values, a
sink, a held share of sigmoid-routed experts), from the configuration file's
own keys: what the ``.mixed16k`` metrics set the kernels' times against.
Counted on the MODEL's sizes and the live lengths (a position in a layer is
K/V heads x (key + value width) numbers whatever the cache's layout or the
kernel's blocks; a prompt's attention is the pairs its layer's own mask
keeps), so that a share cannot pass 100 % whatever a later layout or kernel
does."""

from __future__ import annotations


def sparse_layers(hf: dict) -> int:
    return sum(hf["moe_layer_freq"][:hf["num_hidden_layers"]])


def held_expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of every HELD routed expert (the file's
    ``n_routed_experts``) in every sparse layer: what one decode step has to
    read of them."""
    return (sparse_layers(hf) * hf["n_routed_experts"] * 3 * hf["hidden_size"]
            * hf["moe_intermediate_size"] * itemsize)


def position_bytes(hf: dict, kind: str, itemsize: int = 2) -> int:
    """K and V of one position in one layer of ``kind`` ('full' | 'window')."""
    heads = hf["swa_num_key_value_heads" if kind == "window"
               else "num_key_value_heads"]
    return heads * (hf["head_dim"] + hf["v_head_dim"]) * itemsize


def decode_kv_bytes(hf: dict, full_token_layers: int,
                    window_token_layers: int) -> int:
    """What the decode attention kernel has to read for the engine's counters
    ``decode_kv_token_layers_full`` / ``_ring``: per token step and live
    slot, a full layer its context and a window layer ``min(context,
    window)`` positions, each at its kind's K/V heads."""
    return (full_token_layers * position_bytes(hf, "full")
            + window_token_layers * position_bytes(hf, "window"))


def prefill_attn_flops(hf: dict, pairs: int) -> int:
    """The attention of a prefill: ``pairs`` (the engine's
    ``prefill_attn_pairs``: the (query, key) pairs each layer's own mask
    keeps of a real prompt, n (n + 1) / 2 on a full layer and w (w + 1) / 2 +
    (n - w) w on a window layer, summed over the layers; padding and the
    part of a block the mask drops count against the kernel) x query heads x
    (a score over ``head_dim`` numbers and a weighted value of
    ``v_head_dim``), 2 operations a multiply-add."""
    return (pairs * hf["num_attention_heads"]
            * (hf["head_dim"] + hf["v_head_dim"]) * 2)
