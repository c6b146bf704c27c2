"""Bytes and operations of a latent-attention model with routed experts,
from the configuration file's own keys: what the ``.longctx`` metrics set
the kernels' times against. Counted on the MODEL's sizes (a cached position
is ``kv_lora_rank + qk_rope_head_dim`` numbers a layer, whatever the pool
pads it to; attention is the causal pairs of real positions, whatever the
kernel's blocks visit), so that a share cannot pass 100 % whatever a later
layout or kernel does."""

from __future__ import annotations


def latent_row_bytes(hf: dict, itemsize: int = 2) -> int:
    """One cached position in one layer: the compressed row and the shared
    rotary key (512 + 64 numbers: 1152 B in bfloat16)."""
    return (hf["kv_lora_rank"] + hf["qk_rope_head_dim"]) * itemsize


def decode_bytes(hf: dict, token_layers: int) -> int:
    """What the absorbed decode kernel has to read for the engine's counter
    ``decode_latent_token_layers``: each cached row once."""
    return token_layers * latent_row_bytes(hf)


def prefill_attn_flops(hf: dict, pairs: int) -> int:
    """The expanded causal attention of a prefill: ``pairs`` (the engine's
    ``prefill_attn_pairs``: S (S + 1) / 2 a real prompt and layer) x heads x
    (a score over the key's ``qk_nope + qk_rope`` numbers and a weighted
    value of ``v_head_dim``), 2 operations a multiply-add."""
    key = hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"]
    return pairs * hf["num_attention_heads"] * (key + hf["v_head_dim"]) * 2


def sparse_layers(hf: dict) -> int:
    return max(hf["num_hidden_layers"] - hf["first_k_dense_replace"], 0)


def routed_expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of every routed expert in every sparse layer: what
    one decode step has to read of them (32 slots x 4 picks over 64 hit
    nearly every one). The shared expert is on neither side, as in
    ``held_share.held_expert_bytes``."""
    routed = (hf["n_routed_experts"] * 3 * hf["hidden_size"]
              * hf["moe_intermediate_size"])
    return sparse_layers(hf) * routed * itemsize
