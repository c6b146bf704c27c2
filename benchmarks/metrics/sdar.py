"""Bytes and operations of a model that generates by diffusion over blocks
with every routed expert held (SDAR), from the configuration file's own
keys: what the ``.blocks`` metrics set the block program's and the prefill's
times against. Counted on the MODEL's sizes, the live lengths and the
forwards the engine counted (a position in a layer is K/V heads x 2 x head
size numbers whatever the cache's layout or the kernel's blocks; a forward
reads every expert's three matrices whatever dispatch multiplies them; a
prompt's attention is the pairs its block mask keeps), so that a share
cannot pass 100 % whatever a later layout or kernel does."""

from __future__ import annotations

from typing import Optional

from benchmarks.trace import scopes

PROGRAM = "orion_denoise_block"


def forwards_a_block(hf: dict) -> int:
    """The denoising forwards and the commit."""
    return hf["generation"]["denoising_steps"] + 1


def position_bytes(hf: dict, itemsize: int = 2) -> int:
    """K and V of one position in every layer."""
    return (hf["num_hidden_layers"] * 2 * hf["num_key_value_heads"]
            * hf["head_dim"] * itemsize)


def expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of every routed expert in every layer: what one
    forward has to read of them."""
    return (hf["num_hidden_layers"] * hf["num_experts"] * 3
            * hf["hidden_size"] * hf["moe_intermediate_size"] * itemsize)


def prefill_attn_flops(hf: dict, pairs: int) -> int:
    """``pairs`` (the engine's ``prefill_attn_pairs``: the (query, key) pairs
    the block mask keeps of a prompt's whole blocks, n (n + L) / 2 a layer,
    summed over the layers; padding and the part of a tile the mask drops
    count against the kernel) x query heads x (a score and a weighted value
    over ``head_dim`` numbers each), 2 operations a multiply-add."""
    return pairs * hf["num_attention_heads"] * 2 * hf["head_dim"] * 2


def block_program(obs: dict) -> Optional[tuple]:
    """(the trace by program and part, runs of the block program) of a
    traced run of a configuration of this kind, or None: no trace, no named
    programs (a parent), another kind of configuration, or no run of it."""
    if "generation" not in obs.get("config", {}):
        return None
    got = scopes.for_obs(obs)
    if got is None or not got["module_n"].get(PROGRAM):
        return None
    return got, got["module_n"][PROGRAM]
