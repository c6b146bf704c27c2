"""Bytes and operations of a model of Kimi-delta-attention layers among
latent layers that holds a share of its experts, from the configuration
file's own keys: what the ``.reason128`` metrics set the kernels' times
against. Counted on the MODEL's sizes (a slot's state in a layer is heads x
head x head float32 numbers whatever the kernel's blocks; a prompt position
is the recurrence's own operations, the cheaper of the two forms, whatever
the chunk), so that a share cannot pass 100 % whatever a later layout or
kernel does. The time of a scope that is no kernel of its own name
(``kda/chunk``) is read off the trace here."""

from __future__ import annotations

import bisect
import functools
from typing import Optional


def state_row_bytes(hf: dict) -> int:
    """One slot's state in one KDA layer: heads x d_k x d_v float32."""
    return hf["num_attention_heads"] * hf["head_dim"] * hf["head_dim"] * 4


def decode_bytes(hf: dict, slot_layers: int) -> int:
    """What the decode kernel has to move for the engine's counter
    ``decode_kda_slot_layers``: each live slot's state row read AND written.
    The convolution's rows are moved outside the kernel and are on neither
    side."""
    return slot_layers * 2 * state_row_bytes(hf)


def prefill_flops(hf: dict, token_layers: int) -> int:
    """``prefill_kda_token_layers`` x the recurrence's operations a position
    over all heads: decay the state (d_k d_v), read it along k, write the
    rank-one correction, read it along q (2 d_k d_v each)."""
    return (token_layers * 7 * hf["num_attention_heads"]
            * hf["head_dim"] * hf["head_dim"])


def sparse_layers(hf: dict) -> int:
    return max(hf["num_hidden_layers"] - hf["first_k_dense_replace"], 0)


def held_expert_bytes(hf: dict, itemsize: int = 2) -> int:
    """The three matrices of every HELD routed expert (the file's
    ``num_experts``) in every sparse layer: what one decode step has to read
    of them. The shared expert is on neither side."""
    return (sparse_layers(hf) * hf["num_experts"] * 3 * hf["hidden_size"]
            * hf["moe_intermediate_size"] * itemsize)


@functools.lru_cache(maxsize=2)
def _events(path: str) -> dict:
    from benchmarks.trace import scopes

    return scopes.load(path)


def scope_seconds(obs: dict, program: str, needle: str) -> Optional[float]:
    """Device seconds of ``program``'s operations (the first device's, leaves
    only, as ``scopes.attribute`` books them) whose ``op_name`` path holds
    ``needle``; None without a trace or where the program did not run."""
    from benchmarks.trace import host_spans, reduce, scopes

    if not obs.get("trace"):
        return None
    path = host_spans.newest_trace()
    if path is None:
        return None
    events = _events(path)
    if not events["devices"]:
        return None
    dev = events["devices"][min(events["devices"])]
    modules = sorted(dev.get(reduce.MODULES_LINE, []), key=lambda m: m[1])
    if not any(scopes.stem(name) == program for name, _, _ in modules):
        return None
    starts = [s for _, s, _ in modules]
    ops = dev.get(reduce.OPS_LINE, [])
    total = 0
    for i, s, d in reduce.leaves(
            [(i, op[1], op[2]) for i, op in enumerate(ops)]):
        at = bisect.bisect_right(starts, s) - 1
        if at < 0 or s >= modules[at][1] + modules[at][2]:
            continue
        if (scopes.stem(modules[at][0]) == program
                and needle in (ops[i][3] or "")):
            total += d
    return total / 1e9
