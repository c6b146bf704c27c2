"""Operations and bytes that the ALGORITHM needs, from shapes. Counted here,
under the benchmark, and tested on hand-computed shapes: what the program
moves or recomputes does not enter.

A matmul of [m, k] x [k, n] is 2 m k n. Training is forward + backward = 3x
the forward matmuls. Attention counts only the (query, key) pairs the causal
mask and the sliding window leave."""

from __future__ import annotations


def attended_pairs(seq_len: int, window=None) -> int:
    """Sum over query positions of the keys each attends (itself included)."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    head = window * (window + 1) // 2           # queries 0 .. window-1
    return head + (seq_len - window) * window   # the rest see `window` keys


def _dims(hf: dict):
    D, N, K = hf["hidden_size"], hf["num_attention_heads"], hf["num_key_value_heads"]
    H = hf.get("head_dim") or D // N
    return D, N, K, H, hf["intermediate_size"], hf["num_hidden_layers"], hf["vocab_size"]


def forward_matmul_flops_per_token(hf: dict) -> float:
    """Weight matmuls of one token's forward pass (embedding lookup is a
    gather, not a matmul; the output head is one)."""
    D, N, K, H, F, L, V = _dims(hf)
    attn = 2 * D * (N * H) + 2 * 2 * D * (K * H) + 2 * (N * H) * D
    experts = hf.get("num_experts_per_tok", 1) if hf.get("num_local_experts") else 1
    mlp = 3 * 2 * D * F * experts
    router = 2 * D * hf["num_local_experts"] if hf.get("num_local_experts") else 0
    return L * (attn + mlp + router) + 2 * D * V


def attention_flops(hf: dict, seq_len: int) -> float:
    """Forward score and value matmuls of ONE sequence, all layers."""
    D, N, K, H, F, L, V = _dims(hf)
    pairs = attended_pairs(seq_len, hf.get("sliding_window"))
    return L * N * pairs * (2 * H + 2 * H)


def train_flops_per_token(hf: dict, seq_len: int) -> float:
    fwd = forward_matmul_flops_per_token(hf) + attention_flops(hf, seq_len) / seq_len
    return 3.0 * fwd


def flash_fwd_bwd_flops(hf: dict, seq_len: int, batch: int) -> float:
    """Flash attention forward + backward of a step. The forward is two
    matmuls a (query, key) pair (scores, values); the backward needs four
    (dV, dP, dQ, dK). The scores the kernel recomputes in its backward, and
    the forward that remat runs a second time, are the program's and are not
    counted: 3x the forward."""
    return 3.0 * batch * attention_flops(hf, seq_len)


def expert_weight_bytes(hf: dict, dtype_bytes: int = 2) -> int:
    """All experts' three matrices, all layers."""
    D, N, K, H, F, L, V = _dims(hf)
    return L * hf["num_local_experts"] * 3 * D * F * dtype_bytes


def expert_matmul_flops(hf: dict, rows_per_layer: int) -> float:
    """The three matmuls of a gated expert feed-forward (in and gate [D, F],
    out [F, D]: 2 D F each) over ``rows_per_layer`` (token, expert) rows in
    each of the layers."""
    D, N, K, H, F, L, V = _dims(hf)
    return L * rows_per_layer * 3 * 2 * D * F
