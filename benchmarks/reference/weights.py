"""Weights from the seed, made by the benchmark and by nothing of the program.

One jitted call draws every leaf on the device, in the dtype it is used in
(``fold_in`` of the leaf's path, so a leaf does not depend on the others).
The tree has the layout the program's model reads (``embed.tokens``,
``blocks.attn.wq`` stacked over layers, ...); the reference reads the same
arrays. A test pins the layout against ``orion_tpu.models.init_params``."""

from __future__ import annotations

import zlib
from typing import Any, Optional

import jax
import jax.numpy as jnp

STD = 0.02
NORM_JITTER = 0.05


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)}; kind is 'normal', 'resid' or 'norm'."""
    D, V, L = hf["hidden_size"], hf["vocab_size"], hf["num_hidden_layers"]
    N, K = hf["num_attention_heads"], hf["num_key_value_heads"]
    H = hf.get("head_dim") or D // N
    F = hf["intermediate_size"]
    E = hf.get("num_local_experts", 0)
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
        ("blocks", "attn_norm", "scale"): ((L, D), "norm"),
        ("blocks", "mlp_norm", "scale"): ((L, D), "norm"),
        ("blocks", "attn", "wq"): ((L, D, N * H), "normal"),
        ("blocks", "attn", "wk"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wv"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wo"): ((L, N * H, D), "resid"),
    }
    if not hf.get("tie_word_embeddings", False):
        spec[("lm_head",)] = ((D, V), "normal")
    if E:
        spec[("blocks", "moe", "router")] = ((L, D, E), "normal")
        spec[("blocks", "moe", "w_in")] = ((L, E, D, F), "normal")
        spec[("blocks", "moe", "w_gate")] = ((L, E, D, F), "normal")
        spec[("blocks", "moe", "w_out")] = ((L, E, F, D), "resid")
    else:
        spec[("blocks", "mlp", "w_in")] = ((L, D, F), "normal")
        spec[("blocks", "mlp", "w_gate")] = ((L, D, F), "normal")
        spec[("blocks", "mlp", "w_out")] = ((L, F, D), "resid")
    return spec


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, leaf in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = leaf
    return tree


def _draw(hf: dict, dtype, key):
    L = hf["num_hidden_layers"]
    flat = {}
    for path, (shape, kind) in param_spec(hf).items():
        k = jax.random.fold_in(key, zlib.crc32("/".join(path).encode()))
        z = jax.random.normal(k, shape, dtype)
        if kind == "norm":
            flat[path] = (1.0 + NORM_JITTER * z).astype(dtype)
        else:
            std = STD / (2 * L) ** 0.5 if kind == "resid" else STD
            flat[path] = (std * z).astype(dtype)
    return _nest(flat)


def make_params(hf: dict, dtype: str, seed: int,
                out_shardings: Optional[Any] = None) -> dict:
    """The whole tree in ONE jitted call from ``seed``."""
    fn = jax.jit(
        lambda key: _draw(hf, jnp.dtype(dtype), key),
        out_shardings=out_shardings,
    )
    return fn(jax.random.key(seed % (2 ** 63)))
