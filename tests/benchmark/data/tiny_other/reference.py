"""The plain reference of the tiny configuration that is not Mistral (the
tests' stand-in for what a later PR brings with a new architecture): a dense
GQA decoder whose source names its sizes otherwise (``kv_channels``,
``ffn_hidden_size``, ``multi_query_group_num``, ``num_layers``,
``padded_vocab_size``), gives a head size that is not hidden / heads, and adds
a bias to the q, k and v projections. It reads the configuration file's own
keys and states the interface a reference has: ``param_spec`` and
``logits_at`` (served only, so no ``loss``). The layer loop is a Python loop:
two layers of width 96 need no scan."""

import jax
import jax.numpy as jnp

from benchmarks.reference.model import (F32, _attention, _dense_mlp, _matmul,
                                        _rmsnorm, _rope)


def _sizes(hf):
    return (hf["hidden_size"], hf["num_attention_heads"],
            hf["multi_query_group_num"], hf["kv_channels"])


def param_spec(hf: dict) -> dict:
    D, N, K, H = _sizes(hf)
    L, V, F = hf["num_layers"], hf["padded_vocab_size"], hf["ffn_hidden_size"]
    return {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
        ("blocks", "attn_norm", "scale"): ((L, D), "norm"),
        ("blocks", "mlp_norm", "scale"): ((L, D), "norm"),
        ("blocks", "attn", "wq"): ((L, D, N * H), "normal"),
        ("blocks", "attn", "wk"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "wv"): ((L, D, K * H), "normal"),
        ("blocks", "attn", "bq"): ((L, N * H), "normal"),
        ("blocks", "attn", "bk"): ((L, K * H), "normal"),
        ("blocks", "attn", "bv"): ((L, K * H), "normal"),
        ("blocks", "attn", "wo"): ((L, N * H, D), "resid"),
        ("blocks", "mlp", "w_in"): ((L, D, F), "normal"),
        ("blocks", "mlp", "w_gate"): ((L, D, F), "normal"),
        ("blocks", "mlp", "w_out"): ((L, F, D), "resid"),
    }


def logits_at(params, tokens, at, hf: dict, quant=None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``; a
    dense model has no router, so the margin is infinite everywhere."""
    D, N, K, H = _sizes(hf)
    eps, theta = hf["layernorm_epsilon"], hf["rope_theta"]
    S = tokens.shape[0]
    positions = jnp.arange(S)
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for layer in range(hf["num_layers"]):
            bp = jax.tree.map(lambda w: w[layer].astype(F32), params["blocks"])
            a = bp["attn"]
            h = _rmsnorm(x, bp["attn_norm"]["scale"], eps)
            q = _matmul(h, a["wq"], quant) + a["bq"]
            k = _matmul(h, a["wk"], quant) + a["bk"]
            v = _matmul(h, a["wv"], quant) + a["bv"]
            o = _attention(_rope(q.reshape(S, N, H), positions, theta),
                           _rope(k.reshape(S, K, H), positions, theta),
                           v.reshape(S, K, H), None)
            x = x + _matmul(o.reshape(S, N * H), a["wo"], quant)
            h = _rmsnorm(x, bp["mlp_norm"]["scale"], eps)
            x = x + _dense_mlp(h, bp["mlp"], quant)
        x = _rmsnorm(x[at], params["final_norm"]["scale"].astype(F32), eps)
        return (_matmul(x, params["lm_head"].astype(F32), quant),
                jnp.full(x.shape[:1], jnp.inf))
