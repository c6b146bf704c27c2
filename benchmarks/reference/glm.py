"""The plain reference of GLM-4.7-Flash (zai-org, ``model_type``
``glm4_moe_lite``): the forward pass in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision, no kernel, no cache, no batching.
Written from the published ``config.json`` (the configuration file's own
keys), not from the program. EXPANDED form only: keys and values of every
head are rebuilt from the compressed row at every position; the absorbed
form, the paged rows and the decode kernel are the program's and are what
this is compared with.

Block l, pre-norm residual: ``x += Attn(RMSNorm(x))``, ``x += FFN_l(RMSNorm(x))``.

- Attention (x the normed input): ``c_q = RMSNorm(x Wq_a)`` (``q_lora_rank``);
  ``q = c_q Wq_b`` -> heads x (``qk_nope_head_dim`` | ``qk_rope_head_dim``),
  the rope part rotated; ``[c_kv | k_pe] = x Wkv_a`` (``kv_lora_rank`` |
  ``qk_rope_head_dim``), ``c_kv = RMSNorm(c_kv)``, ``k_pe`` rotated, ONE
  rotary key for all heads; ``[k_nope | v] = c_kv Wkv_b`` -> heads x
  (``qk_nope_head_dim`` | ``v_head_dim``); ``k = [k_nope | k_pe]``; causal
  softmax of ``q . k / sqrt(qk_nope + qk_rope)`` (``rope_scaling`` null: no
  mscale); ``y = (softmax . v) Wo``. No bias anywhere (``attention_bias``).
- FFN: the first ``first_k_dense_replace`` layers a SwiGLU of
  ``intermediate_size``; the others ``n_routed_experts`` experts and
  ``n_shared_experts`` shared, each a SwiGLU of ``moe_intermediate_size``;
  router (``topk_method`` ``noaux_tc``, ``n_group`` = ``topk_group`` = 1: one
  group, so no group selection): ``s = sigmoid(x Wr)`` in float32, the
  ``num_experts_per_tok`` largest of ``s + b`` (``e_score_correction_bias``)
  are chosen, the gates are ``s`` there WITHOUT ``b``, divided by their sum
  + 1e-20 (``norm_topk_prob``), times ``routed_scaling_factor``.
- Final RMSNorm, untied head. The multi-token-prediction module
  (``num_nextn_predict_layers``) is a draft head beside the layers and no
  part of these logits: left out, here and in the program.

ASSUMED, because the config is silent (each ONE function below and one in the
program; the configuration file's ``assumed`` names both): (a) the rotary
pairing is rotate-half (dim i with dim i + 32 of the 64): ``model._rope``;
random weights cannot tell it from the interleaved pairing, the reference
and the program have to agree, and do; (b) the router's scores are float32:
``_router``; (c) the shared expert is added ungated: ``_moe``; (d) the bias
``b`` is drawn as a 'norm' leaf, 1 + 0.05 N(0, 1): ``param_spec``.

Departures, each on purpose: weights are upcast where they are used (one
matrix in float32 at a time); attention runs one query head and one block of
512 queries at a time, and the experts one at a time under ``lax.scan`` over
all positions (a gate of 0 where an expert was not chosen), so that the
reference fits beside 13 GB of weights and cache at 20496 positions; logits
are taken only at the positions asked for. ``quant="int8"`` is the CONTROL
(``model._matmul``): both operands of every weight matmul rounded to int8;
the router's matmul stays float32, as in ``reference/model.py``."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.model import F32, _matmul, _rmsnorm, _rope, _up

Q_BLOCK = 512


def _is_dense(hf: dict, layer: int) -> bool:
    return layer < hf["first_k_dense_replace"]


def _where(hf: dict, layer: int) -> tuple:
    """(path of the layer's block in the tree, its index in the stack or
    None): the program keeps the leading dense layers each on their own and
    stacks the sparse ones (a period of one layer)."""
    lead = min(hf["first_k_dense_replace"], hf["num_hidden_layers"])
    if layer < lead:
        return ("blocks", "lead", str(layer)), None
    return ("blocks", "period", "0"), layer - lead


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)} in the layout the program's model reads."""
    D, V, N = hf["hidden_size"], hf["vocab_size"], hf["num_attention_heads"]
    qr, R = hf["q_lora_rank"], hf["kv_lora_rank"]
    nope, rope, vd = (hf["qk_nope_head_dim"], hf["qk_rope_head_dim"],
                      hf["v_head_dim"])
    E, Fe = hf["n_routed_experts"], hf["moe_intermediate_size"]
    Fs = hf["n_shared_experts"] * Fe
    L = hf["num_hidden_layers"]
    lead = min(hf["first_k_dense_replace"], L)
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
    }
    attn = {
        ("attn_norm", "scale"): ((D,), "norm"),
        ("mlp_norm", "scale"): ((D,), "norm"),
        ("attn", "wq_a"): ((D, qr), "normal"),
        ("attn", "q_a_norm"): ((qr,), "norm"),
        ("attn", "wq_b"): ((qr, N * (nope + rope)), "normal"),
        ("attn", "wkv_a"): ((D, R + rope), "normal"),
        ("attn", "kv_a_norm"): ((R,), "norm"),
        ("attn", "wkv_b"): ((R, N * (nope + vd)), "normal"),
        ("attn", "wo"): ((N * vd, D), "resid"),
    }
    F = hf["intermediate_size"]
    dense = {("mlp", "w_in"): ((D, F), "normal"),
             ("mlp", "w_gate"): ((D, F), "normal"),
             ("mlp", "w_out"): ((F, D), "resid")}
    sparse = {
        ("moe", "router"): ((D, E), "normal"),
        # ASSUMED (d): 1 + 0.05 N(0, 1). The 1 is common to all experts and
        # moves no choice; the jitter does (tests/test_glm.py states the
        # share), and a gate that held the bias would be off by it.
        ("moe", "router_bias"): ((E,), "norm"),
        ("moe", "w_in"): ((E, D, Fe), "normal"),
        ("moe", "w_gate"): ((E, D, Fe), "normal"),
        ("moe", "w_out"): ((E, Fe, D), "resid"),
        ("moe", "shared", "w_in"): ((D, Fs), "normal"),
        ("moe", "shared", "w_gate"): ((D, Fs), "normal"),
        ("moe", "shared", "w_out"): ((Fs, D), "resid"),
    }
    for layer in range(lead):
        path, _ = _where(hf, layer)
        for leaf, sk in {**attn, **dense}.items():
            spec[path + leaf] = sk
    if L > lead:
        path, _ = _where(hf, lead)
        for leaf, (shape, kind) in {**attn, **sparse}.items():
            spec[path + leaf] = ((L - lead,) + shape, kind)
    return spec


_EXPERTS = ("w_in", "w_gate", "w_out")


def _block(params, hf: dict, layer: int):
    """The layer's weights. Of a stacked sparse layer the routed experts'
    three leaves stay whole, beside the layer's index (``_moe`` takes one
    expert's matrix out of the stack at a time: a slice of a layer's 64
    experts is 1.27 GB, and the compiler kept all five alive at once)."""
    path, g = _where(hf, layer)
    node = params
    for part in path:
        node = node[part]
    if g is None:
        return node
    moe = node["moe"]
    out = jax.tree.map(lambda a: a[g], {
        **node, "moe": {k: v for k, v in moe.items() if k not in _EXPERTS}})
    out["moe"]["experts"] = ({k: moe[k] for k in _EXPERTS}, g)
    return out


# -- attention ----------------------------------------------------------------


def _rotate(x, positions, hf: dict):
    """ASSUMED (a): x [S, n, qk_rope_head_dim], rotate-half pairing over all
    of it (``partial_rotary_factor`` 1), plain table at ``rope_theta``."""
    return _rope(x, positions, hf["rope_theta"])


def _head_attention(q, k, v):
    """One head: q/k [S, Hk], v [S, Hv] -> [S, Hv], the full causal softmax
    one block of ``Q_BLOCK`` queries at a time."""
    S, H = q.shape
    n_blocks = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, n_blocks * Q_BLOCK - S), (0, 0)))
    qb = qb.reshape(n_blocks, Q_BLOCK, H)
    k_pos = jnp.arange(S)

    def one_block(b):
        q_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.matmul(qb[b], k.T) / math.sqrt(H)
        mask = k_pos[None, :] <= q_pos[:, None]
        return jnp.matmul(
            jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), v)

    out = jax.lax.map(one_block, jnp.arange(n_blocks))
    return out.reshape(n_blocks * Q_BLOCK, -1)[:S]


def _latent_attention(h, a, positions, hf: dict, quant):
    """Equations 1-3: the expanded form, from the layer's normed input. One
    head at a time, its keys and values rebuilt from the compressed rows
    there (a column block of ``Wq_b`` / ``Wkv_b``: the same numbers as the
    whole product's), so that 20 heads of 20496 positions never exist at
    once."""
    S, N, eps = h.shape[0], hf["num_attention_heads"], hf["rms_norm_eps"]
    R, nope, rope, vd = (hf["kv_lora_rank"], hf["qk_nope_head_dim"],
                         hf["qk_rope_head_dim"], hf["v_head_dim"])
    c_q = _rmsnorm(_matmul(h, _up(a["wq_a"]), quant), _up(a["q_a_norm"]), eps)
    row = _matmul(h, _up(a["wkv_a"]), quant)                  # [S, R + rope]
    c_kv = _rmsnorm(row[:, :R], _up(a["kv_a_norm"]), eps)
    k_pe = _rotate(row[:, None, R:], positions, hf)[:, 0]     # [S, rope]
    wq = _up(a["wq_b"]).reshape(-1, N, nope + rope).transpose(1, 0, 2)
    wkv = _up(a["wkv_b"]).reshape(R, N, nope + vd).transpose(1, 0, 2)

    def one_head(w):
        wq_n, wkv_n = w
        q = _matmul(c_q, wq_n, quant)                         # [S, nope+rope]
        q = jnp.concatenate(
            [q[:, :nope], _rotate(q[:, None, nope:], positions, hf)[:, 0]], -1)
        kv = _matmul(c_kv, wkv_n, quant)                      # [S, nope + vd]
        k = jnp.concatenate([kv[:, :nope], k_pe], -1)
        return _head_attention(q, k, kv[:, nope:])

    o = jax.lax.map(one_head, (wq, wkv))                      # [N, S, vd]
    return _matmul(o.transpose(1, 0, 2).reshape(S, N * vd), _up(a["wo"]),
                   quant)


# -- feed-forward -------------------------------------------------------------


def _swiglu(x, p, quant):
    h = jax.nn.silu(_matmul(x, _up(p["w_gate"]), quant)) * _matmul(
        x, _up(p["w_in"]), quant)
    return _matmul(h, _up(p["w_out"]), quant)


def _router(h, p, hf: dict):
    """Equation 5 -> (gates [S, E], zero where an expert was not chosen;
    the margin [S]: on ``s + b``, what the choice is made on, the last
    expert chosen less the first left out). ASSUMED (b): float32 scores."""
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(h, _up(p["router"])))       # [S, E]
    chosen_on = s + _up(p["router_bias"])[None, :]
    ranked, idx = jax.lax.top_k(chosen_on, k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    at = jnp.arange(h.shape[0])[:, None]
    top = s[at, idx[:, :k]]                                   # WITHOUT b
    if hf["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * hf["routed_scaling_factor"]
    return jnp.zeros_like(s).at[at, idx[:, :k]].set(top), margin


def _moe(h, p, hf: dict, quant):
    gates, margin = _router(h, p, hf)
    stack, layer = p["experts"]

    def one_expert(y, eg):
        e, g = eg
        out = _swiglu(h, {k: stack[k][layer, e] for k in _EXPERTS}, quant)
        return y + g[:, None] * out, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(gates.shape[1]), gates.T))
    # ASSUMED (c): the shared expert is added ungated.
    return y + _swiglu(h, p["shared"], quant), margin


# -- the model ----------------------------------------------------------------


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the smallest router margin over the sparse layers at each of them."""
    eps = hf["rms_norm_eps"]
    positions = jnp.arange(tokens.shape[0])
    margins = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for layer in range(hf["num_hidden_layers"]):
            bp = _block(params, hf, layer)
            h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
            x = x + _latent_attention(h, bp["attn"], positions, hf, quant)
            h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
            if _is_dense(hf, layer):
                x = x + _swiglu(h, bp["mlp"], quant)
            else:
                y, margin = _moe(h, bp["moe"], hf, quant)
                x = x + y
                margins.append(margin)
        x = _rmsnorm(x[at], _up(params["final_norm"]["scale"]), eps)
        margin = (jnp.stack(margins).min(axis=0)[at] if margins
                  else jnp.full(x.shape[:1], jnp.inf))
        return _matmul(x, _up(params["lm_head"]), quant), margin
