"""The plain reference of Laguna-S-2.1 (poolside): the forward pass in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision, no
kernel, no cache, no batching. Written from the published ``config.json``
(the configuration file's own keys), not from the program.

Block l, pre-norm residual: ``x += Attn_l(RMSNorm(x))``, ``x += FFN_l(RMSNorm(x))``.

- Attention, N_l = ``num_attention_heads_per_layer[l]`` query heads over
  ``num_key_value_heads`` KV heads of ``head_dim``: rotary by layer type
  (``rope_parameters``): sliding layers plain RoPE on every dim, full layers
  YaRN on the first ``partial_rotary_factor`` of each head with cos and sin
  multiplied by ``attention_factor``; scores ``q k^T / sqrt(head_dim)``,
  causal, in sliding layers only keys with ``i - j < sliding_window``; the
  output of head n is multiplied by ``sigmoid(h Wg)[:, n]``.
- FFN: ``mlp_layer_types[l]`` dense: SwiGLU of ``intermediate_size``; sparse:
  router logits in float32, softmax, the ``num_experts_per_tok`` largest,
  ``w_e = moe_routed_scaling_factor p_e / sum_T p``, ``y = sum_T w_e E_e(h) +
  E_shared(h)``, every expert a SwiGLU of ``moe_intermediate_size``.

ASSUMED, because the config is silent (each is ONE function below, and one in
the program, so that the published modelling code corrects it in one line):
(a) ``gating: per-head`` is the head-wise sigmoid gate on the attention output
computed from the layer's normed input (Qiu et al., arXiv:2505.06708):
``_head_gate``; (b) router scores are a softmax, no selection bias:
``_router_scores``; (c) the shared expert is added ungated: ``_add_shared``;
(d) no q/k norm, ``hidden_act`` silu, rotate-half pairing: ``_rotate``.

The chip's share (model-configs guide, section 4): the file's ``num_experts``
is how many experts are HELD (``deployment.experts_held`` = [first, end) of
the ``published.num_experts`` the router chooses among). The router keeps its
published width and top-k, the gates are normalised over all chosen experts,
and what the absent experts would add is left out, here and in the program
alike; that partial result goes on to the next layer. The vocabulary is the
file's (a slice is a smaller vocabulary). The per-layer lists are the
published ones, 48 long; the first ``num_hidden_layers`` entries are read.

Departures, each on purpose: weights are upcast where they are used (one
matrix in float32 at a time); attention runs one query head and one block of
512 queries at a time, and the held experts one at a time under ``lax.scan``
over all positions (a gate of 0 where an expert was not chosen), so that the
reference fits beside 13.4 GiB of weights and cache; logits are taken only at
the positions asked for. ``quant="int8"`` is the CONTROL (``model._matmul``):
both operands of every weight matmul rounded to int8; the router's matmul
stays float32, as in ``reference/model.py``."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.model import F32, _matmul, _rmsnorm, _up

Q_BLOCK = 512


# -- what the config says of each layer ---------------------------------------


def _layers(hf: dict) -> list:
    """(attention type, query heads, 'dense' | 'sparse') of each layer run."""
    n = hf["num_hidden_layers"]
    return list(zip(hf["layer_types"][:n],
                    hf["num_attention_heads_per_layer"][:n],
                    hf["mlp_layer_types"][:n]))


def _plan(hf: dict) -> tuple[int, int]:
    """(lead, period): the program keeps the leading dense layers each on
    their own and stacks what follows by its position in the smallest period
    (layer lead + g * period + j is entry g of stack j)."""
    kinds = _layers(hf)
    lead = 0
    while lead < len(kinds) and kinds[lead][2] == "dense":
        lead += 1
    rest = kinds[lead:]
    period = next(p for p in range(1, len(rest) + 1)
                  if all(rest[i] == rest[i % p] for i in range(len(rest))))
    return lead, period


def _where(hf: dict, layer: int) -> tuple:
    """(path of the layer's block in the tree, its index in the stack or
    None)."""
    lead, period = _plan(hf)
    if layer < lead:
        return ("blocks", "lead", str(layer)), None
    g, j = divmod(layer - lead, period)
    return ("blocks", "period", str(j)), g


def param_spec(hf: dict) -> dict:
    """{path: (shape, kind)} in the layout the program's model reads."""
    D, V = hf["hidden_size"], hf["vocab_size"]
    K, H = hf["num_key_value_heads"], hf["head_dim"]
    E, Er = hf["num_experts"], hf["published"]["num_experts"]
    Fe, Fs = hf["moe_intermediate_size"], hf["shared_expert_intermediate_size"]
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
    }
    depth: dict = {}
    for layer in range(hf["num_hidden_layers"]):
        path, g = _where(hf, layer)
        depth[path] = None if g is None else g + 1
    for layer, (_, N, ffn) in enumerate(_layers(hf)):
        path, g = _where(hf, layer)
        if g not in (None, 0):
            continue                      # a later entry of a stack
        lead = () if g is None else (depth[path],)
        block = {
            ("attn_norm", "scale"): ((D,), "norm"),
            ("mlp_norm", "scale"): ((D,), "norm"),
            ("attn", "wq"): ((D, N * H), "normal"),
            ("attn", "wk"): ((D, K * H), "normal"),
            ("attn", "wv"): ((D, K * H), "normal"),
            ("attn", "wg"): ((D, N), "normal"),
            ("attn", "wo"): ((N * H, D), "resid"),
        }
        if ffn == "dense":
            F = hf["intermediate_size"]
            block.update({("mlp", "w_in"): ((D, F), "normal"),
                          ("mlp", "w_gate"): ((D, F), "normal"),
                          ("mlp", "w_out"): ((F, D), "resid")})
        else:
            block.update({
                ("moe", "router"): ((D, Er), "normal"),
                ("moe", "w_in"): ((E, D, Fe), "normal"),
                ("moe", "w_gate"): ((E, D, Fe), "normal"),
                ("moe", "w_out"): ((E, Fe, D), "resid"),
                ("moe", "shared", "w_in"): ((D, Fs), "normal"),
                ("moe", "shared", "w_gate"): ((D, Fs), "normal"),
                ("moe", "shared", "w_out"): ((Fs, D), "resid"),
            })
        for leaf, (shape, kind) in block.items():
            spec[path + leaf] = (lead + shape, kind)
    return spec


def _block(params, hf: dict, layer: int):
    path, g = _where(hf, layer)
    node = params
    for part in path:
        node = node[part]
    return node if g is None else jax.tree.map(lambda a: a[g], node)


# -- rotary -------------------------------------------------------------------


def _inv_freq(rope: dict, head_dim: int):
    """(inverse frequencies of the rotated pairs, factor on cos and sin,
    rotated dims) of one entry of ``rope_parameters``."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1))
    f = [rope["rope_theta"] ** (2 * i / rot) for i in range(rot // 2)]
    if rope["rope_type"] == "default":
        return jnp.asarray([1 / x for x in f], F32), 1.0, rot
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def correction_dim(rotations):
        return (rot * math.log(rope["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))
                / (2 * math.log(rope["rope_theta"])))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    inv = []
    for i, x in enumerate(f):
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append((1 - ramp) / x + ramp / (rope["factor"] * x))
    return jnp.asarray(inv, F32), rope["attention_factor"], rot


def _rotate(x, positions, rope: dict):
    """x [S, n, H]: the first ``rot`` dims of each head rotate, rotate-half
    pairing (dim i with dim i + rot / 2); the others pass through."""
    inv, scale, rot = _inv_freq(rope, x.shape[-1])
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


# -- attention ----------------------------------------------------------------


def _attention(q, k, v, window: Optional[int]):
    """q [S, N, H], k/v [S, K, H] -> [S, N, H]: the full masked softmax, one
    query head and one block of ``Q_BLOCK`` queries at a time. Query head n
    reads KV head n // (N / K)."""
    S, N, H = q.shape
    group = N // k.shape[1]
    n_blocks = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, n_blocks * Q_BLOCK - S), (0, 0), (0, 0)))
    qb = qb.reshape(n_blocks, Q_BLOCK, N, H)
    k_pos = jnp.arange(S)

    def one_head(n):
        kh, vh = k[:, n // group], v[:, n // group]           # [S, H]

        def one_block(b):
            q_pos = b * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.matmul(qb[b, :, n], kh.T) / math.sqrt(H)
            mask = k_pos[None, :] <= q_pos[:, None]
            if window is not None:
                mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
            return jnp.matmul(
                jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1), vh)

        return jax.lax.map(one_block, jnp.arange(n_blocks))

    out = jax.lax.map(one_head, jnp.arange(N))     # [N, blocks, Q_BLOCK, H]
    return out.reshape(N, n_blocks * Q_BLOCK, H)[:, :S].transpose(1, 0, 2)


def _head_gate(h, wg):
    """ASSUMED (a): [S, N] sigmoid gate, one number a head, from the layer's
    normed input."""
    return jax.nn.sigmoid(jnp.matmul(h, _up(wg)))


# -- feed-forward -------------------------------------------------------------


def _swiglu(x, p, quant):
    h = jax.nn.silu(_matmul(x, _up(p["w_gate"]), quant)) * _matmul(
        x, _up(p["w_in"]), quant)
    return _matmul(h, _up(p["w_out"]), quant)


def _router_scores(logits):
    """ASSUMED (b): a softmax over all experts, no selection bias."""
    return jax.nn.softmax(logits, axis=-1)


def _add_shared(y, shared):
    """ASSUMED (c): the shared expert is added ungated."""
    return y + shared


def _moe(h, p, hf: dict, quant):
    """(this chip's part of the layer's output, the router's margin [S]:
    the logit of the last expert chosen less that of the first left out)."""
    k = hf["num_experts_per_tok"]
    logits = jnp.matmul(h, _up(p["router"]))                  # [S, 256]
    ranked = jax.lax.top_k(logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    top, idx = jax.lax.top_k(_router_scores(logits), k)
    weight = hf["moe_routed_scaling_factor"] * top / jnp.sum(
        top, axis=-1, keepdims=True)                  # over ALL k chosen
    gates = jnp.zeros_like(logits).at[
        jnp.arange(h.shape[0])[:, None], idx].set(weight)
    first, end = hf["deployment"]["experts_held"]
    assert end - first == p["w_in"].shape[0] == hf["num_experts"]

    def one_expert(y, ew):
        w_in, w_gate, w_out, g = ew
        e = _swiglu(h, {"w_in": w_in, "w_gate": w_gate, "w_out": w_out}, quant)
        return y + g[:, None] * e, None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (p["w_in"], p["w_gate"], p["w_out"], gates[:, first:end].T))
    return _add_shared(y, _swiglu(h, p["shared"], quant)), margin


# -- the model ----------------------------------------------------------------


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the smallest router margin over the sparse layers at each of them."""
    K, H = hf["num_key_value_heads"], hf["head_dim"]
    eps = hf["rms_norm_eps"]
    S = tokens.shape[0]
    positions = jnp.arange(S)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for layer, (kind, N, ffn) in enumerate(_layers(hf)):
            bp, rope = _block(params, hf, layer), hf["rope_parameters"][kind]
            a = bp["attn"]
            h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
            q = _rotate(_matmul(h, _up(a["wq"]), quant).reshape(S, N, H),
                        positions, rope)
            k = _rotate(_matmul(h, _up(a["wk"]), quant).reshape(S, K, H),
                        positions, rope)
            v = _matmul(h, _up(a["wv"]), quant).reshape(S, K, H)
            window = (hf["sliding_window"] if kind == "sliding_attention"
                      else None)
            o = _attention(q, k, v, window) * _head_gate(h, a["wg"])[..., None]
            x = x + _matmul(o.reshape(S, N * H), _up(a["wo"]), quant)
            h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
            if ffn == "dense":
                x = x + _swiglu(h, bp["mlp"], quant)
            else:
                y, margin = _moe(h, bp["moe"], hf, quant)
                x = x + y
                margins.append(margin)
        x = _rmsnorm(x[at], _up(params["final_norm"]["scale"]), eps)
        margin = (jnp.stack(margins).min(axis=0)[at] if margins
                  else jnp.full(x.shape[:1], jnp.inf))
        return _matmul(x, _up(params["lm_head"]), quant), margin
