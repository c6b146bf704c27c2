"""The plain reference of MiMo-V2.5's language model (XiaomiMiMo, ``model_type:
mimo_v2``): the forward pass in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision, no kernel, no cache, no batching. Written from
the published ``config.json`` (the configuration file's own keys), not from
the program.

Block l of ``num_hidden_layers``, pre-norm residual, RMSNorm with
``layernorm_epsilon``, no bias, untied head: ``x += Attn_l(RMSNorm(x))``,
``x += FFN_l(RMSNorm(x))``.

- Kind: ``hybrid_layer_pattern[l]`` 1 = window layer, 0 = full layer;
  ``moe_layer_freq[l]`` 0 = dense gated-SiLU MLP ``intermediate_size`` wide,
  1 = sparse.
- Attention of kind k: ``num_attention_heads`` query heads of ``head_dim``;
  K/V heads ``num_key_value_heads`` (full) or ``swa_num_key_value_heads``
  (window), keys ``head_dim`` wide, values ``v_head_dim`` wide and multiplied
  by ``attention_value_scale``. Rotary on the first ``int(head_dim x
  partial_rotary_factor)`` dims of each q and k head, rotate-half pairing,
  theta ``rope_theta`` on full layers and ``swa_rope_theta`` on window layers.
  Scores ``q k^T / sqrt(head_dim)``, causal; on window layers only keys with
  ``i - j < sliding_window``.
- Sink (``add_swa_attention_sink_bias`` / ``add_full_attention_sink_bias``):
  one learned scalar ``b_h`` a query head; ``p_ij = exp(s_ij) / (sum_j'
  exp(s_ij') + exp(b_h))``: a softmax over the row's live scores and ``b_h``
  with the sink's column dropped, so a row's weights add up to less than 1.
- Sparse FFN: ``s = sigmoid(h Wr)`` over the published ``n_routed_experts``
  (``scoring_func``), the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` (``topk_method: noaux_tc``; ``n_group`` =
  ``topk_group`` = 1: no group limit), gates the chosen ``s`` WITHOUT the
  bias, normalised to sum 1 (``norm_topk_prob``), no further scale
  (``routed_scaling_factor`` null); experts gated-SiLU
  ``moe_intermediate_size`` wide; no shared expert.

ASSUMED, because the config is silent (each is ONE function below, and one in
the program): (a) no q/k norm: ``_attention``; (b) the sink enters the softmax as
written above, the form the family's public code uses (the config names only
the flag): ``_softmax_under_sink``; (c) ``attention_chunk_size`` 128 is the
source's kernel block, equal to the window, NOT a block-local mask: the mask
is the sliding one, ``_mask``; (d) ``attention_projection_layout: fused_qkv``
is a weight layout, not a shape: the tree keeps wq, wk, wv apart; (e) float32
router scores. The sink logits are drawn uniform over [0, 4): drawn as N(0,
0.02) like a matrix, a sink would hold 1/129 of a window row's mass (every
score of N(0, 0.02) weights is within a tenth of 0) and leaving it out would
move no logit the check can see; a trained sink takes a share of the mass of
the order of tenths, which [0, 4) gives over rows of 1 to 128 live keys
(exp(b) / (n + exp(b)): 0.8 % to 98 %).

The chip's share (model-configs guide, section 4): the file's
``n_routed_experts`` is how many experts are HELD (``deployment.experts_held``
= [first, end) of the ``published.n_routed_experts`` the router chooses
among). The router keeps its published width and top-k, the gates are
normalised over all chosen experts, and what the absent experts would add is
left out, here and in the program alike. The vocabulary is the file's (a
slice is a smaller vocabulary). The per-layer lists are the published ones,
48 long; the first ``num_hidden_layers`` entries are read. Left out of the
model as served: the vision and audio towers and the multi-token-prediction
layers (the catalog's ``config`` is the language model's and has no key for
them).

Departures, each on purpose, so that the reference fits in the 3.2 GB a 16 GB
chip has left beside this configuration's weights and cache (the compiler is
told of the weights and not of the cache): a layer's matrices are upcast
where they are used and NOT BEFORE (``_held_back`` ties a layer's stacks to
the layer's input: left free, the compiler slices every layer's block out of
its stack and upcasts it at the program's start, 4-6 GB), and an expert's
matrices are read out of the stack one expert at a time; a query head is projected, rotated and attended on
its own, one block of ``Q_BLOCK`` queries at a time, so that no [S, 64, 192]
float32 array exists; the dense MLP runs ``ROW_BLOCK`` rows at a time and the
held experts one at a time under ``lax.scan`` (a gate of 0 where an expert
was not chosen); logits are taken only at the positions asked for. ``quant="int8"`` is the CONTROL
(``model._matmul``): both operands of every weight matmul rounded to int8;
the router's matmul stays float32, as in ``reference/model.py``."""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.reference.model import F32, _matmul, _rmsnorm, _up

Q_BLOCK = 512
ROW_BLOCK = 2048
SINK_RANGE = ("uniform", 0.0, 4.0)


# -- what the config says of each layer ---------------------------------------


def _layers(hf: dict) -> list:
    """('window' | 'full', 'dense' | 'sparse') of each layer run."""
    n = hf["num_hidden_layers"]
    return [("window" if w else "full", "sparse" if s else "dense")
            for w, s in zip(hf["hybrid_layer_pattern"][:n],
                            hf["moe_layer_freq"][:n])]


def _kv_heads(hf: dict, kind: str) -> int:
    return hf["swa_num_key_value_heads" if kind == "window"
              else "num_key_value_heads"]


def _has_sink(hf: dict, kind: str) -> bool:
    return bool(hf["add_swa_attention_sink_bias" if kind == "window"
                   else "add_full_attention_sink_bias"])


def _plan(hf: dict) -> tuple[int, int]:
    """(lead, period): the program keeps the leading dense layers each on
    their own and stacks what follows by its position in the smallest period
    (layer lead + g * period + j is entry g of stack j)."""
    kinds = _layers(hf)
    lead = 0
    while lead < len(kinds) and kinds[lead][1] == "dense":
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
    N, H, Hv = hf["num_attention_heads"], hf["head_dim"], hf["v_head_dim"]
    E, Er = hf["n_routed_experts"], hf["published"]["n_routed_experts"]
    Fe = hf["moe_intermediate_size"]
    spec = {
        ("embed", "tokens"): ((V, D), "normal"),
        ("lm_head",): ((D, V), "normal"),
        ("final_norm", "scale"): ((D,), "norm"),
    }
    depth: dict = {}
    for layer in range(hf["num_hidden_layers"]):
        path, g = _where(hf, layer)
        depth[path] = None if g is None else g + 1
    for layer, (kind, ffn) in enumerate(_layers(hf)):
        path, g = _where(hf, layer)
        if g not in (None, 0):
            continue                      # a later entry of a stack
        lead = () if g is None else (depth[path],)
        K = _kv_heads(hf, kind)
        block = {
            ("attn_norm", "scale"): ((D,), "norm"),
            ("mlp_norm", "scale"): ((D,), "norm"),
            ("attn", "wq"): ((D, N * H), "normal"),
            ("attn", "wk"): ((D, K * H), "normal"),
            ("attn", "wv"): ((D, K * Hv), "normal"),
            ("attn", "wo"): ((N * Hv, D), "resid"),
        }
        if _has_sink(hf, kind):
            block[("attn", "sink")] = ((N,), SINK_RANGE)
        if ffn == "dense":
            F = hf["intermediate_size"]
            block.update({("mlp", "w_in"): ((D, F), "normal"),
                          ("mlp", "w_gate"): ((D, F), "normal"),
                          ("mlp", "w_out"): ((F, D), "resid")})
        else:
            block.update({
                ("moe", "router"): ((D, Er), "normal"),
                # Around 1 and not around 0, as in reference/glm.py: the
                # jitter moves choices, and a gate that held the bias would
                # be off by it.
                ("moe", "router_bias"): ((Er,), "norm"),
                ("moe", "w_in"): ((E, D, Fe), "normal"),
                ("moe", "w_gate"): ((E, D, Fe), "normal"),
                ("moe", "w_out"): ((E, Fe, D), "resid"),
            })
        for leaf, (shape, kind_) in block.items():
            spec[path + leaf] = (lead + shape, kind_)
    return spec


def _stack(params, hf: dict, layer: int):
    """(the node of the tree that holds the layer's block, the block's index
    in it or None)."""
    path, g = _where(hf, layer)
    node = params
    for part in path:
        node = node[part]
    return node, g


def _entry(node, g):
    """Entry ``g`` of every leaf of a stack (the node itself where g is
    None)."""
    return node if g is None else jax.tree.map(lambda a: a[g], node)


# -- attention ----------------------------------------------------------------


def _rotate(x, positions, theta: float, rot: int):
    """x [S, n, H]: the first ``rot`` dims of each head rotate, rotate-half
    pairing (dim i with dim i + rot / 2); the others pass through."""
    inv = jnp.asarray(
        [theta ** (-2 * i / rot) for i in range(rot // 2)], F32)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], -1)


def _held_back(tree, x):
    """``tree`` (a layer's matrices, as stored) and ``x`` (the layer's
    input), neither to be computed on before both are there: the upcasts of
    ``tree`` then cannot be moved ahead of the layers before this one."""
    return jax.lax.optimization_barrier((tree, x))


def _mask(q_pos, k_pos, window: Optional[int]):
    """ASSUMED (c): causal, and on a window layer the SLIDING mask ``i - j <
    window`` (``attention_chunk_size`` is no block-local mask)."""
    mask = k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask = mask & (q_pos[:, None] - k_pos[None, :] < window)
    return mask


def _softmax_under_sink(s, mask, sink):
    """ASSUMED (b): the weights of scores ``s`` [Q, S] under ``mask``; with
    ``sink`` (a scalar, the head's) one more term in the denominator and no
    column."""
    s = jnp.where(mask, s, -jnp.inf)
    m = s.max(axis=-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink)
    p = jnp.exp(s - m)
    denom = p.sum(axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sink - m)
    return p / denom


def _one_head(q, k, v, window: Optional[int], sink):
    """q [S, H], k [S, H], v [S, Hv] -> [S, Hv]: the full masked softmax of
    one query head over its K/V head, a block of ``Q_BLOCK`` queries at a
    time."""
    S, H = q.shape
    n_blocks = -(-S // Q_BLOCK)
    qb = jnp.pad(q, ((0, n_blocks * Q_BLOCK - S), (0, 0)))
    qb = qb.reshape(n_blocks, Q_BLOCK, H)
    k_pos = jnp.arange(S)

    def one_block(blk):
        q_pos = blk * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.matmul(qb[blk], k.T) / math.sqrt(H)
        return jnp.matmul(
            _softmax_under_sink(s, _mask(q_pos, k_pos, window), sink), v)

    return jax.lax.map(one_block, jnp.arange(n_blocks)).reshape(
        n_blocks * Q_BLOCK, -1)[:S]


def _attention(h, a, positions, hf: dict, kind: str, quant):
    """The attention of one layer of ``kind`` on its normed input ``h`` [S,
    D] -> [S, N x Hv], before the output projection. ASSUMED (a): no q/k
    norm. Query head n reads K/V head n // (N / K); one head at a time: its
    columns of ``wq`` (rounding per output column, so the int8 control reads
    what the whole matrix would), the rotation, the softmax."""
    S, D = h.shape
    N, H, Hv = hf["num_attention_heads"], hf["head_dim"], hf["v_head_dim"]
    K = _kv_heads(hf, kind)
    theta = hf["swa_rope_theta" if kind == "window" else "rope_theta"]
    rot = int(H * hf["partial_rotary_factor"])
    window = hf["sliding_window"] if kind == "window" else None
    sink = _up(a["sink"]) if _has_sink(hf, kind) else None
    k = _rotate(_matmul(h, _up(a["wk"]), quant).reshape(S, K, H),
                positions, theta, rot)
    v = hf["attention_value_scale"] * _matmul(
        h, _up(a["wv"]), quant).reshape(S, K, Hv)

    def one_head(n):
        wq = jax.lax.dynamic_slice(a["wq"], (0, n * H), (D, H))
        q = _rotate(_matmul(h, _up(wq), quant)[:, None, :], positions,
                    theta, rot)[:, 0]
        g = n // (N // K)
        return _one_head(
            q, jax.lax.dynamic_index_in_dim(k, g, 1, keepdims=False),
            jax.lax.dynamic_index_in_dim(v, g, 1, keepdims=False), window,
            None if sink is None else sink[n])

    out = jax.lax.map(one_head, jnp.arange(N))              # [N, S, Hv]
    return out.transpose(1, 0, 2).reshape(S, N * Hv)


# -- feed-forward -------------------------------------------------------------


def _swiglu(x, p, quant):
    h = jax.nn.silu(_matmul(x, _up(p["w_gate"]), quant)) * _matmul(
        x, _up(p["w_in"]), quant)
    return _matmul(h, _up(p["w_out"]), quant)


def _dense(x, p, quant):
    """The dense MLP, ``ROW_BLOCK`` rows at a time (its hidden rows of a
    16384-token prompt are 2 x 1.07 GB in float32)."""
    S, D = x.shape
    n = -(-S // ROW_BLOCK)
    rows = jnp.pad(x, ((0, n * ROW_BLOCK - S), (0, 0))).reshape(n, ROW_BLOCK, D)
    return jax.lax.map(lambda r: _swiglu(r, p, quant), rows).reshape(
        n * ROW_BLOCK, D)[:S]


def _router(h, p, hf: dict):
    """-> (gates [S, E published], zero where an expert was not chosen; the
    margin [S]: on ``s + b``, what the choice is made on, the last expert
    chosen less the first left out). ASSUMED (e): float32 scores."""
    if (hf["scoring_func"], hf["topk_method"], hf["n_group"],
            hf["topk_group"]) != ("sigmoid", "noaux_tc", 1, 1):
        raise ValueError("the reference states sigmoid scores under a "
                         "selection bias and no group limit")
    k = hf["num_experts_per_tok"]
    s = jax.nn.sigmoid(jnp.matmul(h, _up(p["router"])))       # [S, E]
    ranked, idx = jax.lax.top_k(s + _up(p["router_bias"])[None, :], k + 1)
    margin = ranked[:, k - 1] - ranked[:, k]
    at = jnp.arange(h.shape[0])[:, None]
    top = s[at, idx[:, :k]]                                   # WITHOUT b
    if hf["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if hf["routed_scaling_factor"] is not None:
        top = top * hf["routed_scaling_factor"]
    return jnp.zeros_like(s).at[at, idx[:, :k]].set(top), margin


def _moe(h, p, hf: dict, quant, g=None):
    """(this chip's part of the layer's output, the router's margin [S]).
    ``g``: the layer's index in the stacks ``p`` holds (None: ``p`` is the
    layer's own); an expert's matrices are read out of the stack one expert
    at a time (a layer's 16 experts are 0.8 GB: no copy of them is made)."""
    router = _entry({k: p[k] for k in ("router", "router_bias")}, g)
    gates, margin = _router(h, router, hf)
    first, end = hf["deployment"]["experts_held"]
    E = hf["n_routed_experts"]
    assert end - first == p["w_in"].shape[-3] == E

    def matrix(name, e):
        w = p[name]
        at = (e, 0, 0) if g is None else (g, e, 0, 0)
        size = (1,) * (w.ndim - 2) + w.shape[-2:]
        return jax.lax.dynamic_slice(w, at, size).reshape(w.shape[-2:])

    def one_expert(y, eg):
        e, gate = eg
        out = _swiglu(h, {k: matrix(k, e)
                          for k in ("w_in", "w_gate", "w_out")}, quant)
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(one_expert, jnp.zeros_like(h),
                        (jnp.arange(E), gates[:, first:end].T))
    return y, margin


# -- the model ----------------------------------------------------------------


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the smallest router margin over the sparse layers at each of them."""
    eps = hf["layernorm_epsilon"]
    S = tokens.shape[0]
    positions = jnp.arange(S)
    margins = []
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["tokens"][tokens].astype(F32)
        for layer, (kind, ffn) in enumerate(_layers(hf)):
            node, g = _stack(params, hf, layer)
            node, x = _held_back(node, x)
            bp = _entry({k: v for k, v in node.items() if k != "moe"}, g)
            a = bp["attn"]
            h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
            o = _attention(h, a, positions, hf, kind, quant)
            x = x + _matmul(o, _up(a["wo"]), quant)
            h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
            if ffn == "dense":
                x = x + _dense(h, bp["mlp"], quant)
            else:
                y, margin = _moe(h, node["moe"], hf, quant, g)
                x = x + y
                margins.append(margin)
        head, x = _held_back(params["lm_head"], x[at])
        x = _rmsnorm(x, _up(params["final_norm"]["scale"]), eps)
        margin = (jnp.stack(margins).min(axis=0)[at] if margins
                  else jnp.full(x.shape[:1], jnp.inf))
        return _matmul(x, _up(head), quant), margin
