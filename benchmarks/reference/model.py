"""The plain reference: Mistral / Mixtral forward pass, loss and gradients in
straightforward ``jax.numpy`` and float32 at ``highest`` matmul precision.

No kernels, no cache, no batching tricks, no capacity: attention is the full
masked softmax (causal, with the sliding window where the configuration has
one), RoPE is the half-split ("rotate_half") form of the published
implementation, the MoE is dropless top-2 with gates renormalised over the
two chosen experts (every expert is computed for every token and weighted by
its gate, which is zero for the six not chosen). It follows the published
configuration keys (``hidden_size`` ...), not the program's config.

Departures, each on purpose: layers run under ``lax.scan`` over the stacked
weights, upcasting each matrix where it is used (a float32 copy of 7.5 GB of
weights, or of one 2.9 GB expert layer, does not fit beside them); heads run under ``lax.map`` so the [S, S] scores
of one head exist at a time; logits are taken only at the positions asked
for.

``quant="int8"`` is the CONTROL, not a reference: the same mathematics with
both operands of every weight matmul rounded to int8 (weights per output
channel, activations per token), forward and backward - the nearest
precision below bfloat16. It stands in the program's place to show that the
comparison can fail."""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _q(x, axis):
    """Symmetric int8 rounding along ``axis``: what an int8 matmul sees."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


@jax.custom_vjp
def _int8_matmul(x, w):
    """x [S, D] @ w [D, F] with both operands in int8 (activations per
    token, weights per output channel); the backward pass's two matmuls
    round their operands the same way."""
    return jnp.matmul(_q(x, -1), _q(w, 0))


def _int8_fwd(x, w):
    return _int8_matmul(x, w), (x, w)


def _int8_bwd(res, g):
    x, w = res
    dx = jnp.matmul(_q(g, -1), _q(w, 1).T)
    dw = jnp.matmul(_q(x, 0).T, _q(g, 0))
    return dx, dw


_int8_matmul.defvjp(_int8_fwd, _int8_bwd)


def _matmul(x, w, quant: Optional[str]):
    """x [S, D] @ w [D, F]."""
    if quant is None:
        return jnp.matmul(x, w)
    if quant == "int8":
        return _int8_matmul(x, w)
    raise ValueError(f"unknown control precision {quant!r}")


def _up(w):
    """Upcast a weight where it is used, so that no more than one matrix of
    one layer (one expert) exists in float32 at a time."""
    return w.astype(F32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _rope(x, positions, theta):
    """x [S, n, H]; half-split rotation (HF ``rotate_half``)."""
    H = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, H, 2, dtype=F32) / H))
    ang = positions.astype(F32)[:, None] * inv[None, :]       # [S, H/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window: Optional[int]):
    """q [S, N, H], k/v [S, K, H] -> [S, N, H]; full masked softmax, one
    query head at a time."""
    S, N, H = q.shape
    K = k.shape[1]
    pos = jnp.arange(S)
    mask = pos[None, :] <= pos[:, None]
    if window is not None:
        mask = mask & (pos[:, None] - pos[None, :] < window)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                                     # [S, H]
        s = jnp.matmul(qh, kh.T) / (H ** 0.5)
        s = jnp.where(mask, s, -jnp.inf)
        return jnp.matmul(jax.nn.softmax(s, axis=-1), vh)

    rep = N // K
    kq = jnp.repeat(k, rep, axis=1)
    vq = jnp.repeat(v, rep, axis=1)
    out = jax.lax.map(
        one_head,
        (q.transpose(1, 0, 2), kq.transpose(1, 0, 2), vq.transpose(1, 0, 2)),
    )
    return out.transpose(1, 0, 2)


def _dense_mlp(x, p, quant):
    h = jax.nn.silu(_matmul(x, _up(p["w_gate"]), quant)) * _matmul(
        x, _up(p["w_in"]), quant)
    return _matmul(h, _up(p["w_out"]), quant)


def _moe(x, p, hf, quant):
    """Dropless top-k: softmax over all experts, keep the k largest,
    renormalise over them (Mixtral's published routing). Returns the
    output and, per token, the router's MARGIN: the logit of the last expert
    chosen less that of the first one left out. Where it is near 0 a
    rounding error elsewhere picks another expert."""
    k = hf["num_experts_per_tok"]
    router_logits = jnp.matmul(x, _up(p["router"]))                   # [S, E]
    ranked = jax.lax.top_k(router_logits, k + 1)[0]
    margin = ranked[:, k - 1] - ranked[:, k]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top, idx = jax.lax.top_k(probs, k)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(x.shape[0])[:, None], idx
    ].set(top / jnp.sum(top, axis=-1, keepdims=True))

    def one_expert(y, ew):
        w_in, w_gate, w_out, g = ew
        h = jax.nn.silu(_matmul(x, _up(w_gate), quant)) * _matmul(
            x, _up(w_in), quant)
        return y + g[:, None] * _matmul(h, _up(w_out), quant), None

    y, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (p["w_in"], p["w_gate"], p["w_out"], gates.T),
    )
    return y, margin


def param_spec(hf: dict) -> dict:
    """The tree this model reads, in the layout the program's model reads
    (``embed.tokens``, ``blocks.attn.wq`` stacked over layers, ...):
    {path: (shape, kind)}; kind is 'normal', 'resid' or 'norm'
    (``weights.py`` draws them)."""
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


def hidden_states(params, tokens, hf: dict, quant: Optional[str] = None,
                  whole=lambda tree, where: tree):
    """tokens [S] -> (final-norm input [S, D] in float32, router margins
    [layers, S] or, for a dense model, None). ``whole`` makes a sub-tree of
    weights whole where it is about to be used (the identity on one chip;
    see ``loss`` for several)."""
    D = hf["hidden_size"]
    N, K = hf["num_attention_heads"], hf["num_key_value_heads"]
    H = hf.get("head_dim") or D // N
    eps, theta = hf["rms_norm_eps"], hf["rope_theta"]
    window = hf.get("sliding_window")
    S = tokens.shape[0]
    positions = jnp.arange(S)
    x = whole(params["embed"], "embed")["tokens"][tokens].astype(F32)

    @jax.checkpoint
    def layer(x, bp):
        bp = whole(bp, "blocks")
        h = _rmsnorm(x, _up(bp["attn_norm"]["scale"]), eps)
        a = bp["attn"]
        q = _rope(_matmul(h, _up(a["wq"]), quant).reshape(S, N, H),
                  positions, theta)
        k = _rope(_matmul(h, _up(a["wk"]), quant).reshape(S, K, H),
                  positions, theta)
        v = _matmul(h, _up(a["wv"]), quant).reshape(S, K, H)
        o = _attention(q, k, v, window).reshape(S, N * H)
        x = x + _matmul(o, _up(a["wo"]), quant)
        h = _rmsnorm(x, _up(bp["mlp_norm"]["scale"]), eps)
        if "moe" in bp:
            y, margin = _moe(h, bp["moe"], hf, quant)
            return x + y, margin
        return x + _dense_mlp(h, bp["mlp"], quant), None

    return jax.lax.scan(layer, x, params["blocks"])


def _head(params, hf):
    if hf.get("tie_word_embeddings", False):
        return params["embed"]["tokens"].astype(F32).T
    return params["lm_head"].astype(F32)


def logits_at(params, tokens, at, hf: dict, quant: Optional[str] = None):
    """Float32 logits [len(at), V] of one sequence at positions ``at``, and
    the smallest router margin over the layers at each of them."""
    with jax.default_matmul_precision("highest"):
        x, margins = hidden_states(params, tokens, hf, quant)
        x = _rmsnorm(x[at], params["final_norm"]["scale"].astype(F32),
                     hf["rms_norm_eps"])
        margin = (jnp.full(x.shape[:1], jnp.inf) if margins is None
                  else margins.min(axis=0)[at])
        return _matmul(x, _head(params, hf), quant), margin


def _loss_sum(params, inputs, targets, hf, quant, whole):
    """Summed next-token cross-entropy of the sequences [B, S] given."""
    head = _head({k: whole(v, k) for k, v in params.items()
                  if k in ("embed", "lm_head")}, hf)
    scale = whole(params["final_norm"], "final_norm")["scale"].astype(F32)

    def one(args):
        inp, tgt = args
        x = _rmsnorm(hidden_states(params, inp, hf, quant, whole)[0], scale,
                     hf["rms_norm_eps"])
        lg = _matmul(x, head, quant)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(lg, tgt[:, None], axis=-1)[:, 0])

    return jnp.sum(jax.lax.map(one, (inputs, targets)))


def loss(params, inputs, targets, hf: dict, quant: Optional[str] = None,
         mesh=None, param_specs=None, batch_spec=None):
    """Mean next-token cross-entropy over a batch [B, S] (dense models; the
    router's auxiliary loss is not part of this reference).

    On one chip that is all. Where the weights are sharded over a ``mesh``
    (``param_specs``: each leaf's PartitionSpec) and the batch is split over
    it (``batch_spec``), every chip runs the SAME plain computation on its
    own sequences: it gathers one layer's weights whole where the layer
    starts, and the sums are added up. Left to the compiler, the whole stack
    is gathered before the layer loop and does not fit."""
    if hf.get("num_local_experts"):
        raise ValueError("the training reference covers dense models only")
    n = inputs.shape[0] * inputs.shape[1]
    with jax.default_matmul_precision("highest"):
        if mesh is None:
            return _loss_sum(params, inputs, targets, hf, quant,
                             lambda tree, where: tree) / n
        from jax.sharding import PartitionSpec as P

        def whole(tree, where):
            specs = param_specs[where] if isinstance(where, str) else where
            lead = 1 if where == "blocks" else 0      # the scanned layer axis

            def gather(x, spec):
                for dim, axes in enumerate(tuple(spec)[lead:]):
                    if axes is not None:
                        x = jax.lax.all_gather(x, axes, axis=dim, tiled=True)
                return x

            return jax.tree.map(gather, tree, specs)

        batch_axes = tuple(
            a for axes in tuple(batch_spec) if axes is not None
            for a in ((axes,) if isinstance(axes, str) else axes))

        def local(p, inp, tgt):
            total = _loss_sum(p, inp, tgt, hf, quant, whole)
            return jax.lax.psum(total, batch_axes)

        return jax.shard_map(
            local, mesh=mesh, in_specs=(param_specs, batch_spec, batch_spec),
            out_specs=P(),
        )(params, inputs, targets) / n
